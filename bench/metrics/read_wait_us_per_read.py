"""Host microseconds a device read waits for its result, the copy to the
host included (``arena.read.wait`` over ``arena.reads``)."""


def read(rec):
    prof = rec["profile"]
    if "arena.read.wait" not in prof or "arena.reads" not in prof:
        return None
    return prof["arena.read.wait"]["seconds"] / prof["arena.reads"]["count"] * 1e6
