"""Host microseconds per op in the front-end's group commits
(``fe.group_commit``: op-log and memory-log append, watermark, the blade's
apply into primary and mirrors, log compaction)."""


def read(rec):
    s = rec["profile"].get("fe.group_commit", {}).get("seconds")
    return None if s is None else s / rec["ops"] * 1e6
