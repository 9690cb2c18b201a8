"""Host microseconds per op that the device arena spends building indices
and padding and in its jitted calls until they return (every
``arena.*.prep`` and ``arena.*.dispatch`` span): its cost apart from
waiting for the device."""

from program_spans import arena_host_seconds


def read(rec):
    s = arena_host_seconds(rec["profile"])
    return None if s is None else s / rec["ops"] * 1e6
