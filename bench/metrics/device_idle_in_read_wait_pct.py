"""Share of the traced sub-window in which the device is idle while the
host waits for a device read (%): idle time whose innermost program span is
``arena.read.wait`` or its child ``arena.read.copy``.  Nothing where the
run has no device trace or the program writes no spans of its own."""

from program_spans import traced_idle


def read(rec):
    idle = traced_idle(rec)
    if idle is None:
        return None
    wait = sum(s for label, s in idle if label in ("arena.read.wait", "arena.read.copy"))
    return wait / rec["trace"]["window_s"] * 100.0
