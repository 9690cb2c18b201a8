"""Host microseconds per op spent inside ``DeviceArena`` methods (reads,
staged-write flushes, device copies, clones), waiting on the device and
the transfers."""


def read(rec):
    d0, d1 = rec["span0"], rec["span1"]
    return (d1["arena_s"] - d0["arena_s"]) / rec["ops"] * 1e6
