"""Seconds inside ``ClusterFrontEnd.recover_blade`` in the window, less its
clones and reboot: lease revocation, the directory's persist, retiring the
front-ends, ``ensure_fresh`` and the op-log tail replay.  Nothing where the
store recovered no blade, or where the failover spans were not installed."""


def read(rec):
    d0, d1 = rec["span0"], rec["span1"]
    if d1.get("calls.recover_blade", 0) == d0.get("calls.recover_blade", 0):
        return None
    return sum((d1[f"seconds.{m}"] - d0[f"seconds.{m}"]) * sign for m, sign in
               (("recover_blade", 1), ("clone", -1), ("reboot", -1)))
