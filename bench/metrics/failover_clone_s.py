"""Seconds inside ``DeviceArena.clone`` in the window, each clone's device
copy waited for: a mirror promotion's two whole-arena copies, the promoted
primary's arena and its re-seeded mirror.  Nothing where no clone ran."""


def read(rec):
    d0, d1 = rec["span0"], rec["span1"]
    if d1["calls.clone"] == d0["calls.clone"]:
        return None
    return d1["seconds.clone"] - d0["seconds.clone"]
