"""``DeviceArena.read_runs`` calls per op: each one device-to-host round
trip."""


def read(rec):
    d0, d1 = rec["span0"], rec["span1"]
    return (d1["calls.read_runs"] - d0["calls.read_runs"]) / rec["ops"]
