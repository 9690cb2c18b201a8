"""Host microseconds per op inside the store calls, less the time inside
``DeviceArena`` methods: the front-end's own Python (sharded routing,
structure walks, page cache, logs)."""


def read(rec):
    d0, d1 = rec["span0"], rec["span1"]
    host = (d1["store_s"] - d0["store_s"]) - (d1["arena_s"] - d0["arena_s"])
    return host / rec["ops"] * 1e6
