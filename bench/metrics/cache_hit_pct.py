"""Front-end page-cache hits over lookups in the window (%), summed over
every per-blade front-end, those retired by a failover rebind included."""


def read(rec):
    s0, s1 = rec["stats0"], rec["stats1"]
    hits = s1.get("cache_hits", 0) - s0.get("cache_hits", 0)
    misses = s1.get("cache_misses", 0) - s0.get("cache_misses", 0)
    if hits + misses <= 0:
        return None
    return hits / (hits + misses) * 100.0
