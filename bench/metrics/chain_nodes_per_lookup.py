"""Hash-chain nodes compared per key walked (counters ``hash.chain_nodes`` and
``hash.lookups``: ``RemoteHashTable._lookup`` and ``_stage_chains``), the
length of the chain walks that set how many read waves a batch takes."""


def read(rec):
    prof = rec["profile"]
    lookups = prof.get("hash.lookups", {}).get("count")
    if not lookups:
        return None
    return prof.get("hash.chain_nodes", {}).get("count", 0) / lookups
