"""Seconds from a blade's failure to the first completion after it of an op
whose key lies on a shard the blade held: how long the lost shards serve
nothing.  That op is served by the store call that finds the failure and
runs the mirror's promotion inline, whether it was due before the failure
or after.  Nothing in a run without a fault, or where no such op completed
after it."""

import numpy as np


def read(rec):
    f = rec.get("fault")
    if not f:
        return None
    done = rec["done_s"][f["lost"]]
    after = done[np.isfinite(done) & (done >= f["t"])]
    return float(after.min() - f["t"]) if len(after) else None
