"""95th-percentile due-to-completion latency over every op due in the
window (ms); a failed op counts as slowest."""

import math

import numpy as np


def read(rec):
    v = np.sort(rec["latency_s"])
    return float(v[max(0, math.ceil(0.95 * len(v)) - 1)]) * 1e3
