"""Median due-to-completion latency over every op due in the window (ms);
a failed op counts as slowest."""

import numpy as np


def read(rec):
    v = np.sort(rec["latency_s"])
    return float(v[(len(v) - 1) // 2]) * 1e3
