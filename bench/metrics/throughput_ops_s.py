"""Ops completed inside the measured window, over its length (ops/s)."""


def read(rec):
    return rec["completed_in_window"] / rec["seconds"]
