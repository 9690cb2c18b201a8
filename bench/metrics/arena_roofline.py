"""Share of the HBM roofline the arena's programs reach in the traced
sub-window (%): the bytes the ``DeviceArena`` calls asked to move there
(runs read, runs written, device copies into primary and mirrors), at the
chip's peak HBM bandwidth, over the device's busy time in that window.
Nothing when the trace holds no device time."""


def read(rec):
    red, moved = rec.get("trace"), rec.get("traced_bytes")
    if not red or not red["busy_s"] or not moved:
        return None
    peak = rec["peaks"][rec["device_kind"]]["hbm_bytes_per_s"]
    return moved / peak / red["busy_s"] * 100.0
