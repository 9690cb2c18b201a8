"""Share of the traced sub-window in which no operation ran on the device
(%)."""


def read(rec):
    red = rec.get("trace")
    if not red or not red["window_s"]:
        return None
    return (1.0 - red["busy_s"] / red["window_s"]) * 100.0
