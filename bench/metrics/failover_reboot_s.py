"""Seconds inside ``NVMBackend.reboot`` in the window: the promoted blade
rebuilds its naming table, allocation bitmap and log areas from its bytes
and replays committed memory logs.  Nothing where no blade rebooted, or
where the failover spans were not installed."""


def read(rec):
    d0, d1 = rec["span0"], rec["span1"]
    if d1.get("calls.reboot", 0) == d0.get("calls.reboot", 0):
        return None
    return d1["seconds.reboot"] - d0["seconds.reboot"]
