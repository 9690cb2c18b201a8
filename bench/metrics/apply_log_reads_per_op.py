"""Device reads per op that read a memory log back for the blade's
group-commit apply (``reads.apply_log``: ``NVMBackend.tx_apply``)."""

from program_spans import reads_by_cause


def read(rec):
    causes = reads_by_cause(rec["profile"])
    return None if causes is None else causes["apply_log"] / rec["ops"]
