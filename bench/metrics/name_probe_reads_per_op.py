"""Device reads per op that probe a name slot of a blade (``reads.name_probe``:
``NVMBackend.get_name``), above all the committed-watermark probe of each
cached shard a store call visits."""

from program_spans import reads_by_cause


def read(rec):
    causes = reads_by_cause(rec["profile"])
    return None if causes is None else causes["name_probe"] / rec["ops"]
