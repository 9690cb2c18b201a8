"""Host microseconds per op in the blade's group-commit apply: decoding the
memory log (``log_decode``) and copying its runs to the data area of the
primary and mirrors (``apply_phase``), as ``repro.obs.profile`` times them."""


def read(rec):
    prof = rec["profile"]
    s = sum(prof.get(k, {}).get("seconds", 0.0) for k in ("apply_phase", "log_decode"))
    if not s:
        return None
    return s / rec["ops"] * 1e6
