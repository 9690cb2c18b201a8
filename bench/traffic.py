"""Seeded records and open-loop YCSB traffic for one benchmark cell.

Everything a run sends is drawn here from ``--seed`` before the first op:
the loaded records, the extra keys that inserts add, and for the warm-up
and the measured window each op's due time, kind, key, value and scan
length.  The generator reads one traffic file (``bench/workloads/*.json``)
and knows no cell by name.

A configuration with a ``record`` group, ``{"fieldcount": F,
"fieldlength": L}``, has YCSB's records (``CoreWorkload``): each value is
``F x L`` random bytes, held as one ``uint8`` array of rows.  An update
then carries, as YCSB's default ``writeallfields=false`` has it, one field
index drawn uniformly from ``[0, F)`` and ``L`` new bytes; an insert
carries a whole record.  These come from generators spawned after the
8-byte value's, so the streams of a configuration without ``record`` are
what they were.

The zipfian sampler is YCSB's (Cooper et al., SoCC 2010; Gray et al.'s
inverse CDF over the exact zeta sum), copied from ``benchmarks/keydist.py``.
Ranks map to keys through a seeded permutation of the loaded keys, so every
rank names a distinct loaded key (``keydist.zipf_keys`` hashes ranks modulo
the keyspace, which collides).

Arrivals are a Poisson process conditioned on its count: ``rate x seconds``
ops, their due times sorted uniform draws over the window, drawn from one
fixed stream (``ARRIVALS``) and not from the seed.  The op kinds come in
exact proportions and scan lengths as one fixed set of sizes, in a seeded
order.  So every seed offers the same amount and kind of work at the same
instants, and seeds differ only in which keys and in what order: at a
tail's load, arrival bursts drawn anew per seed would move the tail more
than any change of the store.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

READ, UPDATE, SCAN, INSERT = "read", "update", "scan", "insert"
KINDS = (READ, UPDATE, SCAN, INSERT)
KEY_LO, KEY_HI = 1, 1 << 62          # 8-byte keys, as chip_smoke.py draws them
VAL_LO, VAL_HI = -(1 << 62), 1 << 62
ARRIVALS = 0x5EED                    # the one stream every seed's due times come from


def rngs(seed: int, n: int) -> List[np.random.Generator]:
    """`n` independent generators from one seed (any whole number)."""
    ss = np.random.SeedSequence(seed % (1 << 64))
    return [np.random.default_rng(s) for s in ss.spawn(n)]


# ------------------------------------------------------------ key draws
def _zeta(n: int, theta: float) -> np.ndarray:
    """Cumulative generalized harmonic numbers ``H_{k,theta}``, k = 1..n."""
    return np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -theta)


def zipf_ranks(rng: np.random.Generator, n: int, keyspace: int,
               theta: float) -> np.ndarray:
    """`n` zipfian ranks in ``[0, keyspace)``; rank k has weight (k+1)^-theta."""
    if not 0.0 < theta < 1.0:
        raise ValueError("theta must be in (0, 1) (YCSB convention)")
    zeta = _zeta(keyspace, theta)
    u = rng.random(n) * zeta[-1]
    return np.minimum(np.searchsorted(zeta, u, side="left"), keyspace - 1)


def key_ranks(rng: np.random.Generator, n: int, keyspace: int,
              spec: Dict) -> np.ndarray:
    dist = spec["distribution"]
    if dist == "zipfian":
        return zipf_ranks(rng, n, keyspace, float(spec["theta"]))
    if dist == "uniform":
        return rng.integers(0, keyspace, size=n)
    raise ValueError(f"unknown key distribution {dist!r}")


def exact_mix(rng: np.random.Generator, n: int, mix: Dict[str, float]) -> np.ndarray:
    """`n` op kinds in the mix's exact proportions (largest remainder), in
    a seeded order; codes index ``KINDS``."""
    kinds = sorted(mix)
    for k in kinds:
        if k not in KINDS:
            raise ValueError(f"unknown op kind {k!r}")
    share = np.array([mix[k] for k in kinds], np.float64)
    share = share / share.sum() * n
    counts = np.floor(share).astype(np.int64)
    for i in np.argsort(counts - share)[: n - int(counts.sum())]:
        counts[i] += 1
    codes = np.repeat([KINDS.index(k) for k in kinds], counts)
    return rng.permutation(codes)


def arrival_times(n: int, seconds: float) -> np.ndarray:
    """`n` due times over `seconds`, ascending: a Poisson process
    conditioned on its count, the same for every seed."""
    return np.sort(np.random.default_rng(ARRIVALS).random(n) * seconds)


def stratified_lengths(rng: np.random.Generator, n: int, lo: int, hi: int) -> np.ndarray:
    """`n` scan lengths uniform on ``[lo, hi]`` as one fixed set (evenly
    spaced quantiles), in a seeded order."""
    q = (np.arange(n) + 0.5) / max(n, 1)
    return rng.permutation(lo + np.floor(q * (hi - lo + 1)).astype(np.int64))


# ------------------------------------------------------------- records
@dataclass(frozen=True)
class RecordShape:
    """YCSB's record: `fieldcount` fields of `fieldlength` bytes each."""
    fieldcount: int
    fieldlength: int

    @property
    def width(self) -> int:
        return self.fieldcount * self.fieldlength

    def field(self, f: int) -> slice:
        return slice(f * self.fieldlength, (f + 1) * self.fieldlength)


def record_shape(config: Dict) -> Optional[RecordShape]:
    """The configuration's record shape, None for 8-byte integer values.
    Keys are 8-byte integers either way; a ``record`` group has to state
    exactly ``value_bytes``."""
    if config["key_bytes"] != 8:
        raise ValueError("the store keeps 8-byte integer keys")
    group = config.get("record")
    if group is None:
        if config["value_bytes"] != 8:
            raise ValueError("values other than 8-byte integers need a record group")
        return None
    if set(group) != {"fieldcount", "fieldlength"}:
        raise ValueError(f"a record group has fieldcount and fieldlength, not {sorted(group)}")
    shape = RecordShape(int(group["fieldcount"]), int(group["fieldlength"]))
    if shape.fieldcount < 1 or shape.fieldlength < 1 or shape.width != config["value_bytes"]:
        raise ValueError(f"value_bytes {config['value_bytes']} is not fieldcount x "
                         f"fieldlength = {shape.fieldcount} x {shape.fieldlength}")
    return shape


@dataclass
class Records:
    keys: np.ndarray       # loaded keys, int64, in load order
    values: np.ndarray     # their values: int64, or uint8 rows of `shape.width` bytes
    extra: np.ndarray      # fresh keys for inserts, none of them loaded
    perm: np.ndarray       # rank -> index into `keys` (point ops) / `sorted_keys` (scans)
    sorted_keys: np.ndarray
    shape: Optional[RecordShape] = None


def make_records(seed: int, n: int, n_extra: int,
                 shape: Optional[RecordShape] = None) -> Records:
    if shape is not None:
        r_keys, _, r_perm, r_rows = rngs(seed, 4)
    else:
        r_keys, r_vals, r_perm = rngs(seed, 3)
    want = n + n_extra
    keys = np.empty(0, np.int64)
    while len(keys) < want:
        draw = r_keys.integers(KEY_LO, KEY_HI, size=want + want // 64 + 64, dtype=np.int64)
        keys = np.unique(np.concatenate([keys, draw]))
    keys = r_keys.permutation(keys)[:want]
    if shape is not None:
        vals = r_rows.integers(0, 256, size=(n, shape.width), dtype=np.uint8)
    else:
        vals = r_vals.integers(VAL_LO, VAL_HI, size=n, dtype=np.int64)
    return Records(keys=keys[:n], values=vals, extra=keys[n:],
                   perm=r_perm.permutation(n), sorted_keys=np.sort(keys[:n]),
                   shape=shape)


# ------------------------------------------------------------ op stream
@dataclass
class Ops:
    due: np.ndarray        # seconds from the start of the phase, ascending
    kind: np.ndarray       # index into KINDS
    key: np.ndarray        # read/update/insert: the key; scan: low key
    hi: np.ndarray         # scan: high key (inclusive); else 0
    value: np.ndarray      # 8-byte values: an update's or insert's value; else 0
    # records only: the field an update replaces, -1 for a whole record
    # (and for reads and scans), and the new bytes at their place in a
    # record's row, uint8 [n, width]
    field: Optional[np.ndarray] = None
    data: Optional[np.ndarray] = None
    shape: Optional[RecordShape] = None

    def __len__(self) -> int:
        return len(self.due)


def make_ops(traffic: Dict, rec: Records, seed: int, seconds: float,
             first_extra: int = 0) -> Ops:
    """The ops due in a phase of `seconds` at the traffic's rate.  Inserts
    take ``rec.extra[first_extra:]`` in order, so phases of one run never
    insert the same key twice."""
    _, r_kind, r_key, r_val, r_len = rngs(seed, 5)
    n = int(round(float(traffic["rate_ops_s"]) * seconds))
    due = arrival_times(n, seconds)
    kind = exact_mix(r_kind, n, traffic["ops"])
    n_keys = len(rec.keys)
    idx = rec.perm[key_ranks(r_key, n, n_keys, traffic["keys"])]
    key = rec.keys[idx].copy()
    hi = np.zeros(n, np.int64)
    value = r_val.integers(VAL_LO, VAL_HI, size=n, dtype=np.int64)
    scans = np.flatnonzero(kind == KINDS.index(SCAN))
    if len(scans):
        lo_len, hi_len = traffic["scan_length"]
        lens = stratified_lengths(r_len, len(scans), int(lo_len), int(hi_len))
        start = idx[scans]
        key[scans] = rec.sorted_keys[start]
        hi[scans] = rec.sorted_keys[np.minimum(start + lens - 1, n_keys - 1)]
    ins = np.flatnonzero(kind == KINDS.index(INSERT))
    if first_extra + len(ins) > len(rec.extra):
        raise ValueError("traffic inserts more keys than the records provide")
    key[ins] = rec.extra[first_extra:first_extra + len(ins)]
    value[kind == KINDS.index(READ)] = 0
    value[scans] = 0
    ops = Ops(due=due, kind=kind, key=key, hi=hi, value=value)
    if rec.shape is not None:
        _record_writes(ops, rec.shape, *rngs(seed, 7)[5:])
    return ops


def _record_writes(ops: Ops, shape: RecordShape,
                   r_field: np.random.Generator, r_bytes: np.random.Generator) -> None:
    """Each write's new bytes: an update's one field (uniform over the
    fields) and its `fieldlength` bytes, an insert's whole record."""
    n, width, length = len(ops), shape.width, shape.fieldlength
    one = ops.kind == KINDS.index(UPDATE)
    whole = ops.kind == KINDS.index(INSERT)
    field = np.full(n, -1, np.int64)
    field[one] = r_field.integers(0, shape.fieldcount, size=int(one.sum()))
    data = np.zeros((n, width), np.uint8)
    rows = np.flatnonzero(one)
    cols = field[rows, None] * length + np.arange(length)
    data[rows[:, None], cols] = r_bytes.integers(0, 256, size=(len(rows), length),
                                                 dtype=np.uint8)
    data[whole] = r_bytes.integers(0, 256, size=(int(whole.sum()), width), dtype=np.uint8)
    ops.field, ops.data, ops.shape = field, data, shape


def inserts_needed(traffic: Dict, seconds: float) -> int:
    n = int(round(float(traffic["rate_ops_s"]) * seconds))
    share = traffic["ops"].get(INSERT, 0.0) / sum(traffic["ops"].values())
    return int(np.ceil(share * n)) + 1
