"""Pinned seed-0 streams of bench/traffic.py and the properties that keep
runs of different seeds doing the same amount of work."""

import numpy as np

import traffic as tr

POINT = {"rate_ops_s": 100, "ops": {"read": 0.95, "update": 0.05},
         "keys": {"distribution": "zipfian", "theta": 0.99}}
SCAN = {"rate_ops_s": 100, "ops": {"scan": 0.95, "insert": 0.05},
        "keys": {"distribution": "zipfian", "theta": 0.99}, "scan_length": [1, 100]}


def test_seed0_records_pinned():
    rec = tr.make_records(0, 16, 4)
    assert rec.keys[:4].tolist() == [1965686933980468306, 4248707272725694576,
                                     3489516779801214338, 4115226151236066865]
    assert rec.values[:2].tolist() == [1634352535642710362, -2370528836591267360]
    assert rec.extra.tolist() == [1670576108680466073, 2870219498288692276,
                                  1145145563597835207, 414932278923200520]
    assert rec.perm[:6].tolist() == [11, 4, 15, 14, 12, 10]


def test_seed0_point_ops_pinned():
    rec = tr.make_records(0, 16, 4)
    ops = tr.make_ops(POINT, rec, 0, 0.2)
    assert len(ops) == 20
    assert ops.due[:3].tolist() == [0.00718480746981296, 0.008180882859646355,
                                    0.011144131758950439]
    assert ops.kind.tolist() == [0] * 15 + [1] + [0] * 4
    assert ops.key[:3].tolist() == [1965686933980468306, 2851028651449022731,
                                    1050781144990175324]
    assert ops.value[ops.kind == 1].tolist() == [-1514254446665799629]


def test_seed0_scan_ops_pinned():
    rec = tr.make_records(0, 16, 4)
    ops = tr.make_ops(SCAN, rec, 0, 0.2)
    assert ops.kind.tolist() == [2] * 18 + [3, 2]
    assert ops.key[:3].tolist() == [390599848552479275, 3776588247041756829,
                                    4115226151236066865]
    assert ops.hi[:3].tolist() == [4324200499306433866] * 3
    assert ops.key[ops.kind == 3].tolist() == [1670576108680466073]


def test_every_seed_offers_the_same_work():
    rec = tr.make_records(3, 4096, 64)
    runs = [tr.make_ops(SCAN, rec, seed, 5.0) for seed in (1, 2, 2**33 + 5)]
    for ops in runs:
        assert len(ops) == 500
        assert ops.due.tolist() == runs[0].due.tolist()   # one arrival schedule
        assert np.all(np.diff(ops.due) >= 0) and ops.due[-1] < 5.0
        assert np.sum(ops.kind == tr.KINDS.index(tr.INSERT)) == 25
    lens = [np.sort(np.searchsorted(rec.sorted_keys, o.hi[o.kind == 2])
                    - np.searchsorted(rec.sorted_keys, o.key[o.kind == 2]))
            for o in runs]
    # the same set of scan lengths, apart from scans cut at the last key
    assert abs(int(lens[0].sum()) - int(lens[1].sum())) < 0.02 * lens[0].sum()


def test_zipfian_is_skewed_and_inserts_are_fresh():
    rec = tr.make_records(5, 4096, 200)
    ops = tr.make_ops(POINT, rec, 9, 20.0)
    _, counts = np.unique(ops.key, return_counts=True)
    top = np.sort(counts)[::-1]
    assert top[:410].sum() > 0.6 * len(ops)      # hottest 10% of keys
    assert set(rec.extra.tolist()).isdisjoint(rec.keys.tolist())
    scan = tr.make_ops(SCAN, rec, 9, 2.0, first_extra=3)
    ins = scan.key[scan.kind == tr.KINDS.index(tr.INSERT)]
    assert ins.tolist() == rec.extra[3:3 + len(ins)].tolist()
