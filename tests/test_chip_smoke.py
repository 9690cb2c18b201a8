"""``chip_smoke.py`` rehearsed at a tiny size: the same phases, on the
default JAX device of the test run, with every answer checked."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_phases_answer_like_the_reference_at_tiny_size():
    lines = []
    counts = chip_smoke.run_phases(records=300, queries=100, scans=10,
                                   capacity=1 << 24, seed=3, log=lines.append)
    assert counts["hash get_many"] == 100
    assert counts["bptree get_many"] == 100
    assert counts["range_scan rows"] > 10
    assert counts["hash after failover"] == 300
    assert counts["bptree after failover"] == 300
    phases = [ln.split(":")[0] for ln in lines if ln.startswith("phase ")]
    assert phases == ["phase build", "phase load", "phase query", "phase failover"]
    assert lines[0].startswith("records per structure: 300")
    assert "compiled arena programs" in lines[1]
    assert lines[-1].startswith("answers compared:")


def test_a_wrong_answer_fails_the_run():
    assert chip_smoke._check("same", [1, None], [1, None]) == 2
    with pytest.raises(chip_smoke.SmokeMismatch, match="first difference at 1"):
        chip_smoke._check("diff", [1, 2], [1, None])


def test_refuses_to_run_without_a_tpu(capsys):
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err
