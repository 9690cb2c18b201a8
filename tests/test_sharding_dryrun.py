"""Distribution plumbing on a miniature mesh, run in subprocesses so the
fake-device XLA flag never leaks into other tests (the suite sees 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_mini_mesh_train_lower_compile_and_collectives():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses, json
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models import DecoderLM, abstract_params, make_shardings
        from repro.launch.mesh import rules_for
        from repro.launch.analysis import parse_collectives
        from repro.training import TrainConfig, make_train_step, init_train_state

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke_config("llama3.2-3b", fsdp=True, scan_layers=False)
        rules = rules_for(cfg, mesh, kind="train")
        model = DecoderLM(cfg)
        tcfg = TrainConfig()
        state = init_train_state(model, jax.random.PRNGKey(0), tcfg)
        batch = model.sample_inputs(4, 32)
        fn = make_train_step(model, tcfg, rules, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn).lower(state, batch)
            compiled = lowered.compile()
        colls = parse_collectives(compiled.as_text())
        assert "all-reduce" in colls, colls  # DP/TP reductions must exist
        # and it actually RUNS on the fake 8-device mesh
        with jax.set_mesh(mesh):
            new_state, metrics = jax.jit(fn)(state, batch)
        loss = float(metrics["loss"])
        assert loss == loss and loss > 0
        print("OK", json.dumps({k: v["count"] for k, v in colls.items()}))
    """)
    out = _run(code)
    assert "OK" in out


def test_mini_mesh_moe_ep_a2a_runs():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.models import DecoderLM
        from repro.models.moe import moe_apply, moe_specs
        from repro.models.params import init_params
        from repro.launch.analysis import parse_collectives

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke_config("kimi-k2-1t-a32b", dtype="float32")
        # 8 experts over model=4: EP path; generous capacity for exactness
        m = dataclasses.replace(cfg.moe, impl="ep_a2a", capacity_factor=8.0)
        cfg_a2a = dataclasses.replace(cfg, moe=m)
        cfg_dense = dataclasses.replace(cfg, moe=dataclasses.replace(m, impl="dense"))
        specs = moe_specs(cfg_dense)
        p = init_params(specs, jax.random.PRNGKey(1))
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 16, cfg.d_model))
        y_dense = moe_apply(p, x, cfg_dense, {}, mesh=mesh)
        f = jax.jit(lambda p, x: moe_apply(p, x, cfg_a2a, {}, mesh=mesh))
        with jax.set_mesh(mesh):
            lowered = f.lower(p, x)
            compiled = lowered.compile()
            y_a2a = f(p, x)
        colls = parse_collectives(compiled.as_text())
        assert "all-to-all" in colls, colls
        err = float(jnp.max(jnp.abs(y_a2a - y_dense)))
        assert err < 2e-4, err
        print("OK a2a matches dense on 2x4 mesh, err", err)
    """)
    out = _run(code)
    assert "OK" in out


def test_mini_mesh_decode_and_seq_parallel_attention():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.models import DecoderLM
        from repro.launch.mesh import rules_for

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        # 3 heads: NOT divisible by model=4 -> sequence-parallel rules
        cfg = get_smoke_config("llama3.2-3b", n_heads=3, n_kv_heads=3, head_dim=32,
                               d_model=96, d_ff=128, dtype="float32")
        rules = rules_for(cfg, mesh, kind="train")
        assert rules["act_heads"] is None and rules["act_seq"] == "model"
        model = DecoderLM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = model.sample_inputs(4, 32)
        with jax.set_mesh(mesh):
            loss = jax.jit(lambda p, b: model.loss(p, b, rules, mesh))(params, batch)
        assert bool(jnp.isfinite(loss))
        # decode rules shard the cache length axis instead
        drules = rules_for(cfg, mesh, kind="decode")
        assert drules["act_cache_len"] == "model"
        logits, cache = model.prefill(params, {"tokens": batch["tokens"][:, :16]})
        l2, cache = model.decode_step(params, cache, batch["tokens"][:, 16],
                                      drules, mesh)
        assert bool(jnp.all(jnp.isfinite(l2)))
        print("OK seq-parallel attention + sharded decode")
    """)
    out = _run(code)
    assert "OK" in out


def test_multi_pod_mesh_shape():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        assert m1.devices.shape == (16, 16) and m1.axis_names == ("data", "model")
        m2 = make_production_mesh(multi_pod=True)
        assert m2.devices.shape == (2, 16, 16)
        assert m2.axis_names == ("pod", "data", "model")
        print("OK meshes")
    """)
    out = _run(code)
    assert "OK" in out
