"""The chip's compiler accepts the store's device programs.

Each test compiles, ahead of time, for one TPU v5e chip that is described
but not attached: the blade-arena programs of ``core.devmem`` at the full
1 GiB arena the chip smoke run uses, and the Pallas wave checksum kernel.
Nothing runs; a compile that passes says the program fits and lowers, not
how fast it is.  The topology is described inside a fixture, so only the
test process that is given this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import devmem
from repro.kernels.log_checksum import BLOCK, LANES, ROWS, fletcher32_wave_call

ARENA = 1 << 30  # bytes per blade arena in chip_smoke.py


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_write_scatter_updates_the_arena_in_place(one_chip):
    arena = _spec((ARENA,), jnp.uint8, one_chip)
    idx = _spec((devmem.MAX_BUCKET,), jnp.int32, one_chip)
    vals = _spec((devmem.MAX_BUCKET,), jnp.uint8, one_chip)
    mem = devmem.scatter_program.lower(arena, idx, vals).compile().memory_analysis()
    # donation honoured: the output is the argument's buffer, and no
    # second arena-sized buffer exists
    assert mem.alias_size_in_bytes == ARENA
    assert mem.temp_size_in_bytes < ARENA // 2
    assert mem.output_size_in_bytes == ARENA


@pytest.mark.parametrize("n", [devmem.MIN_BUCKET, 1 << 16, devmem.MAX_BUCKET])
def test_read_wave_gather_and_run_slice_compile(one_chip, n):
    arena = _spec((ARENA,), jnp.uint8, one_chip)
    idx = _spec((n,), jnp.int32, one_chip)
    gather = devmem.gather_program.lower(arena, idx).compile().memory_analysis()
    start = _spec((), jnp.int32, one_chip)
    sliced = devmem.slice_program.lower(arena, start, n).compile().memory_analysis()
    for mem in (gather, sliced):
        # the output is the wave (padded to the chip's tile), never a copy
        # of the arena
        assert n <= mem.output_size_in_bytes < ARENA // 2
        assert mem.temp_size_in_bytes < ARENA // 2


def test_tx_apply_copy_into_primary_and_mirror_in_place(one_chip):
    arenas = (_spec((ARENA,), jnp.uint8, one_chip),) * 2
    idx = _spec((1 << 16,), jnp.int32, one_chip)
    mem = devmem.copy_program.lower(arenas, idx, idx).compile().memory_analysis()
    assert mem.alias_size_in_bytes == 2 * ARENA
    assert mem.temp_size_in_bytes < ARENA // 2


def test_fletcher32_wave_kernel_compiles_for_the_chip(one_chip):
    blocks = 64
    meta = _spec((blocks, 2), jnp.int32, one_chip)
    words = _spec((blocks, ROWS, LANES), jnp.int32, one_chip)
    assert ROWS * LANES == BLOCK
    compiled = jax.jit(
        lambda m, w: fletcher32_wave_call(m, w, blocks)).lower(meta, words).compile()
    assert "tpu_custom_call" in compiled.as_text()
