"""Compile the device path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: blocks that miss
the tiling of the arrays in HBM, and kernels that outgrow scoped VMEM.
These tests compile the programs ``kernels.ops.grouped_reduce`` hands the
chip, at the widths the SQL engine uses: 8192-row chunks with few and with
many groups, and a short last chunk. Nothing runs; the results are checked
by the interpret-mode tests and by ``chip_smoke.py`` on the chip.

The topology is described inside a fixture: only one process at a time
may load the TPU library, and it keeps it until it exits, so nothing here
touches it at import time.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bucket_reduce import bucket_reduce


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows,groups", [
    (8192, 24),     # a full chunk, hour-of-day keys
    (8192, 1024),
    (8192, 8192),   # a full chunk of distinct keys
    (700, 24),      # a filtered short last chunk
])
def test_grouped_reduce_kernel_compiles(one_chip, rows, groups):
    """The padded shapes grouped_reduce sends to the kernel compile into
    one Mosaic custom call and fit the chip's memory."""
    prows = ops._pow2_at_least(rows, ops._MIN_ROWS)
    pgroups = ops._pow2_at_least(groups, ops._MIN_GROUPS)
    assert pgroups <= ops._MAX_KERNEL_GROUPS  # these widths take the kernel
    compiled = ops._kernel_sums.lower(
        _spec((prows,), jnp.float32, one_chip),
        _spec((prows,), jnp.int32, one_chip),
        n_buckets=pgroups, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == prows * 8
    assert mem.output_size_in_bytes == pgroups * 4


def test_bucket_reduce_unaligned_rows_compile(one_chip):
    """Called directly with a row count that is no multiple of the block,
    the kernel pads to whole blocks itself; D > 1 keeps its layout."""
    fn = jax.jit(lambda v, i: bucket_reduce(v, i, 33))
    for d in (1, 8):
        compiled = fn.lower(_spec((3077, d), jnp.float32, one_chip),
                            _spec((3077,), jnp.int32, one_chip)).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_x64_segment_sum_compiles(one_chip):
    """The int64 envelope (sum(|v|) up to 2**62) compiles for the chip,
    which emulates 64-bit integers."""
    with jax.enable_x64(True):
        compiled = ops._x64_sums.lower(
            _spec((8192,), jnp.int64, one_chip),
            _spec((8192,), jnp.int32, one_chip),
            n_buckets=8192).compile()
    assert compiled.memory_analysis().output_size_in_bytes == 8192 * 8
