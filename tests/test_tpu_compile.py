"""AOT compiles of the main path's kernels for a described TPU v5e.

Nothing runs here: the TPU compiler that ships with jax compiles for a
chip that is described, not attached, and refuses what the chip would
refuse (HBM or VMEM overflow, unaligned tiles, kernels that cannot be
partitioned). Shapes are the chip smoke's own: 20M-row masks, the 8M-row
density grid, and the 4-chip SPMD mask.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and xdist workers all import this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from geomesa_tpu.ops import pallas_kernels as pk
from geomesa_tpu.parallel.mesh import DATA_AXIS

MASK_ROWS = 20_000_000
DENSITY_ROWS = 8_000_000
K, W = 8, 4  # padded query boxes / time windows


def _padded(n: int, granule: int = pk.TILE) -> int:
    return -(-n // granule) * granule


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's compiles can be written to the persistent cache
    # but never read back: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / no topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels pick interpret mode from jax.default_backend(), which
    is the CPU here: compile them as the chip runs them."""
    monkeypatch.setattr(pk, "_auto_interpret", lambda interpret: False)


def _shapes(sharding, n, dtypes):
    return [jax.ShapeDtypeStruct((n,), dt, sharding=sharding) for dt in dtypes]


def _small(sharding, *shapes_dtypes):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes_dtypes]


_MASKS = {
    # kind: (runner, row dtypes, small (shape, dtype) operands)
    "z3": (pk._z3_run, [jnp.int32] * 4 + [jnp.bool_],
           [((K, 4), jnp.int32), ((W, 3), jnp.int32)]),
    "z2": (pk._z2_run, [jnp.int32] * 2 + [jnp.bool_], [((K, 4), jnp.int32)]),
    "xz2": (pk._xz2_run, [jnp.float32] * 4 + [jnp.bool_],
            [((K, 4), jnp.float32)]),
    "xz3": (pk._xz3_run, [jnp.float32] * 4 + [jnp.int32] * 2 + [jnp.bool_],
            [((K, 4), jnp.float32), ((W, 3), jnp.int32)]),
}


@pytest.mark.parametrize("kind", sorted(_MASKS))
def test_mask_kernel_compiles_at_20m_rows(topo, kind):
    one = SingleDeviceSharding(topo.devices[0])
    run, row_dtypes, small = _MASKS[kind]
    args = _shapes(one, _padded(MASK_ROWS), row_dtypes) + _small(one, *small)
    compiled = run.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grid", [256, 512])
def test_density_kernel_compiles_at_8m_rows(topo, grid):
    one = SingleDeviceSharding(topo.devices[0])
    n = _padded(DENSITY_ROWS)
    args = _shapes(one, n, [jnp.float32] * 2 + [jnp.int32] * 2 + [jnp.bool_])
    args += _small(one, ((K, 4), jnp.float32), ((W, 3), jnp.int32), ((4,), jnp.float32))
    compiled = pk._density_run.lower(
        *args, width=grid, height=grid, with_time=True, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # lane-dense row tiles: the operands stay their own size in HBM
    # (an (N, 1) column layout padded each one 128x and overflowed HBM)
    assert compiled.memory_analysis().temp_size_in_bytes < n * 4


def test_spmd_z3_mask_compiles_on_4_chips(topo, compiled_kernels):
    from geomesa_tpu.parallel.executor import _raw_mask_fn

    mesh = Mesh(np.asarray(topo.devices[:4]), (DATA_AXIS,))
    rows = NamedSharding(mesh, P(DATA_AXIS))
    rep = NamedSharding(mesh, P())
    n = _padded(MASK_ROWS, pk.TILE * 4)
    args = _shapes(rows, n, [jnp.int32] * 4 + [jnp.bool_])
    args += _small(rep, ((K, 4), jnp.int32), ((W, 3), jnp.int32))
    fn = jax.jit(_raw_mask_fn("z3", "pallas_spmd", mesh))
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_batched_exact_z3_compiles_at_20m_rows(topo):
    """The function query_many dispatches on one chip: Q stacked exact
    box+window predicates over the 20M-row limb columns, span-framed
    bitmaps out (executor._exact_bitmap_batch_fn)."""
    from geomesa_tpu.parallel.executor import _exact_bitmap_batch_fn

    mesh = Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,))
    one = SingleDeviceSharding(topo.devices[0])
    n = _padded(MASK_ROWS)
    q = 20
    fn = _exact_bitmap_batch_fn(True, n, q, "local", mesh)
    args = _shapes(one, n, [jnp.uint32] * 6 + [jnp.bool_])
    args += _small(one, ((q, 8), jnp.uint32), ((q, 4), jnp.uint32))
    compiled = fn._jitted.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15 * 2**30


@pytest.mark.parametrize("grid", [256, 512])
def test_dual_density_pass_compiles_at_8m_rows(topo, compiled_kernels, grid):
    """The executor's whole density pass (ops/aggregations
    .make_sharded_density_dual, pallas edition): the f32 band, the
    Pallas grid and the band-index extraction in one program."""
    from geomesa_tpu.ops.aggregations import make_sharded_density_dual

    mesh = Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,))
    one = SingleDeviceSharding(topo.devices[0])
    with_time, _ = make_sharded_density_dual(mesh, grid, grid, "pallas")
    n = _padded(DENSITY_ROWS)
    args = _shapes(one, n, [jnp.float32] * 2 + [jnp.int32] * 2 + [jnp.bool_])
    args += _small(one, ((K, 4), jnp.float32), ((W, 3), jnp.int32), ((4,), jnp.float32))
    compiled = with_time._jitted.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the band compaction scatters nothing over the rows (bounded
    # jnp.nonzero's bincount did: a colliding scatter-add of every row)
    assert "scatter" not in text
    # and builds no row-length int32 temporary (the cumsum of the mask)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < n * 4 + grid * grid * 4


def test_shard_batched_exact_z3_compiles_on_4_chips(topo):
    """query_many's dispatch on a 4-chip mesh: each chip frames its own
    rows' hit window (executor._exact_shard_bitmap_batch_fn)."""
    from geomesa_tpu.parallel.executor import _exact_shard_bitmap_batch_fn

    mesh = Mesh(np.asarray(topo.devices[:4]), (DATA_AXIS,))
    rows = NamedSharding(mesh, P(DATA_AXIS))
    rep = NamedSharding(mesh, P())
    n = _padded(MASK_ROWS, pk.TILE * 4)
    q = 20
    fn = _exact_shard_bitmap_batch_fn(True, n // 4, q, mesh)
    args = _shapes(rows, n, [jnp.uint32] * 6 + [jnp.bool_])
    args += _small(rep, ((q, 8), jnp.uint32), ((q, 4), jnp.uint32))
    fn._jitted.lower(*args).compile()


@pytest.mark.parametrize("with_time", [True, False])
def test_band_bitmap_compiles_at_8m_rows(topo, with_time):
    """The band bitmap a density request fetches when its band outgrows the
    index buffer (ops/aggregations.make_sharded_band_bitmap): the band test
    fused into the tile pack, no scatter, and no row-length mask in HBM
    (a flat jnp.packbits relayouts the mask to 16 bytes a row)."""
    from geomesa_tpu.ops.aggregations import make_sharded_band_bitmap

    mesh = Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,))
    one = SingleDeviceSharding(topo.devices[0])
    fns = make_sharded_band_bitmap(mesh, 256, 256)
    n = _padded(DENSITY_ROWS)
    if with_time:
        args = _shapes(one, n, [jnp.float32] * 2 + [jnp.int32] * 2 + [jnp.bool_])
        args += _small(one, ((K, 4), jnp.float32), ((W, 3), jnp.int32), ((4,), jnp.float32))
    else:
        args = _shapes(one, n, [jnp.float32] * 2 + [jnp.bool_])
        args += _small(one, ((K, 4), jnp.float32), ((4,), jnp.float32))
    compiled = fns[0 if with_time else 1]._jitted.lower(*args).compile()
    assert "scatter" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes < n // 8 + 2**12
    assert mem.temp_size_in_bytes < n // 8 + 2**21
