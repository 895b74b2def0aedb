"""ops.compaction.compact_indices against jnp.nonzero(size=, fill_value=).

The helper replaces bounded ``jnp.nonzero`` in the density band and the
RLE run extraction, so it must return the same array element for element:
ascending true indices, ``fill_value`` past the true count, int32.
"""

import functools
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from geomesa_tpu.ops.compaction import compact_indices

SIZES = (1, 256, 4096, 8192)


def _rows(size: int) -> int:
    # never a multiple of 128 or 1024, and room for size + 1 trues
    return 4 * size + 77


def _mask(case: str, n: int, size: int) -> np.ndarray:
    rng = np.random.default_rng(zlib.crc32(f"{case}/{n}/{size}".encode()))
    m = np.zeros(n, bool)
    if case == "empty":
        pass
    elif case == "all":
        m[:] = True
    elif case == "first":
        m[0] = True
    elif case == "last":
        m[-1] = True
    elif case in ("count_eq_size", "count_size_plus_1"):
        k = size + (case == "count_size_plus_1")
        m[np.sort(rng.choice(n, k, replace=False))] = True
    else:
        m = rng.random(n) < float(case)
    return m


@functools.lru_cache(maxsize=None)
def _fns(size: int, fill: int):
    ours = jax.jit(lambda m: compact_indices(m, size, fill))
    want = jax.jit(lambda m: jnp.nonzero(m, size=size, fill_value=fill)[0])
    return ours, want


@pytest.mark.parametrize("fill", ["n", -7])
@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize(
    "case",
    ["empty", "all", "first", "last", "count_eq_size", "count_size_plus_1",
     "1e-4", "0.01", "0.5"],
)
def test_compact_indices_matches_bounded_nonzero(case, size, fill):
    n = _rows(size)
    fill_value = n if fill == "n" else fill
    m = _mask(case, n, size)
    ours, want = _fns(size, fill_value)
    idx, count = ours(jnp.asarray(m))
    assert idx.dtype == jnp.int32 and count.dtype == jnp.int32
    assert idx.shape == (size,)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want(jnp.asarray(m))))
    assert int(count) == int(m.sum())


@pytest.mark.parametrize("n", [1, 127, 255, 257, 1023, 100_003])
def test_compact_indices_odd_lengths_at_density_cap(n):
    """Lengths around and off the block size, at the density band's cap."""
    from geomesa_tpu.ops.aggregations import DENSITY_BAND_CAP

    m = np.random.default_rng(n).random(n) < 0.3
    idx, count = jax.jit(lambda a: compact_indices(a, DENSITY_BAND_CAP, n))(jnp.asarray(m))
    want = np.full(DENSITY_BAND_CAP, n, np.int32)
    hits = np.flatnonzero(m)[:DENSITY_BAND_CAP]
    want[: hits.size] = hits
    np.testing.assert_array_equal(np.asarray(idx), want)
    assert int(count) == int(m.sum())
