"""Device aggregation push-downs: density grids and masked reductions.

The reference runs aggregations inside tablet servers so only small partial
results travel to the client (AggregatingScan.scala:22-168, DensityScan.scala:
30-59 with GridSnap, StatsScan, BinAggregatingScan). The TPU analog fuses the
candidate mask with the aggregation in one XLA pass over sharded columns —
features never leave HBM; only the [H, W] grid / scalar reductions do.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from geomesa_tpu.ops.compaction import compact_indices
from geomesa_tpu.ops.filters import spatial_mask, temporal_mask
from geomesa_tpu.parallel.mesh import DATA_AXIS, gated
from geomesa_tpu.utils.devstats import instrumented_jit


def grid_snap_indices(
    x: jnp.ndarray,
    y: jnp.ndarray,
    env: jnp.ndarray,
    width: int,
    height: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(col, row, in_env) with GridSnap semantics (utils GridSnap.scala:1-120):
    i = floor((v - min) * n / extent), right edge snapped into the last cell.
    ``env`` is a dynamic [4] array (xmin, ymin, xmax, ymax) so new query
    envelopes don't recompile the kernel; only width/height are static."""
    xmin, ymin, xmax, ymax = env[0], env[1], env[2], env[3]
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    col = jnp.floor((x - xmin) / dx).astype(jnp.int32)
    row = jnp.floor((y - ymin) / dy).astype(jnp.int32)
    in_env = (x >= xmin) & (x <= xmax) & (y >= ymin) & (y <= ymax)
    col = jnp.clip(col, 0, width - 1)
    row = jnp.clip(row, 0, height - 1)
    return col, row, in_env


def density_kernel(
    x: jnp.ndarray,
    y: jnp.ndarray,
    mask: jnp.ndarray,
    env: jnp.ndarray,
    width: int,
    height: int,
) -> jnp.ndarray:
    """Masked scatter-add into an [H, W] grid (DensityScan analog)."""
    col, row, in_env = grid_snap_indices(x, y, env, width, height)
    w = jnp.where(mask & in_env, jnp.float32(1.0), jnp.float32(0.0))
    flat = row * width + col
    grid = jnp.zeros(height * width, dtype=jnp.float32)
    grid = grid.at[flat].add(w)
    return grid.reshape(height, width)


# --- exact device density: certain grid + host-certified band ---------------
#
# The device bins in f32, so points within f32 error of a grid-cell
# boundary or a query-box edge may land differently than the host's f64
# path. The DUAL pass makes the device grid EXACTLY host-parity, reusing
# the banded-polygon idiom (parallel/executor._poly_mask_body: device
# decides the bulk, host certifies the ring): rows the device cannot
# certify in f32 are excluded from the device grid and their indices
# returned for the host to evaluate and bin from its f64 block columns.

DENSITY_BAND_CAP = 8192  # per-shard band index buffer (32KB i32 d2h)
_BAND_ULPS = 16.0  # margin over the rigorous f32 quantization+rounding bound


def density_band(x, y, env, width, height, boxes):
    """(band, near): ``band`` = rows whose cell assignment or box
    membership could differ between the device's f32 columns/arithmetic
    and the host's f64 originals — f32 quantization of the coordinate
    (<= 0.5 ulp of |x|), f32 rounding of env/box bounds, and the f32
    (x - xmin)/dx evaluation; ``near`` = band rows that additionally pass
    every test with band-widened edges (the candidate set the host must
    certify — band rows far outside every box need no certification).

    Padded boxes are inverted (min > max) and satisfy neither the wide
    nor the strict test; NaN coordinates (null geometries) fail every
    comparison and are never banded.

    The boxes are folded one at a time (K is static) so every output is
    one elementwise expression of the row's x and y: XLA fuses it into
    its consumers and builds no row-length temporary (an [N, K] test
    reduced over K puts the f32 margins and per-box flags in HBM)."""
    xmin, ymin, xmax, ymax = env[0], env[1], env[2], env[3]
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    eps = jnp.float32(_BAND_ULPS * 2.0 ** -23)
    ex = eps * jnp.maximum(jnp.maximum(jnp.abs(xmin), jnp.abs(xmax)), jnp.abs(x))
    ey = eps * jnp.maximum(jnp.maximum(jnp.abs(ymin), jnp.abs(ymax)), jnp.abs(y))
    tx = (x - xmin) / dx
    ty = (y - ymin) / dy
    ttx = ex / jnp.abs(dx) + eps * jnp.abs(tx)
    tty = ey / jnp.abs(dy) + eps * jnp.abs(ty)
    cell_band = (jnp.abs(tx - jnp.round(tx)) <= ttx) | (
        jnp.abs(ty - jnp.round(ty)) <= tty
    )
    any_wide = any_strict = any_edge = jnp.zeros(x.shape, jnp.bool_)
    for k in range(boxes.shape[0]):
        bx0, by0, bx1, by1 = boxes[k, 0], boxes[k, 1], boxes[k, 2], boxes[k, 3]
        exk = jnp.maximum(ex, eps * jnp.maximum(jnp.abs(bx0), jnp.abs(bx1)))
        eyk = jnp.maximum(ey, eps * jnp.maximum(jnp.abs(by0), jnp.abs(by1)))
        in_wide = (
            (x >= bx0 - exk) & (x <= bx1 + exk)
            & (y >= by0 - eyk) & (y <= by1 + eyk)
        )
        in_strict = (
            (x >= bx0 + exk) & (x <= bx1 - exk)
            & (y >= by0 + eyk) & (y <= by1 - eyk)
        )
        any_wide = any_wide | in_wide
        any_strict = any_strict | in_strict
        any_edge = any_edge | (in_wide & ~in_strict)
    box_band = any_edge & ~any_strict
    band = cell_band | box_band
    near = (
        band
        & any_wide
        & (tx >= -ttx) & (tx <= width + ttx)
        & (ty >= -tty) & (ty <= height + tty)
    )
    return band, near


def make_sharded_density_dual(
    mesh, width: int, height: int, mode: str = "xla",
    band_cap: int = DENSITY_BAND_CAP,
):
    """Jitted shard_map density passes: per-shard fused exact-predicate
    mask + grid, partial grids psum'd over the row axis (the client-merge
    analog, QueryPlanner.scala:87-92, but on ICI instead of RPC). The
    spatial test runs on raw f32 coords vs raw boxes, the temporal test on
    raw (bin, offset) windows.

    mode "pallas"/"pallas_spmd" runs the per-shard grid as the MXU one-hot
    matmul kernel (pallas_kernels.density_grid_pallas) when the grid fits
    its VMEM budget; "xla" keeps the scatter-add (the CPU shape).

    Each call returns (grid, band_idx, band_count) where the [H, W] grid
    counts only rows the device can certify (mask & ~band), ``band_idx``
    is the [n_shards * band_cap] packed-array indices of band candidates
    (-1 padding), and ``band_count`` the per-shard true candidate counts
    (count > band_cap means the buffer truncated — the caller fetches that
    segment's band whole from ``make_sharded_band_bitmap``). The executor
    certifies the band rows against the plan's post filter on the f64 host
    columns and adds their f64 GridSnap bins, making the final grid
    exactly host-parity."""
    from geomesa_tpu.ops.filters import bbox_mask_f32
    from geomesa_tpu.ops.pallas_kernels import DENSITY_MAX_DIM, density_grid_pallas

    use_pallas = mode != "xla" and (
        width <= DENSITY_MAX_DIM and height <= DENSITY_MAX_DIM
    )

    def _band_outputs(cand, local_n):
        idx, cnt = compact_indices(cand, band_cap, local_n)
        cnt = cnt.reshape(1)
        shard = jax.lax.axis_index(DATA_AXIS).astype(jnp.int32)
        gidx = jnp.where(idx < local_n, idx + shard * local_n, jnp.int32(-1))
        return gidx, cnt

    def step(x, y, bins, offs, valid, boxes, windows, env):
        band, near = density_band(x, y, env, width, height, boxes)
        tm = temporal_mask(bins, offs, windows)
        if use_pallas:
            grid = density_grid_pallas(
                x, y, bins, offs, valid & ~band, boxes, windows, env,
                width, height, True,
            )
        else:
            m = valid & bbox_mask_f32(x, y, boxes) & tm
            grid = density_kernel(x, y, m & ~band, env, width, height)
        grid = jax.lax.psum(grid, DATA_AXIS)
        gidx, cnt = _band_outputs(near & valid & tm, x.shape[0])
        return grid, gidx, cnt

    def step_no_time(x, y, valid, boxes, env):
        band, near = density_band(x, y, env, width, height, boxes)
        if use_pallas:
            grid = density_grid_pallas(
                x, y, None, None, valid & ~band, boxes, None, env,
                width, height, False,
            )
        else:
            m = valid & bbox_mask_f32(x, y, boxes)
            grid = density_kernel(x, y, m & ~band, env, width, height)
        grid = jax.lax.psum(grid, DATA_AXIS)
        gidx, cnt = _band_outputs(near & valid, x.shape[0])
        return grid, gidx, cnt

    from geomesa_tpu.parallel.mesh import shard_map_fn

    d = P(DATA_AXIS)
    r = P()
    # the psum reduction is a REAL collective: gate both programs so
    # concurrent multi-device executions can never interleave their
    # rendezvous (parallel/mesh.gated)
    with_time = gated(instrumented_jit("density_dual.time", 
        shard_map_fn(
            step,
            mesh,
            in_specs=(d, d, d, d, d, r, r, r),
            out_specs=(r, d, d),
            check=not use_pallas,
        )
    ), mesh)
    no_time = gated(instrumented_jit("density_dual.notime", 
        shard_map_fn(
            step_no_time,
            mesh,
            in_specs=(d, d, d, r, r),
            out_specs=(r, d, d),
            check=not use_pallas,
        )
    ), mesh)
    return with_time, no_time


# --- band bitmap: the band of a shard that outgrew its index buffer ----------
#
# The dual pass's grid is exact for every row outside the band whatever the
# band's size; only the band's index buffer is bounded. When a shard's band
# count exceeds it, this program recomputes the same candidate mask and
# returns it whole as a packed bitmap (n/8 bytes a shard) for the host to
# decode and certify. The bits are packed down the sublanes of each
# (8, lanes) row tile: a flat packbits over [n] relayouts to [n/8, 8] with
# the minor dim padded to 128 lanes (16 bytes a row of HBM), while the tile
# pack fuses the band test into one reduce with no row-length temporary.

_BITMAP_LANES = 128


def _row_tile_lanes(local_n: int) -> int:
    """Lanes of the row tile ``pack_row_tiles`` packs (local_n % 8 == 0)."""
    return math.gcd(_BITMAP_LANES, local_n // 8)


def pack_row_tiles(m):
    """[n] bool -> [n/8] uint8: byte b*lanes + l holds rows
    b*8*lanes + s*lanes + l for s = 0..7, the first row in the high bit
    (np.packbits' bit order, so the native decode reads it as is)."""
    lanes = _row_tile_lanes(m.shape[0])
    t = m.reshape(-1, 8, lanes).astype(jnp.uint8)
    w = jnp.left_shift(jnp.uint8(1), jnp.arange(7, -1, -1, dtype=jnp.uint8))
    return jnp.sum(t * w[None, :, None], axis=1, dtype=jnp.uint8).reshape(-1)


def row_tile_rows(pos: np.ndarray, local_n: int) -> np.ndarray:
    """Inverse of ``pack_row_tiles`` over the shards' concatenated bitmaps:
    set-bit positions (the decode of the packed stream) -> sorted row
    indices of the [n_shards * local_n] packed array."""
    lanes = _row_tile_lanes(local_n)
    shard, p = np.divmod(np.asarray(pos, dtype=np.int64), local_n)
    block, r = np.divmod(p, 8 * lanes)
    lane, sub = np.divmod(r, 8)
    return np.sort(shard * local_n + block * (8 * lanes) + sub * lanes + lane)


def make_sharded_band_bitmap(mesh, width: int, height: int):
    """(with_time, no_time): the dual pass's band candidates (``near &
    valid`` and the time mask where there is one) as a packed bitmap per
    shard (``pack_row_tiles``; decode with ``row_tile_rows``). Same inputs
    as ``make_sharded_density_dual``'s editions; no kernel, no compaction
    and no collective, so no dispatch gate either."""

    def band_bitmap(x, y, bins, offs, valid, boxes, windows, env):
        _, near = density_band(x, y, env, width, height, boxes)
        return pack_row_tiles(near & valid & temporal_mask(bins, offs, windows))

    def band_bitmap_no_time(x, y, valid, boxes, env):
        _, near = density_band(x, y, env, width, height, boxes)
        return pack_row_tiles(near & valid)

    from geomesa_tpu.parallel.mesh import shard_map_fn

    d = P(DATA_AXIS)
    r = P()
    with_time = instrumented_jit("density_bitmap.time", shard_map_fn(
        band_bitmap, mesh, in_specs=(d, d, d, d, d, r, r, r), out_specs=d,
    ))
    no_time = instrumented_jit("density_bitmap.notime", shard_map_fn(
        band_bitmap_no_time, mesh, in_specs=(d, d, d, r, r), out_specs=d,
    ))
    return with_time, no_time


# --- aggregate pyramid build reduction ---------------------------------------
#
# The GeoBlocks-style pyramid (ops/pyramid.py) pre-aggregates every row
# into a coarse z2 cell grid so hot polygon/bbox aggregations answer from
# interior partial sums. The build reduction runs straight off the
# HBM-resident segment mirrors: the z2 segments already hold each row's
# EXACT integer grid coordinates (seg.xi / seg.yi, decoded from the index
# keys), so the device bins by integer shifts — bit-identical to the host
# build that decodes the same keys, no f32 coordinate rounding anywhere.


def make_pyramid_counts(mesh, bits: int, src_bits: int = 31):
    """Jitted shard_map pyramid-count pass: (xi, yi, mask) -> [H, W] i32
    per-cell row counts, psum'd over the data axis. ``mask`` excludes
    tombstoned and null-geometry rows (their lenient-encoded keys would
    otherwise count in cell 0). Counting sorts the flat cell ids once and
    reads each cell's count off as the difference of its searchsorted
    boundaries (integer-exact, scatter-free)."""
    n = 1 << bits
    shift = src_bits - bits

    def step(xi, yi, mask):
        cx = jax.lax.shift_right_logical(xi, shift)
        cy = jax.lax.shift_right_logical(yi, shift)
        flat = jnp.where(mask, cy * n + cx, jnp.int32(n * n))
        s = jnp.sort(flat)
        bounds = jnp.searchsorted(s, jnp.arange(n * n + 1, dtype=jnp.int32))
        grid = jnp.diff(bounds).astype(jnp.int32).reshape(n, n)
        return jax.lax.psum(grid, DATA_AXIS)

    from geomesa_tpu.parallel.mesh import shard_map_fn

    d = P(DATA_AXIS)
    return gated(instrumented_jit(
        "agg.pyramid",
        shard_map_fn(step, mesh, in_specs=(d, d, d), out_specs=P()),
    ), mesh)


# the host reference implementation lives in geomesa_tpu.index.aggregators
# (pure numpy, so the host-only datastore path has no jax dependency)
