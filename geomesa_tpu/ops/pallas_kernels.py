"""Pallas TPU kernels for the scan + aggregation hot loops.

The fused candidate mask is the framework's per-row hot op (the tserver
Z3Iterator seek/next loop, accumulo/iterators/Z3Iterator.scala:42-65; the
Z2/XZ variants, filters/Z2Filter.scala:18-20, XZ2IndexKeySpace.scala:26+).
The XLA version in ops/filters.py materializes an [N, K] broadcast; these
Pallas kernels stream row tiles through VMEM and accumulate the per-box /
window tests in registers, so HBM traffic is one read of each column + one
bool write — the memory-bound optimum.

The density kernel is the DensityScan analog (iterators/DensityScan.scala:
30-59): instead of a scatter-add (which serializes on TPU), each row tile
builds weighted one-hot row/col matrices and accumulates the grid as an
outer-product matmul R^T @ C on the MXU — the systolic array does the
scatter.

Shapes: rows padded to a multiple of TILE; boxes [K, 4] and windows [W, 3]
are small and live in VMEM replicated per tile. On non-TPU backends
``interpret=True`` keeps the kernels testable (conftest's CPU mesh).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

TILE = 8 * 128  # one (8, 128) vreg-shaped row tile per grid step
# one-hot density matmul VMEM budget: R[H,128] + C[W,128] bf16 + out[H,W] f32
DENSITY_MAX_DIM = 512


def _row_spec():
    return pl.BlockSpec((8, 128), lambda i: (i, 0))


def _small(a):
    return pl.BlockSpec(a.shape, lambda i: (0, 0))


def _contains(x, y, boxes_ref, k):
    """Any-box containment; dtype-generic (int curve domain or raw f32)."""
    m = jnp.zeros(x.shape, dtype=jnp.bool_)
    for j in range(k):  # k is a small static pad; unrolled vector ops
        m = m | (
            (x >= boxes_ref[j, 0])
            & (x <= boxes_ref[j, 2])
            & (y >= boxes_ref[j, 1])
            & (y <= boxes_ref[j, 3])
        )
    return m


def _temporal(bins, offs, windows_ref, w):
    m = jnp.zeros(bins.shape, dtype=jnp.bool_)
    for j in range(w):
        m = m | (
            (bins == windows_ref[j, 0])
            & (offs >= windows_ref[j, 1])
            & (offs <= windows_ref[j, 2])
        )
    return m


def _overlap(bxmin, bymin, bxmax, bymax, boxes_ref, k):
    m = jnp.zeros(bxmin.shape, dtype=jnp.bool_)
    for j in range(k):
        m = m | (
            (bxmin <= boxes_ref[j, 2])
            & (bxmax >= boxes_ref[j, 0])
            & (bymin <= boxes_ref[j, 3])
            & (bymax >= boxes_ref[j, 1])
        )
    return m


# -- candidate-mask kernels -------------------------------------------------


def _z3_mask_kernel(xi_ref, yi_ref, bins_ref, offs_ref, valid_ref, boxes_ref,
                    windows_ref, out_ref, *, k: int, w: int):
    spatial = _contains(xi_ref[...], yi_ref[...], boxes_ref, k)
    temporal = _temporal(bins_ref[...], offs_ref[...], windows_ref, w)
    out_ref[...] = valid_ref[...] & spatial & temporal


def _z2_mask_kernel(xi_ref, yi_ref, valid_ref, boxes_ref, out_ref, *, k: int):
    out_ref[...] = valid_ref[...] & _contains(xi_ref[...], yi_ref[...], boxes_ref, k)


def _xz2_mask_kernel(bxmin_ref, bymin_ref, bxmax_ref, bymax_ref, valid_ref,
                     boxes_ref, out_ref, *, k: int):
    out_ref[...] = valid_ref[...] & _overlap(
        bxmin_ref[...], bymin_ref[...], bxmax_ref[...], bymax_ref[...], boxes_ref, k
    )


def _xz3_mask_kernel(bxmin_ref, bymin_ref, bxmax_ref, bymax_ref, bins_ref,
                     offs_ref, valid_ref, boxes_ref, windows_ref, out_ref,
                     *, k: int, w: int):
    overlap = _overlap(
        bxmin_ref[...], bymin_ref[...], bxmax_ref[...], bymax_ref[...], boxes_ref, k
    )
    temporal = _temporal(bins_ref[...], offs_ref[...], windows_ref, w)
    out_ref[...] = valid_ref[...] & overlap & temporal


def _run_mask(kernel, row_args, small_args, interpret):
    """Common pallas_call driver: row columns tiled (8, 128), small query
    descriptors replicated whole into VMEM."""
    n = row_args[0].shape[0]
    if n % TILE:
        raise ValueError(f"rows must be padded to {TILE}")
    rows = n // 128
    shape = (rows, 128)
    grid = (rows // 8,)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_row_spec()] * len(row_args) + [_small(a) for a in small_args],
        out_specs=_row_spec(),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.bool_),
        interpret=interpret,
    )(*[a.reshape(shape) for a in row_args], *small_args)
    return out.reshape(n)


def _auto_interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


@functools.partial(jax.jit, static_argnames=("interpret",))
def _z3_run(xi, yi, bins, offs, valid, boxes, windows, interpret):
    kern = functools.partial(_z3_mask_kernel, k=boxes.shape[0], w=windows.shape[0])
    return _run_mask(kern, (xi, yi, bins, offs, valid), (boxes, windows), interpret)


def z3_query_mask_pallas(xi, yi, bins, offs, valid, boxes, windows,
                         interpret: bool | None = None):
    """Drop-in for ops.filters.z3_query_mask; rows must be TILE-padded."""
    return _z3_run(
        jnp.asarray(xi, jnp.int32),
        jnp.asarray(yi, jnp.int32),
        jnp.asarray(bins, jnp.int32),
        jnp.asarray(offs, jnp.int32),
        jnp.asarray(valid),
        jnp.asarray(boxes, jnp.int32),
        jnp.asarray(windows, jnp.int32),
        _auto_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _z2_run(xi, yi, valid, boxes, interpret):
    kern = functools.partial(_z2_mask_kernel, k=boxes.shape[0])
    return _run_mask(kern, (xi, yi, valid), (boxes,), interpret)


def z2_query_mask_pallas(xi, yi, valid, boxes, interpret: bool | None = None):
    """Drop-in for ops.filters.z2_query_mask; rows must be TILE-padded."""
    return _z2_run(
        jnp.asarray(xi, jnp.int32),
        jnp.asarray(yi, jnp.int32),
        jnp.asarray(valid),
        jnp.asarray(boxes, jnp.int32),
        _auto_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _xz2_run(bxmin, bymin, bxmax, bymax, valid, boxes, interpret):
    kern = functools.partial(_xz2_mask_kernel, k=boxes.shape[0])
    return _run_mask(kern, (bxmin, bymin, bxmax, bymax, valid), (boxes,), interpret)


def xz2_overlap_mask_pallas(bxmin, bymin, bxmax, bymax, valid, boxes,
                            interpret: bool | None = None):
    """Drop-in for ops.filters.bbox_overlap_mask (f32 extent test)."""
    return _xz2_run(
        jnp.asarray(bxmin, jnp.float32),
        jnp.asarray(bymin, jnp.float32),
        jnp.asarray(bxmax, jnp.float32),
        jnp.asarray(bymax, jnp.float32),
        jnp.asarray(valid),
        jnp.asarray(boxes, jnp.float32),
        _auto_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _xz3_run(bxmin, bymin, bxmax, bymax, bins, offs, valid, boxes, windows, interpret):
    kern = functools.partial(_xz3_mask_kernel, k=boxes.shape[0], w=windows.shape[0])
    return _run_mask(
        kern, (bxmin, bymin, bxmax, bymax, bins, offs, valid), (boxes, windows), interpret
    )


def xz3_overlap_mask_pallas(bxmin, bymin, bxmax, bymax, bins, offs, valid,
                            boxes, windows, interpret: bool | None = None):
    """XZ3: f32 extent overlap AND int (bin, offset) window test."""
    return _xz3_run(
        jnp.asarray(bxmin, jnp.float32),
        jnp.asarray(bymin, jnp.float32),
        jnp.asarray(bxmax, jnp.float32),
        jnp.asarray(bymax, jnp.float32),
        jnp.asarray(bins, jnp.int32),
        jnp.asarray(offs, jnp.int32),
        jnp.asarray(valid),
        jnp.asarray(boxes, jnp.float32),
        jnp.asarray(windows, jnp.int32),
        _auto_interpret(interpret),
    )


# -- density: one-hot outer-product matmul on the MXU -----------------------


def _density_kernel(x_ref, y_ref, bins_ref, offs_ref, valid_ref, boxes_ref,
                    windows_ref, env_ref, out_ref, *, k: int, w: int,
                    width: int, height: int, with_time: bool):
    """Accumulate the [H, W] density grid across row-tile grid steps.

    grid[r, c] = sum_i weight_i * [row_i == r] * [col_i == c]
               = (W ⊙ onehot_rows) @ onehot_cols^T   — an MXU matmul,
    replacing the data-dependent scatter-add the reference does per tserver
    (DensityScan.scala:30-59 sparse map + GridSnap).

    Rows arrive lane-dense, (8, 128) per step like the mask kernels: each
    sublane row of 128 points builds [H, 128] / [W, 128] one-hots and
    contracts them over the 128 lanes. An (N, 1) column layout pads every
    operand 128x in HBM (8M rows: 30.5 MB -> 3.81 GB per column).
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    from geomesa_tpu.ops.aggregations import grid_snap_indices

    x = x_ref[...]  # (8, 128) f32
    y = y_ref[...]
    # exact f32 spatial predicate (raw-domain boxes)
    m = _contains(x, y, boxes_ref, k)
    if with_time:
        m = m & _temporal(bins_ref[...], offs_ref[...], windows_ref, w)
    m = m & valid_ref[...]
    # single shared GridSnap implementation (aggregations.grid_snap_indices)
    # keeps XLA-vs-Pallas density parity by construction
    env = tuple(env_ref[0, j] for j in range(4))
    col, row, in_env = grid_snap_indices(x, y, env, width, height)
    weight = jnp.where(m & in_env, jnp.float32(1.0), jnp.float32(0.0))
    lanes = x.shape[1]
    rows_iota = jax.lax.broadcasted_iota(jnp.int32, (height, lanes), 0)
    cols_iota = jax.lax.broadcasted_iota(jnp.int32, (width, lanes), 0)
    acc = out_ref[...]
    for s in range(x.shape[0]):
        # bf16 one-hots (0/1 weights are exact in bf16), f32 accumulation
        r_onehot = jnp.where(
            rows_iota == row[s:s + 1, :], weight[s:s + 1, :], jnp.float32(0.0)
        ).astype(jnp.bfloat16)  # (H, 128)
        c_onehot = jnp.where(
            cols_iota == col[s:s + 1, :], jnp.float32(1.0), jnp.float32(0.0)
        ).astype(jnp.bfloat16)  # (W, 128)
        acc = acc + jax.lax.dot_general(
            r_onehot,
            c_onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("width", "height", "with_time", "interpret"))
def _density_run(x, y, bins, offs, valid, boxes, windows, env,
                 width, height, with_time, interpret):
    n = x.shape[0]
    if n % TILE:
        raise ValueError(f"rows must be padded to {TILE}")
    shape = (n // 128, 128)
    kern = functools.partial(
        _density_kernel,
        k=boxes.shape[0],
        w=windows.shape[0],
        width=width,
        height=height,
        with_time=with_time,
    )
    out_spec = pl.BlockSpec((height, width), lambda i: (0, 0))
    env2 = env.reshape(1, 4)
    return pl.pallas_call(
        kern,
        grid=(n // TILE,),
        in_specs=[_row_spec()] * 5 + [_small(boxes), _small(windows), _small(env2)],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((height, width), jnp.float32),
        interpret=interpret,
    )(
        x.reshape(shape),
        y.reshape(shape),
        bins.reshape(shape),
        offs.reshape(shape),
        valid.reshape(shape),
        boxes,
        windows,
        env2,
    )


def density_grid_pallas(x, y, bins, offs, valid, boxes, windows, env,
                        width: int, height: int, with_time: bool,
                        interpret: bool | None = None):
    """Fused mask + density grid; (bins, offs, windows) ignored unless
    ``with_time``. width/height must be <= DENSITY_MAX_DIM (VMEM budget)."""
    if width > DENSITY_MAX_DIM or height > DENSITY_MAX_DIM:
        raise ValueError(f"grid dims must be <= {DENSITY_MAX_DIM}")
    n = x.shape[0]
    if bins is None:
        bins = jnp.zeros(n, jnp.int32)
        offs = jnp.zeros(n, jnp.int32)
        windows = jnp.zeros((1, 3), jnp.int32)
    return _density_run(
        jnp.asarray(x, jnp.float32),
        jnp.asarray(y, jnp.float32),
        jnp.asarray(bins, jnp.int32),
        jnp.asarray(offs, jnp.int32),
        jnp.asarray(valid),
        jnp.asarray(boxes, jnp.float32),
        jnp.asarray(windows, jnp.int32),
        jnp.asarray(env, jnp.float32),
        width,
        height,
        with_time,
        _auto_interpret(interpret),
    )
