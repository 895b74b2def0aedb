"""TpuScanExecutor: run the index pre-filter on device over sharded columns.

Replaces the reference's tserver-side scan loop (BatchScanPlan fan-out,
accumulo/index/AccumuloQueryPlan.scala:113-140, + Z3Iterator reject,
accumulo/iterators/Z3Iterator.scala:42-65) with one fused XLA pass:

  host planner --> int-domain boxes + per-bin windows (query descriptor)
  device       --> candidate mask -> on-device COMPACTION to a hit list
  host         --> exact CQL post-filter on the (small) candidate set

The device mask is conservative and the exact post-filter is unchanged, so
result sets are identical to the host scan path (parity by construction).

Transfer protocol (the tserver "return only matching KVs" analog,
Z3Iterator.scala:42-65): the device compacts the mask into run-length
encoded hit runs — rows are z-sorted, so a box query's hits are contiguous
runs and RLE is ~8x smaller than an index list — and fuses (count, n_runs,
starts, lengths) into ONE int32 buffer so a query costs a single
device->host round trip. n_runs > capacity escalates to the next pow2
bucket (the segment remembers it); when the run list would exceed the
packed bitmap's size the N/8-byte bitmap is transferred instead.

Dispatch and resolve are SPLIT (dispatch_hits / _PendingHits.rows) so many
scans pipeline over a high-latency device link: all buffers start computing
and copying host-ward before the first blocking read — the client-side
BatchScanner thread-pool analog (AccumuloQueryPlan.scala:113-140).

Device residency is SEGMENTED and incremental: each write batch becomes a
new device segment (only new rows cross the host->device link); tombstones
flip bits in the device-side valid mask instead of invalidating the mirror;
once fragmentation exceeds MAX_SEGMENTS the mirror is rebuilt as one merged
segment (a full re-upload — the compaction analog).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from geomesa_tpu.curve import time_to_binned, zorder
from geomesa_tpu.curve.binnedtime import TimePeriod, binned_to_time
from geomesa_tpu.index.planner import QueryPlan
from geomesa_tpu.ops.compaction import compact_indices
from geomesa_tpu.ops.filters import (
    bbox_overlap_mask,
    pad_boxes,
    pad_windows,
    temporal_mask,
    z2_query_mask,
    z3_query_mask,
)
from geomesa_tpu.ops.zkernels import pack_mask_rows
from geomesa_tpu.parallel.mesh import (
    DATA_AXIS,
    default_mesh,
    pad_to_multiple,
    replicate,
    shard_array,
    shard_map_fn,
)
from geomesa_tpu.store.blocks import FeatureBlock, IndexTable
from geomesa_tpu.utils import audit, deadline, faults, trace
from geomesa_tpu.utils.devstats import (
    count_d2h,
    devstats_metrics,
    instrumented_jit,
    record_pad,
)

# initial hit-run capacity: 4096 runs * 8B = 32 KiB per segment transfer
HIT_CAPACITY0 = 4096
# merge device segments once a query must touch more than this many
MAX_SEGMENTS = 8
# runs buffers bigger than n/DENSE_BITMAP_FACTOR rows' worth degrade to the
# packed N/8-byte bitmap (8B/run vs 1bit/row break-even at n/64 runs)
DENSE_BITMAP_FACTOR = 64

def _mask_mode(mesh) -> str:
    """Which kernel implementation the executor runs.

    "pallas"       streaming Pallas kernel, single chip
    "pallas_spmd"  Pallas kernel per shard under shard_map (multi-chip:
                   each chip scans its resident rows — the tablet-server
                   fan-out of BatchScanPlan, AccumuloQueryPlan.scala:113-140)
    "xla"          broadcast-compare XLA fallback (CPU, or GEOMESA_PALLAS=0)

    GEOMESA_PALLAS overrides: 0 -> xla, spmd -> pallas_spmd (interpret mode
    off-TPU; lets the CPU mesh tests exercise the SPMD kernel path).
    """
    import os

    env = os.environ.get("GEOMESA_PALLAS", "auto")
    if env == "0":
        return "xla"
    if env == "spmd":
        return "pallas_spmd"
    if env == "1" or jax.default_backend() == "tpu":
        return "pallas" if mesh.devices.size == 1 else "pallas_spmd"
    return "xla"


def _xla_mask_fn(kind: str):
    if kind == "z3":
        return z3_query_mask
    if kind == "z2":
        return z2_query_mask
    if kind == "xz3":
        def run(bxmin, bymin, bxmax, bymax, bins, offs, valid, boxes, windows):
            m = bbox_overlap_mask(bxmin, bymin, bxmax, bymax, valid, boxes)
            return m & temporal_mask(bins, offs, windows)

        return run
    return bbox_overlap_mask  # xz2


def _pallas_mask_fn(kind: str):
    from geomesa_tpu.ops import pallas_kernels as pk

    return {
        "z3": pk.z3_query_mask_pallas,
        "z2": pk.z2_query_mask_pallas,
        "xz2": pk.xz2_overlap_mask_pallas,
        "xz3": pk.xz3_overlap_mask_pallas,
    }[kind]


# how many leading row-sharded args each kind's mask takes (the rest are
# replicated query descriptors)
_KIND_ROW_ARGS = {"z3": 5, "z2": 3, "xz2": 5, "xz3": 7}


def _raw_mask_fn(kind: str, mode: str, mesh):
    """Unjitted bool-mask callable for one index kind."""
    if mode == "xla":
        return _xla_mask_fn(kind)
    fn = _pallas_mask_fn(kind)
    if mode == "pallas":
        return fn
    # pallas_spmd: per-shard Pallas kernel over the row axis; row columns
    # stay sharded, query descriptors are replicated
    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.parallel.mesh import shard_map_fn

    nrow = _KIND_ROW_ARGS[kind]
    nsmall = 2 if kind in ("z3", "xz3") else 1
    return shard_map_fn(
        fn,
        mesh,
        in_specs=tuple([P(DATA_AXIS)] * nrow + [P()] * nsmall),
        out_specs=P(DATA_AXIS),
        check=False,
    )


# IN-list / '<>'-chain device cap: values dedup, then pad to pow2 K
# buckets {1,2,4,8,16,32} (bounded jit variants); longer lists answer on
# the conservative host path. Was 8 through round 4 (VERDICT #7 leftover).
_ATTR_K_CAP = 32

# jit caches shared across DeviceIndex instances: one entry per
# (kind, capacity-bucket, mode[, mesh]) — shapes bucket again inside jit
_RUNS_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}
_PACKED_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _fn_key(kind: str, mode: str, mesh) -> tuple:
    return (kind, mode, mesh)


def _gathered(mask, mesh):
    """Wrap a mask body so the extraction ops see a REPLICATED operand.

    The run/bitmap extraction ops downstream of every mask (bounded
    compaction, scatter-at, the _span_bounds framing, packbits) lower
    pathologically under GSPMD when their operand stays row-sharded:
    measured 7.1 s vs 7 ms for the same bounded-nonzero extraction at
    262k rows on the 8-device CPU mesh — a ~1000x cliff that dominated
    the CPU-mesh test/fuzz wall time. The mask computation itself
    partitions perfectly, so all-gather the bool mask (one BYTE per row
    in XLA — packbits runs after the gather) once and let extraction
    compile to its single-device form. At segment sizes that is n bytes
    over ICI per scan step (~20 MB per query at 20M rows — still small
    next to the reference's tablet servers shipping whole KV ranges
    back per scan, iterators/Z3Iterator.scala:42-65)."""
    if mesh is None or getattr(mesh, "devices", np.empty(0)).size <= 1:
        return mask
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    rep = NamedSharding(mesh, P())

    def wrapped(*args):
        out = mask(*args)
        if isinstance(out, tuple):
            return tuple(jax.lax.with_sharding_constraint(o, rep) for o in out)
        return jax.lax.with_sharding_constraint(out, rep)

    return wrapped


def _mesh_gated(fn, mesh):
    """Serialize one multi-device execution at a time through the mesh's
    dispatch gate (``mesh.gated`` / ``mesh.dispatch_gate``) — the fence
    half of the rendezvous-safety contract: XLA's collective rendezvous
    assumes programs launch in one global order per device set, and two
    threads interleaving collective-bearing programs (the ``_gathered``
    all-gather, a cross-shard ``jnp.sum``/``psum``) can deadlock it —
    the hazard PR 9's tests surfaced with concurrent SOLO queries on a
    multi-device mesh. Single-device meshes return ``fn`` unchanged,
    and the collective-free shard_map editions (shard-extract bitmaps,
    the stacked-mask SPMD kernel) never wrap at all — their layout IS
    the other half of the contract."""
    from geomesa_tpu.parallel.mesh import gated

    return gated(fn, mesh)


def _mask_runs(m, rcap: int):
    """Bool mask -> (count, n_runs, starts[rcap], ends[rcap]) — the shared
    RLE extraction both transfer layouts build on (their parity depends on
    this staying the single source of truth)."""
    cnt = jnp.sum(m.astype(jnp.int32))
    prev = jnp.concatenate([jnp.zeros((1,), m.dtype), m[:-1]])
    nxt = jnp.concatenate([m[1:], jnp.zeros((1,), m.dtype)])
    starts, nruns = compact_indices(m & ~prev, rcap, m.shape[0])
    ends, _ = compact_indices(m & ~nxt, rcap, m.shape[0])
    return cnt, nruns, starts, ends


def _runs_from_mask(m, rcap: int):
    """Bool mask -> fused RLE buffer [count, n_runs, starts*rcap, lens*rcap]."""
    cnt, nruns, starts, ends = _mask_runs(m, rcap)
    head = jnp.stack([cnt, nruns])
    return jnp.concatenate([head, starts, ends - starts + 1]).astype(jnp.int32)


def _runs_fn(kind: str, rcap: int, mode: str, mesh):
    """Mask -> fused RLE buffer (see _runs_from_mask)."""
    key = (rcap,) + _fn_key(kind, mode, mesh)
    fn = _RUNS_FNS.get(key)
    if fn is None:
        mask = _raw_mask_fn(kind, mode, mesh)
        mask = _gathered(mask, mesh)

        def run(*args):
            return _runs_from_mask(mask(*args), rcap)

        fn = _mesh_gated(instrumented_jit(f"runs.{kind}", run), mesh)
        _RUNS_FNS[key] = fn
    return fn


def _exact_mask_body(has_time: bool, mode: str, mesh, attr=False):
    """Unjitted exact-predicate mask callable (ops.filters.exact_st_mask),
    shard_map-wrapped for multi-chip meshes.

    ``attr`` adds the unified-code attribute plane (the device half of
    the reference's join attribute strategy, AttributeIndex.scala:42,392
    — evaluate the secondary attribute predicate AT the data): one extra
    row-sharded i32 ``codes`` column (ranks into the segment's sorted
    unified value space — dictionary vocab for strings, np.unique of raw
    values for numeric/date columns) tested against a replicated
    per-query vector. Two editions share the plumbing:

    - ``attr=True`` (membership): qcode shape (K,) — equality is K=1,
      IN-lists pad to the batch's K bucket; -2 = literal absent from the
      segment's value space, matching nothing; nulls are -1.
    - ``attr="range"``: qcode shape (2,) = [lo, hi] inclusive code
      interval (code order == value order because the unified space is
      sorted); empty intervals encode as lo > hi. Value predicates
      clamp lo >= 0 host-side so nulls (-1) stay out, but IS NULL is
      the deliberate interval [-1, -1] — do NOT add a codes >= 0 guard
      here (pad rows also rank -1 and are excluded by the valid mask
      inside the base st mask, not by this combine).

    jit re-specializes per K automatically (shape-keyed); the two
    editions are distinct cache-key values of ``attr``."""
    from geomesa_tpu.ops.filters import exact_st_mask

    if attr:
        combine = _attr_combine(attr)
    if has_time and attr:
        def body(xh, xl, yh, yl, th, tl, valid, codes, box, win, qcode):
            m = exact_st_mask(xh, xl, yh, yl, valid, box, th, tl, win)
            return combine(m, codes, qcode)
    elif has_time:
        def body(xh, xl, yh, yl, th, tl, valid, box, win):
            return exact_st_mask(xh, xl, yh, yl, valid, box, th, tl, win)
    elif attr:
        def body(xh, xl, yh, yl, valid, codes, box, qcode):
            m = exact_st_mask(xh, xl, yh, yl, valid, box)
            return combine(m, codes, qcode)
    else:
        def body(xh, xl, yh, yl, valid, box):
            return exact_st_mask(xh, xl, yh, yl, valid, box)
    nrow, nrep = _exact_arg_counts(has_time, attr)
    if mode != "spmd":
        return body
    from jax.sharding import PartitionSpec as P

    return shard_map_fn(
        body,
        mesh,
        in_specs=tuple([P(DATA_AXIS)] * nrow + [P()] * nrep),
        out_specs=P(DATA_AXIS),
        check=False,
    )


def _attr_combine(attr):
    """The attr-plane combinator shared by ALL mask bodies (point box,
    extent envelope, polygon ray cast — one home so the planes can never
    diverge). attr True = membership against a (K,) qcode vector;
    "range" = one inclusive [lo, hi] interval. Value predicates clamp
    lo >= 0 host-side so nulls (-1) stay out, but IS NULL is the
    deliberate interval [-1, -1] — do NOT add a codes >= 0 guard here
    (pad rows also rank -1 and are excluded by the valid mask inside
    the base masks, not by this combine)."""
    if attr == "range":
        def combine(m, codes, qcode):
            return m & (codes >= qcode[0]) & (codes <= qcode[1])
    elif attr == "notmember":
        # complement membership (`<>` chains): code NOT in the excluded
        # set AND not null — CQL `a <> x` is false on null rows, and the
        # -2 absent-literal sentinel can equal no code, so an excluded
        # value missing from this segment's space excludes nothing
        def combine(m, codes, qcode):
            return m & (codes >= 0) & ~(
                codes[:, None] == qcode[None, :]
            ).any(axis=-1)
    elif attr == "vocabmask":
        # arbitrary membership as a u8 lookup over the segment's code
        # space: qcode is a [U_pad] 0/1 vector built host-side by running
        # the ORACLE's own matcher over the sorted unified vocab (LIKE /
        # ILIKE with any wildcards — exact parity by construction).
        # Null/pad rows (-1) clip to index 0 but are excluded by the
        # codes >= 0 term
        def combine(m, codes, qcode):
            lut = qcode[jnp.clip(codes, 0, qcode.shape[0] - 1)]
            return m & (codes >= 0) & (lut > 0)
    else:
        def combine(m, codes, qcode):
            return m & (codes[:, None] == qcode[None, :]).any(axis=-1)
    return combine


def _exact_arg_counts(has_time: bool, attr) -> Tuple[int, int]:
    """(row-sharded, replicated) arg counts of the exact mask layouts —
    THE single table both _exact_mask_body's shard specs and the
    shard-extract wrapper consult (must track _exact_args)."""
    if has_time and attr:
        return 8, 3
    if has_time:
        return 7, 2
    if attr:
        return 6, 2
    return 5, 1


def _span_bounds(m):
    """(cnt, lo, hi) of a bool mask in ONE fused pass: iota-select
    min/max reductions instead of argmax over m and argmax over m[::-1]
    — the reversal materializes a full copy of the mask per query on
    TPU, which dominated the batched framing at 20M rows. Semantics
    match the argmax pair exactly, including the empty-mask case
    (lo=0, hi=n-1)."""
    n = m.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    cnt = jnp.sum(m.astype(jnp.int32))
    lo_f = jnp.min(jnp.where(m, idx, jnp.int32(n)))
    hi_f = jnp.max(jnp.where(m, idx, jnp.int32(-1)))
    lo = jnp.where(cnt > 0, lo_f, jnp.int32(0))
    hi = jnp.where(cnt > 0, hi_f, jnp.int32(n - 1))
    return cnt, lo, hi


def _bitmap_frame_step(m, span_cap: int):
    """One query's span framing: (header [cnt, lo, hi, start], packed
    window bits) — shared by the replicated and per-shard bitmap batch
    kernels (their wire parity depends on this staying single-sourced)."""
    n = m.shape[0]
    cnt, lo, hi = _span_bounds(m)
    # caller guarantees span_cap <= n and both multiples of 8
    start = jnp.clip((lo // 8) * 8, 0, n - span_cap)
    window = jax.lax.dynamic_slice(m, (start,), (span_cap,))
    return jnp.stack([cnt, lo, hi, start]), jnp.packbits(window)


_EXACT_RUNS_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}
_EXACT_PACKED_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _exact_runs_fn(has_time: bool, rcap: int, mode: str, mesh,
                   attr=False):
    key = (has_time, rcap, mode, mesh, attr)
    fn = _EXACT_RUNS_FNS.get(key)
    if fn is None:
        mask = _exact_mask_body(has_time, mode, mesh, attr)
        mask = _gathered(mask, mesh)

        def run(*args):
            return _runs_from_mask(mask(*args), rcap)

        fn = _mesh_gated(instrumented_jit("exact_runs", run), mesh)
        _EXACT_RUNS_FNS[key] = fn
    return fn


_EXACT_MASK_BATCH_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}
_EXACT_SHARD_MASK_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}
_DUAL_MASK_BATCH_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}
_DUAL_SHARD_MASK_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _mask_batch_rows(mask, has_time: bool, args, attr=False):
    """Vmapped [q, rows] bool mask over stacked point descriptors — the
    stacked-mask sibling of _point_desc_split's lax.scan split, shared
    by the replicated AND per-shard mask-batch editions (their parity
    depends on this staying single-sourced)."""
    mask_of, descs = _point_desc_split(mask, has_time, args, attr)
    return jax.vmap(lambda *d: mask_of(d))(*descs)


def _exact_mask_batch_fn(has_time: bool, q: int, mode: str, mesh, attr=False):
    """Q stacked exact predicates -> ONE full-table packed bitmap
    u8[q, n/8] in a single segment sweep — the coalescer's kernel
    (parallel/batch.py).

    The per-query RLE/span-framing machinery the other batch layouts pay
    (cumsum + bounded-nonzero per query) dominates their wall at serving
    sizes: ~60-130 ms/query vs ~0.6 ms for the mask compare itself at
    200k rows on the CPU gate box. Stacking the predicate descriptors
    and emitting the raw [N, rows] mask packed to bits skips ALL of it:
    one vmapped limb-compare pass over the resident columns, one
    packbits, n/8 bytes per query over the link, and the host demuxes
    each query's rows with the native ctz decoder (~1 ms per 1 MB).
    ``q`` is the PADDED query count (pow2 buckets keep jit shapes
    bounded); pad rows repeat the last descriptor and are never decoded.
    ``attr`` threads the rank-code attribute plane exactly like
    _exact_bitmap_batch_fn's editions (the coalescer's attr fold). On a
    multi-device mesh use _exact_shard_mask_batch_fn — the per-shard,
    collective-free edition — instead; this replicated form serves
    single-device meshes."""
    key = (has_time, q, mode, mesh, attr)
    fn = _EXACT_MASK_BATCH_FNS.get(key)
    if fn is None:
        body = _exact_mask_body(has_time, mode, mesh, attr)
        body = _gathered(body, mesh)

        def run(*args):
            m = _mask_batch_rows(body, has_time, args, attr)
            return pack_mask_rows(m)

        fn = _mesh_gated(instrumented_jit("exact_mask_batch", run), mesh)
        _EXACT_MASK_BATCH_FNS[key] = fn
    return fn


def _exact_shard_mask_batch_fn(has_time: bool, q: int, mesh, attr=False):
    """PER-SHARD edition of _exact_mask_batch_fn — the multi-chip
    stacked-mask kernel: the local mask AND the bit-pack both run INSIDE
    shard_map, so each chip sweeps only its RESIDENT rows and emits its
    own u8[q, shard_n/8] packed plane; the leading axis concatenates
    across shards -> [D*q, shard_n/8] with NO cross-chip collective at
    all (the rendezvous-safety contract's collective-free half — a
    coalesced group on an SPMD mesh compiles to one such sweep per
    chip). The host stitches shard planes with row offsets (shard d's
    rows start at d * shard_n), exactly the shard-extract bitmap
    discipline minus the span framing the mask layout exists to skip."""
    key = (has_time, q, mesh, attr)
    fn = _EXACT_SHARD_MASK_FNS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        # the UNWRAPPED local mask body: shard_map provides the locality
        local_mask = _exact_mask_body(has_time, "local", mesh, attr)
        nrow, nrep = _exact_arg_counts(has_time, attr)

        def shard_body(*args):
            m = _mask_batch_rows(local_mask, has_time, args, attr)
            return pack_mask_rows(m)  # per shard: [q, shard_n/8]

        wrapped = shard_map_fn(
            shard_body,
            mesh,
            in_specs=tuple([P(DATA_AXIS)] * nrow + [P()] * nrep),
            out_specs=P(DATA_AXIS),
            check=False,
        )
        # collective-free by construction: NOT mesh-gated (concurrent
        # stacked sweeps cannot rendezvous, so they may overlap freely)
        fn = instrumented_jit("exact_shard_mask_batch", wrapped)
        _EXACT_SHARD_MASK_FNS[key] = fn
    return fn


def _dual_mask_batch_fn(kind: str, has_time: bool, q: int, mode: str, mesh,
                        attr=False):
    """Dual-plane (hit/decided) edition of _exact_mask_batch_fn for the
    extent-envelope ('xz') and banded-polygon ('poly') coalesced folds:
    Q stacked descriptors -> (hit u8[q, n/8], decided u8[q, n/8]) full-
    table packed planes in one sweep — no span framing, no RLE. Decided
    rows are final; hit & ~decided is the ring/band the host certifies
    (_XZBatchScan's resolve contract, unchanged)."""
    key = (kind, has_time, q, mode, mesh, attr)
    fn = _DUAL_MASK_BATCH_FNS.get(key)
    if fn is None:
        if kind == "xz":
            body = _xz_exact_mask_body(has_time, mode, mesh, attr)
            split = _xz_desc_split
        else:
            body = _poly_mask_body(has_time, mode, mesh, attr)
            split = _poly_desc_split
        body = _gathered(body, mesh)

        def run(*args):
            mask_of, descs = split(body, attr, args)
            hit, dec = jax.vmap(lambda *d: mask_of(d))(*descs)
            return pack_mask_rows(hit), pack_mask_rows(dec)

        fn = _mesh_gated(instrumented_jit(f"{kind}_mask_batch", run), mesh)
        _DUAL_MASK_BATCH_FNS[key] = fn
    return fn


def _dual_shard_mask_batch_fn(kind: str, has_time: bool, q: int, mesh,
                              attr=False):
    """PER-SHARD edition of _dual_mask_batch_fn: each chip packs its
    LOCAL hit/decided planes inside shard_map -> two [D*q, shard_n/8]
    buffers, collective-free like _exact_shard_mask_batch_fn."""
    key = (kind, has_time, q, mesh, attr)
    fn = _DUAL_SHARD_MASK_FNS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        if kind == "xz":
            local = _xz_exact_mask_body(has_time, "local", mesh, attr)
            nrow, nrep = _xz_arg_counts(attr)
            split = _xz_desc_split
        else:
            local = _poly_mask_body(has_time, "local", mesh, attr)
            nrow, nrep = _poly_arg_counts(has_time, attr)
            split = _poly_desc_split

        def shard_body(*args):
            mask_of, descs = split(local, attr, args)
            hit, dec = jax.vmap(lambda *d: mask_of(d))(*descs)
            return pack_mask_rows(hit), pack_mask_rows(dec)

        wrapped = shard_map_fn(
            shard_body,
            mesh,
            in_specs=tuple([P(DATA_AXIS)] * nrow + [P()] * nrep),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            check=False,
        )
        # collective-free by construction: NOT mesh-gated
        fn = instrumented_jit(f"{kind}_shard_mask_batch", wrapped)
        _DUAL_SHARD_MASK_FNS[key] = fn
    return fn


_EXACT_COUNT_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _exact_count_fn(has_time: bool, mode: str, mesh, attr=False):
    """Mask -> scalar hit count (NO extraction, no gather-to-replicated:
    jnp.sum reduces the row-sharded mask directly — XLA inserts the
    cross-shard reduction). One i32 back over the link per execution."""
    key = (has_time, mode, mesh, attr)
    fn = _EXACT_COUNT_FNS.get(key)
    if fn is None:
        mask = _exact_mask_body(has_time, mode, mesh, attr)

        def run(*args):
            return jnp.sum(mask(*args), dtype=jnp.int32)

        fn = _mesh_gated(instrumented_jit("exact_count", run), mesh)
        _EXACT_COUNT_FNS[key] = fn
    return fn


_EXACT_STAT_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _exact_stat_hist_fn(has_time: bool, mode: str, mesh, u_pad: int):
    """Mask x target rank-codes -> i32[1 + u_pad]: [total hit count,
    per-code hit counts]. The device half of the stats push-down: the
    host reconstructs EXACT value-distribution sketches (MinMax incl.
    HLL, Enumeration, TopK, Histogram, Frequency) from per-code counts
    via the segment's sorted vocab — U counts cross the link instead of
    N rows (the StatsScan compute-at-data analog, AggregatingScan.scala:
    22-168 / KryoLazyStatsIterator). Counting is the sort + boundary-
    searchsorted shape (the measured density-edition winner on silicon),
    not a scatter-add; null/pad rows (code -1) sort into the discard
    bucket past u_pad."""
    key = (has_time, mode, mesh, u_pad)
    fn = _EXACT_STAT_FNS.get(key)
    if fn is None:
        mask = _exact_mask_body(has_time, mode, mesh, False)

        def run(tcodes, *args):
            m = mask(*args)
            cnt = jnp.sum(m, dtype=jnp.int32)
            live = m & (tcodes >= 0)
            flat = jnp.where(live, tcodes, jnp.int32(u_pad))
            s = jnp.sort(flat)
            bounds = jnp.searchsorted(
                s, jnp.arange(u_pad + 1, dtype=jnp.int32)
            ).astype(jnp.int32)
            hist = jnp.diff(bounds)
            return jnp.concatenate([cnt[None], hist])

        fn = _mesh_gated(instrumented_jit("exact_stat_hist", run), mesh)
        _EXACT_STAT_FNS[key] = fn
    return fn


def _point_desc_split(mask, has_time: bool, args, attr=False):
    """Shared arg split for the point batch builders: returns
    (mask_of(desc), stacked desc arrays for lax.scan). ``attr`` adds the
    codes column (row-sharded) and per-query qcode vectors [q, K] to
    the scan (K = pow2 membership bucket, equality is K=1)."""
    if has_time and attr:
        xh, xl, yh, yl, th, tl, valid, codes, boxes, wins, qcodes = args
        return (
            lambda d: mask(xh, xl, yh, yl, th, tl, valid, codes,
                           d[0], d[1], d[2]),
            (boxes, wins, qcodes),
        )
    if has_time:
        xh, xl, yh, yl, th, tl, valid, boxes, wins = args
        return (
            lambda d: mask(xh, xl, yh, yl, th, tl, valid, d[0], d[1]),
            (boxes, wins),
        )
    if attr:
        xh, xl, yh, yl, valid, codes, boxes, qcodes = args
        return (
            lambda d: mask(xh, xl, yh, yl, valid, codes, d[0], d[1]),
            (boxes, qcodes),
        )
    xh, xl, yh, yl, valid, boxes = args
    return lambda d: mask(xh, xl, yh, yl, valid, d[0]), (boxes,)


def _start_d2h(*bufs) -> None:
    """Kick device->host copies without blocking (best effort)."""
    for b in bufs:
        try:
            b.copy_to_host_async()
        except Exception:  # pragma: no cover - transfer started lazily
            pass


_EXACT_BITMAP_BATCH_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _exact_bitmap_batch_fn(has_time: bool, span_cap: int, q: int, mode: str,
                           mesh, attr=False):
    """Q exact scans -> (headers i32[q,4], bitmaps u8[q, span_cap//8]).

    The TPU-native extraction: NO compaction on device. Here the device only
    does streaming-friendly work: the mask, fused iota-select min/max
    reductions for the first/last hit (_span_bounds — no mask reversal),
    a dynamic-slice of the span window, and a bit-pack.
    The host unpacks and RLE-extracts at C speed from the (span-framed)
    bitmap. Header = (count, lo, hi, slice_start); a span wider than
    span_cap is detected host-side (hi - start + 1 > span_cap) and that
    query refetches singly while the segment learns a bigger span bucket.

    DeviceSegment.dispatch_exact_batch runs it on a single-device mesh;
    a multi-device mesh frames per shard instead
    (_exact_shard_bitmap_batch_fn).
    """
    key = (has_time, span_cap, q, mode, mesh, attr)
    fn = _EXACT_BITMAP_BATCH_FNS.get(key)
    if fn is None:
        mask = _exact_mask_body(has_time, mode, mesh, attr)
        mask = _gathered(mask, mesh)

        def run(*args):
            mask_of, descs = _point_desc_split(mask, has_time, args, attr)

            def step(carry, d):
                return carry, _bitmap_frame_step(mask_of(d), span_cap)

            _, (headers, bitmaps) = jax.lax.scan(step, 0, descs)
            return headers, bitmaps

        fn = _mesh_gated(instrumented_jit("exact_bitmap_batch", run), mesh)
        _EXACT_BITMAP_BATCH_FNS[key] = fn
    return fn


_EXACT_SHARD_BITMAP_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _exact_shard_bitmap_batch_fn(has_time: bool, span_cap: int, q: int,
                                 mesh, attr=False):
    """PER-SHARD extraction edition of _exact_bitmap_batch_fn: the mask
    AND the span framing both run INSIDE shard_map, so each chip frames
    only its LOCAL hit window — no cross-chip collective at all, not
    even the mask all-gather. The host stitches shard windows with row
    offsets (shard d's rows start at d * shard_n). This is the true pod
    shape: per-tablet partial results merged client-side
    (AccumuloQueryPlan.scala:113-140), with D2H = D small windows
    instead of one gathered mask. ``span_cap`` is the PER-SHARD window
    (multiple of 8, <= shard_n); a shard whose true span exceeds it
    triggers the single-query fallback host-side."""
    key = (has_time, span_cap, q, mesh, attr)
    fn = _EXACT_SHARD_BITMAP_FNS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        # the UNWRAPPED local mask body: shard_map provides the locality
        local_mask = _exact_mask_body(has_time, "local", mesh, attr)
        nrow, nrep = _exact_arg_counts(has_time, attr)

        def shard_body(*args):
            mask_of, descs = _point_desc_split(
                local_mask, has_time, args, attr
            )

            def step(carry, d):
                # LOCAL rows only: shard_map scopes the mask to the shard
                return carry, _bitmap_frame_step(mask_of(d), span_cap)

            _, (headers, bitmaps) = jax.lax.scan(step, 0, descs)
            return headers, bitmaps  # per shard: [q, 4], [q, span_cap//8]

        wrapped = shard_map_fn(
            shard_body,
            mesh,
            in_specs=tuple([P(DATA_AXIS)] * nrow + [P()] * nrep),
            # leading axis concatenates across shards -> [D*q, ...]
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            check=False,
        )
        fn = instrumented_jit("exact_shard_bitmap_batch", wrapped)
        _EXACT_SHARD_BITMAP_FNS[key] = fn
    return fn


class _ShardBitmapBatch:
    """One per-shard bitmap batch: [D*q, 4] headers + [D*q, cap//8]
    windows, fetched once; shard d / query i slices at d*q + i."""

    __slots__ = ("hdr", "bits", "span_cap", "n_shards", "q", "shard_n",
                 "seg", "_np", "proto", "local_shards")

    def __init__(self, hdr, bits, span_cap, n_shards, q, shard_n,
                 seg=None, proto=""):
        self.hdr = hdr
        self.bits = bits
        self.span_cap = span_cap
        self.n_shards = n_shards
        self.q = q
        self.shard_n = shard_n
        self.seg = seg
        self._np = None
        self.proto = proto
        # None = single-process (all shards readable); else the set of
        # shard indices THIS process owns — overflow fallbacks must
        # filter their (replicated, global) rows to these shards or a
        # multi-process union would double-count the overflowing query
        self.local_shards: Optional[set] = None

    def _fetch(self):
        if self._np is None:
            with _shared_fetch_span(self.q, self.proto):
                if not getattr(self.hdr, "is_fully_addressable", True):
                    self.local_shards = {
                        int(s.index[0].start or 0) // self.q
                        for s in self.hdr.addressable_shards
                    }
                h = _np_local(self.hdr).reshape(self.n_shards, self.q, 4)
                b = _np_local(self.bits).reshape(self.n_shards, self.q, -1)
            self._np = (h, b)
            self.hdr = self.bits = None
            if self.seg is not None:
                nonempty = h[:, :, 0] > 0
                spans = np.where(nonempty, h[:, :, 2] - h[:, :, 3] + 1, 0)
                self.seg.remember_shard_span(int(spans.max(initial=0)))
        return self._np


def _np_local(arr) -> np.ndarray:
    """Host view of a device array that may span MULTIPLE PROCESSES.

    Also the ``device.fetch`` fault point: every scan-resolution D2H
    transfer funnels through here, so an injected fetch fault surfaces
    exactly where a lost device mid-query would — and the datastore's
    degradation path re-answers from the host scan.

    On a jax.distributed (DCN) mesh the per-shard outputs are global
    arrays whose remote shards this process cannot read — np.asarray
    raises. Read the ADDRESSABLE shards into a zero-filled global-shaped
    buffer instead: a zeroed header row is an empty window (count 0), so
    each process resolves exactly its own shards' hits — the per-executor
    partial results the reference's Spark partitions return
    (GeoMesaSpark.scala:38-50), with the client (caller) unioning
    processes. Single-process arrays take the plain asarray path.

    The ``device.fetch`` span mirrors the fault point: every D2H
    boundary crossing lands on the owning query's trace with the bytes
    that moved (the kernel-vs-link split of arxiv 2203.14362 §5)."""
    with trace.span("device.fetch", bytes=int(getattr(arr, "nbytes", 0))):
        deadline.check("device.fetch")
        faults.fault_point("device.fetch")
        if getattr(arr, "is_fully_addressable", True):
            out = np.asarray(arr)
            fetched = int(getattr(arr, "nbytes", 0))
        else:
            out = np.zeros(arr.shape, dtype=arr.dtype)
            fetched = 0
            for s in arr.addressable_shards:
                local = np.asarray(s.data)
                out[s.index] = local
                fetched += int(local.nbytes)  # only LOCAL shards crossed
        # counted AFTER the read: a faulted fetch that degraded to the
        # host scan moved nothing over the link
        count_d2h(fetched)
        return out


def join_upload(mesh, xs: np.ndarray, ys: np.ndarray, floor: int = 64):
    """Upload one spatial-join probe group through the segment-upload
    path: f32 coordinate pair, NaN-padded to the pow2 bucket above the
    group (NaN probe rows fall out of every join kernel comparison), pad
    efficiency recorded like any mirror upload, H2D crossing the
    ``device.dispatch`` boundary (fault point + span + byte counters) via
    ``mesh.replicate``. Returns (x_dev, y_dev)."""
    n = len(xs)
    cap = _pow2_at_least(max(n, 1), floor)
    px = np.full(cap, np.nan, dtype=np.float32)
    py = np.full(cap, np.nan, dtype=np.float32)
    px[:n] = xs
    py[:n] = ys
    record_pad(n, cap, kind="join")
    return replicate(mesh, px), replicate(mesh, py)


def join_fetch(arr) -> np.ndarray:
    """Resolve a join kernel's mask output to host: the ``device.fetch``
    boundary (fault point + span + D2H byte counters), shared with every
    other scan-resolution transfer."""
    return _np_local(arr)


class _PendingShardBitmapHits:
    """One query's slice across every shard window: decode each shard's
    bitmap, offset by the shard's row base, concatenate (rows stay
    sorted — shard bases ascend). Any shard span wider than the window
    falls back to the single-query refetch."""

    __slots__ = ("seg", "batch", "i", "_refetch", "_packed", "_rows")

    def __init__(self, seg, batch: _ShardBitmapBatch, i: int, refetch, packed):
        self.seg = seg
        self.batch = batch
        self.i = i
        self._refetch = refetch
        self._packed = packed
        self._rows: Optional[np.ndarray] = None

    def rows(self) -> np.ndarray:
        if self._rows is None:
            self._rows = self._resolve()
        return self._rows

    def _resolve(self) -> np.ndarray:
        h, b = self.batch._fetch()
        parts = []
        for d in range(self.batch.n_shards):
            cnt, _lo, hi, start = (int(v) for v in h[d, self.i])
            if cnt == 0:
                continue
            if hi - start + 1 > self.batch.span_cap:
                # one overflowing shard: re-answer the whole query singly
                rows = _PendingHits(
                    self.seg, self.seg._rcap,
                    self._refetch(self.seg._rcap), self._refetch,
                    self._packed,
                ).rows()
                if self.batch.local_shards is not None:
                    # the refetch is replicated (GLOBAL rows) but this
                    # process must keep the per-partition contract: only
                    # rows on its own shards (the union across processes
                    # re-covers everything exactly once)
                    sn = self.batch.shard_n
                    keep = np.isin(rows // sn,
                                   np.fromiter(self.batch.local_shards,
                                               dtype=np.int64))
                    rows = rows[keep]
                return rows
            base = d * self.batch.shard_n
            parts.append(
                base + _decode_bitmap_rows(b[d, self.i], start, cnt)
            )
        if not parts:
            return np.empty(0, dtype=np.int64)
        return np.concatenate(parts)


def _decode_bitmap_rows(bits: np.ndarray, start: int, max_out: int) -> np.ndarray:
    """Span-window bitmap -> global row indices: C++ ctz-style decode
    (native/bitdecode.cpp, ~1 ms per 1 MB window) with the numpy
    unpackbits fallback (~35 ms). ``max_out`` is the wire header's hit
    count (every set bit lies inside the span window by construction)."""
    from geomesa_tpu.native import bitmap_rows_native

    rows = bitmap_rows_native(bits, start, max_out)
    if rows is not None:
        return rows
    return start + np.flatnonzero(np.unpackbits(bits)).astype(np.int64)


_POPCOUNT8 = np.unpackbits(
    np.arange(256, dtype=np.uint8)[:, None], axis=1
).sum(axis=1).astype(np.int64)


def _decode_full_bitmap_rows(packed: np.ndarray, n: int) -> np.ndarray:
    """Full-table packed bitmap -> row indices < n (the dense-degrade
    transfers): popcount-table count + the native decode, numpy
    fallback. Pad bits beyond n are always clear (the valid mask), so
    the bound check is belt and braces."""
    packed = np.asarray(packed)
    cnt = int(_POPCOUNT8[packed].sum())
    rows = _decode_bitmap_rows(packed, 0, cnt)
    if len(rows) and rows[-1] >= n:
        rows = rows[rows < n]
    return rows


def _shared_fetch_span(q: int, proto: str):
    """Span around a BATCHED buffer fetch serving ``q`` queries. The
    blocked wall of the whole shared sweep lands on whichever query
    resolves first, so the span carries ``shared_q`` — the slow-query
    batch log (store/datastore.py ``_log_slow_batch``) apportions the
    wait across the members that rode the sweep instead of blaming the
    first member's span tree for all of it. ``proto`` names the batch's
    wire layout (``bitmap``, ``bitmap_shard``, ``mask_{kind}``...)."""
    return trace.span("device.fetch.shared", shared_q=int(q), proto=proto)


class _MaskBatch:
    """One coalesced mask-batch buffer: u8[q, n/8] full-table packed
    bitmaps (see _exact_mask_batch_fn), fetched once. ``prefetch``-able:
    the coalescer resolves the shared D2H inside its OWN cost collector
    so the sweep's bytes split across members instead of landing in the
    first resolver's receipt."""

    __slots__ = ("buf", "n_rows", "q_real", "_np", "proto")

    def __init__(self, buf, n_rows: int, q_real: int, proto=""):
        self.buf = buf
        self.n_rows = n_rows  # real (unpadded) segment rows
        self.q_real = q_real
        self._np = None
        self.proto = proto

    def _fetch(self):
        if self._np is None:
            with _shared_fetch_span(self.q_real, self.proto):
                self._np = _np_local(self.buf)
            self.buf = None
        return self._np


class _PendingMaskHits:
    """One query's row of a coalesced mask batch: decode the full-table
    packed bitmap with the native ctz decoder. No span framing, no
    capacity escalation — the bitmap covers every row by construction."""

    __slots__ = ("batch", "i", "_rows")

    def __init__(self, batch: "_MaskBatch", i: int):
        self.batch = batch
        self.i = i
        self._rows: Optional[np.ndarray] = None

    def prefetch(self) -> None:
        self.batch._fetch()

    def rows(self) -> np.ndarray:
        if self._rows is None:
            self._rows = _decode_full_bitmap_rows(
                self.batch._fetch()[self.i], self.batch.n_rows
            )
        return self._rows


class _ShardMaskBatch:
    """One SPMD coalesced mask-batch buffer: u8[D*q, shard_n/8] per-shard
    packed planes (see _exact_shard_mask_batch_fn), fetched once; shard d
    / query i slices at [d, i] after the reshape. ``prefetch``-able like
    _MaskBatch so the coalescer's shared D2H apportions across members.
    On a multi-process (DCN) mesh _np_local zero-fills the shards this
    process cannot read, and zero bits decode to no rows — each process
    resolves exactly its own shards' hits, union across processes."""

    __slots__ = ("buf", "n_rows", "n_shards", "q", "q_real", "shard_n",
                 "_np", "proto")

    def __init__(self, buf, n_rows: int, n_shards: int, q: int, q_real: int,
                 shard_n: int, proto=""):
        self.buf = buf
        self.n_rows = n_rows  # real (unpadded) segment rows
        self.n_shards = n_shards
        self.q = q  # padded query count (the wire layout's stride)
        self.q_real = q_real
        self.shard_n = shard_n
        self._np = None
        self.proto = proto

    def _fetch(self):
        if self._np is None:
            with _shared_fetch_span(self.q_real, self.proto):
                self._np = _np_local(self.buf).reshape(
                    self.n_shards, self.q, -1
                )
            self.buf = None
        return self._np


class _PendingShardMaskHits:
    """One query's row of an SPMD coalesced mask batch: decode each
    shard's full-plane bitmap with the native ctz decoder, offset by the
    shard's row base, concatenate (rows stay sorted — shard bases
    ascend). No span framing, no capacity escalation — each plane covers
    every resident row of its shard by construction."""

    __slots__ = ("batch", "i", "_rows")

    def __init__(self, batch: "_ShardMaskBatch", i: int):
        self.batch = batch
        self.i = i
        self._rows: Optional[np.ndarray] = None

    def prefetch(self) -> None:
        self.batch._fetch()

    def rows(self) -> np.ndarray:
        if self._rows is None:
            b = self.batch._fetch()
            sn = self.batch.shard_n
            parts = []
            for d in range(self.batch.n_shards):
                base = d * sn
                bound = min(sn, self.batch.n_rows - base)
                if bound <= 0:
                    break  # later shards hold only pad rows
                got = _decode_full_bitmap_rows(b[d, self.i], bound)
                if len(got):
                    parts.append(base + got)
            self._rows = (
                np.concatenate(parts) if parts
                else np.empty(0, dtype=np.int64)
            )
        return self._rows


class _DualMaskBatch:
    """One coalesced dual-plane (hit/decided) mask-batch buffer pair for
    the extent/polygon folds: single-device [q, n/8] x2, or per-shard
    [D*q, shard_n/8] x2 (n_shards=1 IS the single-device case — one
    class, one fetch/decode path)."""

    __slots__ = ("hit", "dec", "n_rows", "n_shards", "q", "q_real",
                 "shard_n", "_np", "proto")

    def __init__(self, hit, dec, n_rows: int, n_shards: int, q: int,
                 q_real: int, shard_n: int, proto=""):
        self.hit = hit
        self.dec = dec
        self.n_rows = n_rows
        self.n_shards = n_shards
        self.q = q
        self.q_real = q_real
        self.shard_n = shard_n
        self._np = None
        self.proto = proto

    def _fetch(self):
        if self._np is None:
            with _shared_fetch_span(self.q_real, self.proto):
                h = _np_local(self.hit).reshape(self.n_shards, self.q, -1)
                d = _np_local(self.dec).reshape(self.n_shards, self.q, -1)
            self._np = (h, d)
            self.hit = self.dec = None
        return self._np


class _PendingDualMaskHits:
    """One extent/polygon query's slice of a coalesced dual mask batch:
    rows() -> (hit_rows, decided_rows), both sorted, decided a subset of
    hit — the _XZBatchScan resolve contract, full-table planes instead
    of span windows (no overflow fallback to need)."""

    __slots__ = ("batch", "i", "_rows")

    def __init__(self, batch: "_DualMaskBatch", i: int):
        self.batch = batch
        self.i = i
        self._rows = None

    def prefetch(self) -> None:
        self.batch._fetch()

    def rows(self):
        if self._rows is None:
            h, dc = self.batch._fetch()
            sn = self.batch.shard_n
            hits, decs = [], []
            for d in range(self.batch.n_shards):
                base = d * sn
                bound = min(sn, self.batch.n_rows - base)
                if bound <= 0:
                    break
                got = _decode_full_bitmap_rows(h[d, self.i], bound)
                if len(got):
                    hits.append(base + got)
                got = _decode_full_bitmap_rows(dc[d, self.i], bound)
                if len(got):
                    decs.append(base + got)
            empty = np.empty(0, dtype=np.int64)
            self._rows = (
                np.concatenate(hits) if hits else empty,
                np.concatenate(decs) if decs else empty,
            )
        return self._rows


class _BitmapBatch:
    """One bitmap batch (headers + span-framed bitmaps), fetched once.
    Remembers the stream's widest span on the segment (once per batch)."""

    __slots__ = ("hdr", "bits", "span_cap", "seg", "_np", "proto")

    def __init__(self, hdr, bits, span_cap: int, seg=None, proto=""):
        self.hdr = hdr
        self.bits = bits
        self.span_cap = span_cap
        self.seg = seg
        self._np = None
        self.proto = proto

    def _fetch(self):
        if self._np is None:
            with _shared_fetch_span(self.hdr.shape[0], self.proto):
                self._np = (_np_local(self.hdr), _np_local(self.bits))
            self.hdr = self.bits = None
            if self.seg is not None:
                h = self._np[0]
                nonempty = h[:, 0] > 0
                spans = np.where(nonempty, h[:, 2] - h[:, 3] + 1, 0)
                self.seg.remember_span(int(spans.max(initial=0)))
        return self._np

    def header(self, i: int) -> np.ndarray:
        return self._fetch()[0][i]

    def query_bits(self, i: int) -> np.ndarray:
        return self._fetch()[1][i]


class _PendingBitmapHits:
    """One query's slice of a bitmap batch: unpacks the span window and
    extracts hit rows host-side; a span wider than the window falls back
    to the single-query runs refetch."""

    __slots__ = ("seg", "batch", "i", "_refetch", "_packed", "_rows")

    def __init__(self, seg: "DeviceSegment", batch: _BitmapBatch, i: int,
                 refetch, packed):
        self.seg = seg
        self.batch = batch
        self.i = i
        self._refetch = refetch
        self._packed = packed
        self._rows: Optional[np.ndarray] = None

    def rows(self) -> np.ndarray:
        if self._rows is None:
            self._rows = self._resolve()
        return self._rows

    def _resolve(self) -> np.ndarray:
        header = self.batch.header(self.i)
        cnt, _lo, hi, start = (int(v) for v in header)
        if cnt == 0:
            return np.empty(0, dtype=np.int64)
        if hi - start + 1 > self.batch.span_cap:
            return _PendingHits(
                self.seg, self.seg._rcap,
                self._refetch(self.seg._rcap), self._refetch, self._packed,
            ).rows()
        return _decode_bitmap_rows(self.batch.query_bits(self.i), start, cnt)


def _xz_arg_counts(attr) -> Tuple[int, int]:
    """(row-sharded, replicated) arg counts of the extent mask layouts —
    THE single table for _xz_exact_mask_body's shard specs, the dual
    shard-extract kernels, and DeviceSegment._xz_args (must stay in
    lock-step)."""
    if attr:
        return 13, 3  # + codes column / + qcode vector
    return 12, 2


def _xz_exact_mask_body(has_time: bool, mode: str, mesh, attr=False):
    """Unjitted full-scan extent mask: (hit, decided) over ALL rows.

    hit = stored envelope overlaps the query envelope (exact f64 via
    sort-key limb compares) AND the time window matches (xz3); decided =
    provably final (rectangle query AND (envelope inside the box, or an
    isrect feature), never a placeholder/null geometry). hit & ~decided is
    the boundary-straddling ring that still needs the host's per-geometry
    test — the same decision logic as the candidate-gather devseek
    (_devseek_xz_fn) but streaming, which is how this hardware wants it.

    ``attr`` adds the unified-rank-code attribute plane exactly like
    _exact_mask_body's editions (True = membership over a (K,) qcode
    vector, "range" = one inclusive [lo, hi] interval): the attr test
    ANDs into ``hit`` BEFORE ``decided`` derives from it, so decided
    rows are final for the full spatial-AND-attr predicate and the ring
    only ever carries attr-passing rows (the host's per-geometry test
    needs no attr re-check).

    Query descriptor qbox: u32[12] = (xmin, ymin, xmax, ymax, zero) x
    (hi, lo) limbs + [rect_flag, 0]."""
    from geomesa_tpu.ops.zkernels import limbs_in_range, limbs_leq

    if attr:
        acomb = _attr_combine(attr)

    def parts(
        bxmin_h, bxmin_l, bymin_h, bymin_l, bxmax_h, bxmax_l,
        bymax_h, bymax_l, isrect, valid, th, tl, qbox, win,
    ):
        """(hit, finalizable): decided = hit & finalizable (callers AND
        the attr plane into hit FIRST when present)."""
        qxmin_h, qxmin_l = qbox[0], qbox[1]
        qymin_h, qymin_l = qbox[2], qbox[3]
        qxmax_h, qxmax_l = qbox[4], qbox[5]
        qymax_h, qymax_l = qbox[6], qbox[7]
        zero_h, zero_l = qbox[8], qbox[9]
        rect = qbox[10] != 0
        overlap = (
            limbs_leq(qxmin_h, qxmin_l, bxmax_h, bxmax_l)
            & limbs_leq(bxmin_h, bxmin_l, qxmax_h, qxmax_l)
            & limbs_leq(qymin_h, qymin_l, bymax_h, bymax_l)
            & limbs_leq(bymin_h, bymin_l, qymax_h, qymax_l)
        )
        placeholder = (
            (bxmin_h == zero_h) & (bxmin_l == zero_l)
            & (bymin_h == zero_h) & (bymin_l == zero_l)
            & (bxmax_h == zero_h) & (bxmax_l == zero_l)
            & (bymax_h == zero_h) & (bymax_l == zero_l)
        )
        inside = (
            limbs_leq(qxmin_h, qxmin_l, bxmin_h, bxmin_l)
            & limbs_leq(bxmax_h, bxmax_l, qxmax_h, qxmax_l)
            & limbs_leq(qymin_h, qymin_l, bymin_h, bymin_l)
            & limbs_leq(bymax_h, bymax_l, qymax_h, qymax_l)
        )
        hit = overlap & valid
        if has_time:
            hit = hit & limbs_in_range(th, tl, win[0], win[1], win[2], win[3])
        return hit, rect & ~placeholder & (inside | isrect)

    if attr:
        def core(
            bxmin_h, bxmin_l, bymin_h, bymin_l, bxmax_h, bxmax_l,
            bymax_h, bymax_l, isrect, valid, th, tl, codes,
            qbox, win, qcode,
        ):
            hit, fin = parts(
                bxmin_h, bxmin_l, bymin_h, bymin_l, bxmax_h, bxmax_l,
                bymax_h, bymax_l, isrect, valid, th, tl, qbox, win,
            )
            hit = acomb(hit, codes, qcode)
            return hit, hit & fin
    else:
        def core(
            bxmin_h, bxmin_l, bymin_h, bymin_l, bxmax_h, bxmax_l,
            bymax_h, bymax_l, isrect, valid, th, tl, qbox, win,
        ):
            hit, fin = parts(
                bxmin_h, bxmin_l, bymin_h, bymin_l, bxmax_h, bxmax_l,
                bymax_h, bymax_l, isrect, valid, th, tl, qbox, win,
            )
            return hit, hit & fin

    if mode != "spmd":
        return core
    from jax.sharding import PartitionSpec as P

    nrow, nrep = _xz_arg_counts(attr)
    return shard_map_fn(
        core,
        mesh,
        in_specs=tuple([P(DATA_AXIS)] * nrow + [P()] * nrep),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        check=False,
    )


def _xz_desc_split(mask, attr, args):
    """Shared arg split for the extent batch builders (the dual-plane
    edition of _point_desc_split): (mask_of(desc), stacked desc arrays
    for lax.scan)."""
    if attr:
        *cols, qboxes, wins, qcodes = args
        return (lambda d: mask(*cols, d[0], d[1], d[2])), (qboxes, wins, qcodes)
    *cols, qboxes, wins = args
    return (lambda d: mask(*cols, d[0], d[1])), (qboxes, wins)


_XZ_RUNS_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}
_XZ_PACKED_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _xz_dual_runs(hit, decided, rcap: int):
    """(hit, decided) masks -> one fused buffer [2 x (2 + 2*rcap)]."""
    return jnp.concatenate(
        [_runs_from_mask(hit, rcap), _runs_from_mask(decided, rcap)]
    )


_XZ_BITMAP_BATCH_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _dual_bitmap_row(hit, decided, span_cap: int):
    """(hit, decided) masks -> (header i32[4], bits u8[2*span_cap//8]):
    THE span-framed dual-plane wire step (header = cnt/lo/hi/start keyed
    on the hit span; decided is a subset so one window frames both) —
    shared by the xz and polygon bitmap batch kernels."""
    n = hit.shape[0]
    cnt, lo, hi = _span_bounds(hit)
    start = jnp.clip((lo // 8) * 8, 0, n - span_cap)
    hw = jax.lax.dynamic_slice(hit, (start,), (span_cap,))
    dw = jax.lax.dynamic_slice(decided, (start,), (span_cap,))
    bits = jnp.concatenate([jnp.packbits(hw), jnp.packbits(dw)])
    return jnp.stack([cnt, lo, hi, start]), bits


def _xz_bitmap_batch_fn(has_time: bool, span_cap: int, q: int, mode: str,
                        mesh, attr=False):
    """Extent edition of _exact_bitmap_batch_fn (see _dual_bitmap_row)."""
    key = (has_time, span_cap, q, mode, mesh, attr)
    fn = _XZ_BITMAP_BATCH_FNS.get(key)
    if fn is None:
        mask = _xz_exact_mask_body(has_time, mode, mesh, attr)
        mask = _gathered(mask, mesh)

        def run(*args):
            mask_of, descs = _xz_desc_split(mask, attr, args)

            def step(carry, d):
                hit, decided = mask_of(d)
                return carry, _dual_bitmap_row(hit, decided, span_cap)

            _, (headers, bitmaps) = jax.lax.scan(step, 0, descs)
            return headers, bitmaps

        fn = _mesh_gated(instrumented_jit("xz_bitmap_batch", run), mesh)
        _XZ_BITMAP_BATCH_FNS[key] = fn
    return fn


_DUAL_SHARD_BITMAP_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _dual_shard_bitmap_batch_fn(kind: str, has_time: bool, span_cap: int,
                                q: int, mesh, attr=False):
    """PER-SHARD extraction edition of the dual-plane bitmap batches
    (``kind`` = 'xz' extent envelopes | 'poly' banded ray cast): the
    local mask AND the dual span framing run INSIDE shard_map, each chip
    framing its LOCAL hit/decided windows; the host stitches shard rows
    with offsets (see _exact_shard_bitmap_batch_fn — same shape, two
    planes per window). ``attr`` threads the rank-code attribute plane
    through the local mask (both kinds)."""
    key = (kind, has_time, span_cap, q, mesh, attr)
    fn = _DUAL_SHARD_BITMAP_FNS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        if kind == "xz":
            local = _xz_exact_mask_body(has_time, "local", mesh, attr)
            nrow, nrep = _xz_arg_counts(attr)

            def split(args):
                return _xz_desc_split(local, attr, args)
        else:
            local = _poly_mask_body(has_time, "local", mesh, attr)
            nrow, nrep = _poly_arg_counts(has_time, attr)

            def split(args):
                return _poly_desc_split(local, attr, args)

        def shard_body(*args):
            mask_of, descs = split(args)

            def step(carry, d):
                hit, dec = mask_of(d)
                return carry, _dual_bitmap_row(hit, dec, span_cap)

            _, (headers, bitmaps) = jax.lax.scan(step, 0, descs)
            return headers, bitmaps  # per shard: [q,4], [q, 2*cap//8]

        wrapped = shard_map_fn(
            shard_body,
            mesh,
            in_specs=tuple([P(DATA_AXIS)] * nrow + [P()] * nrep),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            check=False,
        )
        fn = instrumented_jit(f"{kind}_shard_bitmap_batch", wrapped)
        _DUAL_SHARD_BITMAP_FNS[key] = fn
    return fn


class _PendingDualShardBitmapHits:
    """One extent/polygon query across every shard's dual windows:
    rows() -> (hit_rows, decided_rows) stitched with shard offsets; any
    shard span wider than the window falls back to the single-query
    dual-runs refetch."""

    __slots__ = ("seg", "batch", "i", "_refetch", "_packed", "_rows")

    def __init__(self, seg, batch: "_ShardBitmapBatch", i: int,
                 refetch, packed):
        self.seg = seg
        self.batch = batch
        self.i = i
        self._refetch = refetch
        self._packed = packed
        self._rows = None

    def rows(self):
        if self._rows is None:
            self._rows = self._resolve()
        return self._rows

    def _resolve(self):
        h, b = self.batch._fetch()
        hits, decs = [], []
        for d in range(self.batch.n_shards):
            cnt, _lo, hi, start = (int(v) for v in h[d, self.i])
            if cnt == 0:
                continue
            if hi - start + 1 > self.batch.span_cap:
                return _PendingXZHits(
                    self.seg, self.seg._rcap,
                    self._refetch(self.seg._rcap), self._refetch,
                    self._packed,
                ).rows()
            both = b[d, self.i]
            half = len(both) // 2
            base = d * self.batch.shard_n
            hits.append(base + _decode_bitmap_rows(both[:half], start, cnt))
            decs.append(base + _decode_bitmap_rows(both[half:], start, cnt))
        empty = np.empty(0, dtype=np.int64)
        return (
            np.concatenate(hits) if hits else empty,
            np.concatenate(decs) if decs else empty,
        )


class _PendingXZBitmapHits:
    """One extent query's slice of a bitmap batch: rows() -> (hit_rows,
    decided_rows), like _PendingXZHits; span overflow falls back to the
    single-query dual-runs refetch."""

    __slots__ = ("seg", "batch", "i", "_refetch", "_packed", "_rows")

    def __init__(self, seg: "DeviceSegment", batch: "_BitmapBatch", i: int,
                 refetch, packed):
        self.seg = seg
        self.batch = batch
        self.i = i
        self._refetch = refetch
        self._packed = packed
        self._rows = None

    def rows(self):
        if self._rows is None:
            self._rows = self._resolve()
        return self._rows

    def _resolve(self):
        header = self.batch.header(self.i)
        cnt, _lo, hi, start = (int(v) for v in header)
        if cnt == 0:
            empty = np.empty(0, dtype=np.int64)
            return empty, empty
        if hi - start + 1 > self.batch.span_cap:
            return _PendingXZHits(
                self.seg, self.seg._rcap,
                self._refetch(self.seg._rcap), self._refetch, self._packed,
            ).rows()
        both = self.batch.query_bits(self.i)
        h = len(both) // 2
        return (
            _decode_bitmap_rows(both[:h], start, cnt),
            _decode_bitmap_rows(both[h:], start, cnt),  # decided <= hit
        )


# banded polygon ray cast: rows within EPS of a ring vertex's latitude, or
# within XINT_K*EPS of a computed edge crossing, are BAND rows (device
# cannot certify them in f32) and take the host's exact test; everything
# else is decided on device. EPS covers f32 coordinate rounding (ulp at
# |lon|<=180 is ~1.5e-5) plus crossing arithmetic error with wide margin.
POLY_EPS = 1e-4
POLY_XINT_K = 16.0


def _poly_arg_counts(has_time: bool, attr) -> Tuple[int, int]:
    """(row-sharded, replicated) arg counts of the polygon mask layouts —
    THE single table for _poly_mask_body's shard specs, the dual
    shard-extract kernels, and DeviceSegment._poly_args."""
    nrow = 9 if has_time else 7
    if attr:
        return nrow + 1, 4  # + codes column / + qcode vector
    return nrow, 3


def _poly_mask_body(has_time: bool, mode: str, mesh, attr=False):
    """Unjitted banded point-in-polygon mask: (hit, decided) over ALL rows.

    The device analog of the host's exact geometry post-filter for
    point-schema INTERSECTS(polygon) queries (role of the tserver-side
    filter push-down, accumulo/iterators/FilterTransformIterator.scala):
    exact envelope bound via sort-key limb compares, then an f32 ray cast
    over the polygon's edges (lax.scan; streaming, no gathers). Crossing
    parity decides in/out; rows inside the error band stay hit-but-
    undecided and the host certifies them — identical results to the host
    path by construction, device work O(N * edges) streaming.

    ``attr`` threads the rank-code attribute plane (True = membership,
    "range" = [lo, hi] interval): the attr test ANDs into ``hit`` before
    ``decided`` derives, so the band ring only carries attr-passing rows
    (the host certification needs no attr re-check)."""
    from geomesa_tpu.ops.filters import exact_st_mask

    if attr:
        acomb = _attr_combine(attr)

    def core(xh, xl, yh, yl, th, tl, valid, xf, yf, edges, box, win):
        if has_time:
            env = exact_st_mask(xh, xl, yh, yl, valid, box, th, tl, win)
        else:
            env = exact_st_mask(xh, xl, yh, yl, valid, box)
        eps = jnp.float32(POLY_EPS)
        keps = jnp.float32(POLY_EPS * POLY_XINT_K)

        def step(carry, e):
            crossings, band = carry
            x1, y1, x2, y2 = e[0], e[1], e[2], e[3]
            degen = (x1 == x2) & (y1 == y2)
            straddle = (y1 > yf) != (y2 > yf)
            dy = jnp.where(y2 == y1, jnp.float32(1.0), y2 - y1)
            xint = x1 + (yf - y1) / dy * (x2 - x1)
            cross = straddle & (xf < xint) & ~degen
            near = (jnp.abs(yf - y1) < eps) | (jnp.abs(yf - y2) < eps)
            # xint's f32 error scales with the edge slope |dx|/|dy| (the
            # y-side representation error is amplified through the
            # interpolation), so the crossing band must widen with it;
            # |dy| < eps edges are fully covered by the vertex strips
            slope_tol = keps * (
                jnp.float32(1.0)
                + jnp.abs(x2 - x1) / jnp.maximum(jnp.abs(dy), eps)
            )
            nearx = straddle & (jnp.abs(xf - xint) < slope_tol)
            band = band | ((near | nearx) & ~degen)
            return (crossings + cross.astype(jnp.int32), band), None

        (crossings, band), _ = jax.lax.scan(
            step,
            (jnp.zeros(xf.shape, jnp.int32), jnp.zeros(xf.shape, bool)),
            edges,
        )
        odd = (crossings & 1) == 1
        hit = env & (odd | band)
        return hit, band

    def finish(hit, band, codes=None, qcode=None):
        if attr:
            hit = acomb(hit, codes, qcode)
        return hit, hit & ~band

    if has_time and attr:
        def body(xh, xl, yh, yl, th, tl, valid, xf, yf, codes,
                 edges, box, win, qcode):
            hit, band = core(xh, xl, yh, yl, th, tl, valid, xf, yf,
                             edges, box, win)
            return finish(hit, band, codes, qcode)
    elif has_time:
        def body(xh, xl, yh, yl, th, tl, valid, xf, yf, edges, box, win):
            hit, band = core(xh, xl, yh, yl, th, tl, valid, xf, yf,
                             edges, box, win)
            return finish(hit, band)
    elif attr:
        # the dummy window rides along unused so every caller (single,
        # batch, escalation refetch) shares ONE argument layout
        def body(xh, xl, yh, yl, valid, xf, yf, codes, edges, box, win,
                 qcode):
            hit, band = core(xh, xl, yh, yl, None, None, valid, xf, yf,
                             edges, box, None)
            return finish(hit, band, codes, qcode)
    else:
        def body(xh, xl, yh, yl, valid, xf, yf, edges, box, win):
            hit, band = core(xh, xl, yh, yl, None, None, valid, xf, yf,
                             edges, box, None)
            return finish(hit, band)
    if mode != "spmd":
        return body
    from jax.sharding import PartitionSpec as P

    nrow, nrep = _poly_arg_counts(has_time, attr)
    return shard_map_fn(
        body,
        mesh,
        in_specs=tuple([P(DATA_AXIS)] * nrow + [P()] * nrep),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        check=False,
    )


def _poly_desc_split(mask, attr, args):
    """Shared arg split for the polygon batch builders: (mask_of(desc),
    stacked desc arrays for lax.scan)."""
    if attr:
        *cols, edges, boxes, wins, qcodes = args
        return (
            lambda d: mask(*cols, d[0], d[1], d[2], d[3]),
            (edges, boxes, wins, qcodes),
        )
    *cols, edges, boxes, wins = args
    return (lambda d: mask(*cols, d[0], d[1], d[2])), (edges, boxes, wins)


_POLY_RUNS_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}
_POLY_BITMAP_BATCH_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}
_POLY_PACKED_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _poly_runs_fn(has_time: bool, rcap: int, mode: str, mesh, attr=False):
    """Single polygon query -> dual fused RLE buffer (xz layout)."""
    key = (has_time, rcap, mode, mesh, attr)
    fn = _POLY_RUNS_FNS.get(key)
    if fn is None:
        mask = _poly_mask_body(has_time, mode, mesh, attr)
        mask = _gathered(mask, mesh)

        def run(*args):
            hit, decided = mask(*args)
            return _xz_dual_runs(hit, decided, rcap)

        fn = _mesh_gated(instrumented_jit("poly_runs", run), mesh)
        _POLY_RUNS_FNS[key] = fn
    return fn


def _poly_packed_fn(has_time: bool, mode: str, mesh, attr=False):
    """Dual full packed bitmaps (hit | decided) for one polygon query —
    the dense-result degrade mirror of _xz_packed_fn."""
    key = (has_time, mode, mesh, attr)
    fn = _POLY_PACKED_FNS.get(key)
    if fn is None:
        mask = _poly_mask_body(has_time, mode, mesh, attr)
        mask = _gathered(mask, mesh)

        def run(*args):
            hit, dec = mask(*args)
            return jnp.concatenate([jnp.packbits(hit), jnp.packbits(dec)])

        fn = _mesh_gated(instrumented_jit("poly_packed", run), mesh)
        _POLY_PACKED_FNS[key] = fn
    return fn


def _poly_bitmap_batch_fn(has_time: bool, span_cap: int, q: int, mode: str,
                          mesh, attr=False):
    """Polygon edition of _xz_bitmap_batch_fn: headers i32[q,4] +
    bitmaps u8[q, 2*span_cap//8] (hit | decided planes)."""
    key = (has_time, span_cap, q, mode, mesh, attr)
    fn = _POLY_BITMAP_BATCH_FNS.get(key)
    if fn is None:
        mask = _poly_mask_body(has_time, mode, mesh, attr)
        mask = _gathered(mask, mesh)

        def run(*args):
            mask_of, descs = _poly_desc_split(mask, attr, args)

            def step(carry, d):
                hit, dec = mask_of(d)
                return carry, _dual_bitmap_row(hit, dec, span_cap)

            _, (headers, bitmaps) = jax.lax.scan(step, 0, descs)
            return headers, bitmaps

        fn = _mesh_gated(instrumented_jit("poly_bitmap_batch", run), mesh)
        _POLY_BITMAP_BATCH_FNS[key] = fn
    return fn


def _xz_runs_fn(has_time: bool, rcap: int, mode: str, mesh, attr=False):
    key = (has_time, rcap, mode, mesh, attr)
    fn = _XZ_RUNS_FNS.get(key)
    if fn is None:
        mask = _xz_exact_mask_body(has_time, mode, mesh, attr)
        mask = _gathered(mask, mesh)

        def run(*args):
            hit, decided = mask(*args)
            return _xz_dual_runs(hit, decided, rcap)

        fn = _mesh_gated(instrumented_jit("xz_runs", run), mesh)
        _XZ_RUNS_FNS[key] = fn
    return fn


def _xz_packed_fn(has_time: bool, mode: str, mesh, attr=False):
    key = (has_time, mode, mesh, attr)
    fn = _XZ_PACKED_FNS.get(key)
    if fn is None:
        mask = _xz_exact_mask_body(has_time, mode, mesh, attr)
        mask = _gathered(mask, mesh)

        def run(*args):
            hit, decided = mask(*args)
            return jnp.concatenate([jnp.packbits(hit), jnp.packbits(decided)])

        fn = _mesh_gated(instrumented_jit("xz_packed", run), mesh)
        _XZ_PACKED_FNS[key] = fn
    return fn


def _exact_packed_fn(has_time: bool, mode: str, mesh, attr=False):
    key = (has_time, mode, mesh, attr)
    fn = _EXACT_PACKED_FNS.get(key)
    if fn is None:
        mask = _exact_mask_body(has_time, mode, mesh, attr)
        mask = _gathered(mask, mesh)

        def run(*args):
            return jnp.packbits(mask(*args))

        fn = _mesh_gated(instrumented_jit("exact_packed", run), mesh)
        _EXACT_PACKED_FNS[key] = fn
    return fn


_KNN_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _knn_fn(k: int, mode: str, mesh):
    """(xf, yf, valid, qx, qy) -> top-k row indices by f32 haversine.

    pallas_spmd meshes rank per shard (k indices per chip, stacked) — the
    per-tablet partial-result + client-merge shape of the reference's
    distributed kNN, with lax.top_k as the per-chip ranker."""
    # mesh is ALWAYS in the key: the non-spmd edition's dispatch gate
    # (and the spmd edition's shard specs) are both per-mesh state
    key = (k, mode, mesh)
    fn = _KNN_FNS.get(key)
    if fn is None:

        def dists(xf, yf, valid, qx, qy):
            rx = jnp.radians(xf)
            ry = jnp.radians(yf)
            qxr = jnp.radians(qx)
            qyr = jnp.radians(qy)
            sdy = jnp.sin((ry - qyr) * 0.5)
            sdx = jnp.sin((rx - qxr) * 0.5)
            a = sdy * sdy + jnp.cos(ry) * jnp.cos(qyr) * sdx * sdx
            d = jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))
            return jnp.where(valid, d, jnp.inf)

        def local_topk(xf, yf, valid, qx, qy):
            d = dists(xf, yf, valid, qx, qy)
            kk = min(k, d.shape[0])
            _, idx = jax.lax.top_k(-d, kk)
            return idx

        if mode == "pallas_spmd":
            from jax.sharding import PartitionSpec as P

            def per_shard(xf, yf, valid, qx, qy):
                d = dists(xf, yf, valid, qx, qy)
                kk = min(k, d.shape[0])
                _, idx = jax.lax.top_k(-d, kk)
                # shard-local -> segment-global row index
                return idx + jax.lax.axis_index(DATA_AXIS) * d.shape[0]

            body = shard_map_fn(
                per_shard,
                mesh,
                in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P()),
                out_specs=P(DATA_AXIS),
                check=False,
            )
            # per-shard top-k is collective-free (axis_index + local
            # top_k, P(DATA_AXIS) out concatenates without comms)
            fn = instrumented_jit("knn", body)
        else:
            # a replicated top_k over row-sharded columns lowers with
            # cross-shard collectives on a multi-device mesh: gate it
            fn = _mesh_gated(instrumented_jit("knn", local_topk), mesh)
        _KNN_FNS[key] = fn
    return fn


def _packed_fn(kind: str, mode: str, mesh):
    key = _fn_key(kind, mode, mesh)
    fn = _PACKED_FNS.get(key)
    if fn is None:
        mask = _raw_mask_fn(kind, mode, mesh)
        mask = _gathered(mask, mesh)

        def run(*args):
            return jnp.packbits(mask(*args))

        fn = _mesh_gated(instrumented_jit(f"packed.{kind}", run), mesh)
        _PACKED_FNS[key] = fn
    return fn


def _pad_rows(n: int, m: int) -> int:
    """Pad row count to a pow2 multiple of m so segment shapes bucket."""
    units = max(1, -(-n // m))
    p = 1
    while p < units:
        p *= 2
    return p * m


class DeviceSegment:
    """Device-resident mirror of a contiguous run of blocks of one index.

    The unit of incremental upload: a write batch seals new block(s), which
    become one new segment; existing segments' coordinate columns are never
    re-transferred. Rows are padded to a pow2 multiple of the shard/tile
    granule so jit shape buckets stay bounded.
    """

    def __init__(self, mesh, table: IndexTable, blocks: Sequence[FeatureBlock]):
        self.mesh = mesh
        self.kind = table.index.name  # "z3" | "z2" | "xz2" | "xz3"
        self.blocks = list(blocks)
        self.block_ids = [id(b) for b in blocks]
        ft = table.ft
        xs: List[np.ndarray] = []
        ys: List[np.ndarray] = []
        ts: List[np.ndarray] = []
        bins: List[np.ndarray] = []
        envs: List[np.ndarray] = []
        self.block_starts: List[int] = []
        n = 0
        geom = ft.default_geometry.name
        for b in blocks:
            self.block_starts.append(n)
            key = b.key.astype(np.int64) if b.key.dtype != object else None
            if self.kind == "z3":
                xi, yi, ti = zorder.z3_decode(key)
                ts.append(ti.astype(np.int32))
                bins.append(b.bins.astype(np.int32))
                xs.append(xi.astype(np.int32))
                ys.append(yi.astype(np.int32))
            elif self.kind == "z2":
                xi, yi = zorder.z2_decode(key)
                xs.append(xi.astype(np.int32))
                ys.append(yi.astype(np.int32))
            else:  # xz2 / xz3: per-feature bounding boxes, ulp-widened so the
                # f32 cast can never shrink a bbox out of a true overlap
                bx = b.columns.get(geom + "__bxmin")
                if bx is not None:
                    # envelope companion columns stored at ingest
                    e = np.stack(
                        [
                            bx,
                            b.columns[geom + "__bymin"],
                            b.columns[geom + "__bxmax"],
                            b.columns[geom + "__bymax"],
                        ],
                        axis=1,
                    ).astype(np.float64)
                else:  # legacy blocks: walk the object column
                    e = np.zeros((b.n, 4), dtype=np.float64)
                    for i, g in enumerate(b.full_col(geom)):
                        if g is not None:
                            e[i] = g.envelope.as_tuple()
                e32 = np.empty((b.n, 4), dtype=np.float32)
                e32[:, 0] = np.nextafter(e[:, 0].astype(np.float32), np.float32(-np.inf))
                e32[:, 1] = np.nextafter(e[:, 1].astype(np.float32), np.float32(-np.inf))
                e32[:, 2] = np.nextafter(e[:, 2].astype(np.float32), np.float32(np.inf))
                e32[:, 3] = np.nextafter(e[:, 3].astype(np.float32), np.float32(np.inf))
                envs.append(e32)
                if self.kind == "xz3":
                    bins.append(b.bins.astype(np.int32))
                    _, offs = time_to_binned(
                        b.full_col(ft.default_date.name), ft.xz3_interval
                    )
                    ts.append(offs.astype(np.int32))
            n += b.n
        self.n = n
        # Pallas modes need a whole number of row tiles PER SHARD; the XLA
        # mode only needs byte-aligned shards (packbits fallback). Don't pay
        # the devices*TILE granule when the kernels will never run — if the
        # mode later flips to pallas on an xla-granule segment, hit_rows
        # degrades that segment to the XLA mask instead of crashing.
        from geomesa_tpu.ops.pallas_kernels import TILE

        size = max(1, mesh.devices.size)
        if _mask_mode(mesh) == "xla":
            m = int(np.lcm(size * 8, TILE))
        else:
            m = size * TILE
        self.n_padded = _pad_rows(max(n, 1), m)
        record_pad(n, self.n_padded, kind=self.kind)
        self._pallas_ok = (self.n_padded // size) % TILE == 0
        self._m = self.n_padded  # pack() pads straight to the bucketed size
        self.fids = np.concatenate(
            [b.full_col("__fid__") for b in blocks]
        ) if blocks else np.empty(0, dtype=object)
        self._valid_host = np.ones(n, dtype=bool)
        self.valid = self._pack([self._valid_host], bool, False)
        # adaptive run capacity: grows on overflow, remembered per segment
        self._rcap = HIT_CAPACITY0
        # bitmap-batch span window (rows): starts at the full segment and
        # narrows to the widest observed query span
        self._span_cap = 0  # 0 = unlearned -> full segment
        # per-SHARD span window for the shard-extract bitmap edition
        # (each chip frames its local hits; window <= shard_n)
        self._shard_span_cap = 0
        # raw f32 coords + ms offsets are only needed by fused aggregations;
        # packed lazily on first density_scan (load_raw)
        self.xf = self.yf = self.t_ms = None
        self._raw_loaded = False
        if self.kind in ("z2", "z3"):
            self.xi = self._pack(xs, np.int32, 0)
            self.yi = self._pack(ys, np.int32, 0)
        else:
            env = np.concatenate(envs) if envs else np.empty((0, 4), np.float32)
            # inverted pad boxes (min > max) never overlap a query box
            self.bxmin = self._pack([env[:, 0]], np.float32, 1.0)
            self.bymin = self._pack([env[:, 1]], np.float32, 1.0)
            self.bxmax = self._pack([env[:, 2]], np.float32, 0.0)
            self.bymax = self._pack([env[:, 3]], np.float32, 0.0)
        if self.kind in ("z3", "xz3"):
            self.ti = self._pack(ts, np.int32, 0)
            self.bins = self._pack(bins, np.int32, -1)

    def _pack(self, parts, dtype, fill):
        arr = np.concatenate(parts) if parts else np.empty(0, dtype)
        return shard_array(self.mesh, pad_to_multiple(arr, self._m, fill))

    def apply_tombstones(self, tombstones: set) -> None:
        """Clear deleted rows in the device valid mask (no re-pack).

        The reference applies deletes as per-row mutations; here a delete
        flips valid bits so the very next device scan excludes the rows —
        the executor stays active after delete_features (no host fallback).
        """
        if not self.n:
            return
        keep = np.array([f not in tombstones for f in self.fids], dtype=bool)
        if not np.array_equal(keep, self._valid_host):
            self._valid_host = keep
            self.valid = self._pack([keep], bool, False)
            if getattr(self, "_exact_loaded", False) and self.tvalid is not None:
                nulls = getattr(self, "_t_nulls_host", None)
                self.tvalid = (
                    self.valid
                    if nulls is None
                    else self._pack([keep & ~nulls], bool, False)
                )
            if (
                getattr(self, "_exact_xz_loaded", False)
                and getattr(self, "_xz_t_nulls_host", None) is not None
            ):
                # xz3 temporal-valid mask bakes in the tombstones too —
                # devseek hits ARE the result set, nothing downstream
                # strips deleted rows
                self.xz_tvalid = self._pack(
                    [keep & ~self._xz_t_nulls_host], bool, False
                )

    def load_raw(self, table: IndexTable) -> bool:
        """Pack raw f32 coords (+ in-bin ms offsets for day/week z3) for the
        fused aggregation path. Returns False when unsupported (month/year
        bins are non-uniform / overflow int32 ms offsets)."""
        if self._raw_loaded:
            return self.kind == "z2" or self.t_ms is not None
        self._raw_loaded = True
        ft = table.ft
        geom = ft.default_geometry.name
        xfs = [b.full_col(geom + "__x").astype(np.float32) for b in self.blocks]
        yfs = [b.full_col(geom + "__y").astype(np.float32) for b in self.blocks]
        self.xf = self._pack(xfs, np.float32, 0.0)
        self.yf = self._pack(yfs, np.float32, 0.0)
        if self.kind == "z3":
            if ft.z3_interval not in (TimePeriod.DAY, TimePeriod.WEEK):
                return False
            traw = []
            for b in self.blocks:
                t_ms = b.full_col(ft.default_date.name).astype(np.int64)
                starts = binned_to_time(
                    b.bins.astype(np.int64), np.zeros(b.n, np.int64), ft.z3_interval
                )
                traw.append((t_ms - starts).astype(np.int32))
            self.t_ms = self._pack(traw, np.int32, -1)
        return True

    def agg_mask(self, table: IndexTable):
        """Packed (valid & finite-geometry) row mask for the aggregate
        pyramid build reduction (ops/pyramid.py): null geometries encode
        leniently (clipped keys land in cell 0) and must never count in
        a cell, exactly as the host build excludes them. Cached per
        tombstone state (``self.valid`` is re-packed whenever tombstones
        move, so identity of that array keys the cache)."""
        got = getattr(self, "_agg_mask", None)
        if got is not None and got[0] is self.valid:
            return got[1]
        geom = table.ft.default_geometry.name
        finite = (
            np.concatenate(
                [
                    np.isfinite(
                        np.asarray(b.full_col(geom + "__x"), dtype=np.float64)
                    )
                    & np.isfinite(
                        np.asarray(b.full_col(geom + "__y"), dtype=np.float64)
                    )
                    for b in self.blocks
                ]
            )
            if self.blocks
            else np.empty(0, dtype=bool)
        )
        mask = self._pack([self._valid_host & finite], bool, False)
        self._agg_mask = (self.valid, mask)
        return mask

    def _mask_args(self, boxes_dev, windows_dev) -> tuple:
        if self.kind == "z3":
            return (self.xi, self.yi, self.bins, self.ti, self.valid, boxes_dev, windows_dev)
        if self.kind == "z2":
            return (self.xi, self.yi, self.valid, boxes_dev)
        if self.kind == "xz3":
            return (
                self.bxmin, self.bymin, self.bxmax, self.bymax,
                self.bins, self.ti, self.valid, boxes_dev, windows_dev,
            )
        return (self.bxmin, self.bymin, self.bxmax, self.bymax, self.valid, boxes_dev)

    def _mode(self) -> str:
        mode = _mask_mode(self.mesh)
        if mode != "xla" and not self._pallas_ok:
            mode = "xla"  # segment was padded for the XLA granule only
        return mode

    def remember_rcap(self, nruns: int) -> None:
        """Adapt the dispatch capacity to observed run counts: grow to 2x
        the need (pow2), decay gently when queries shrink, and never exceed
        the packed-bitmap break-even — one fragmented query must not lock
        later queries into bitmap-sized transfers forever."""
        cap_hi = HIT_CAPACITY0
        limit = max(HIT_CAPACITY0, self.n_padded // (2 * DENSE_BITMAP_FACTOR))
        while cap_hi < limit:
            cap_hi *= 2
        want = HIT_CAPACITY0
        while want < 2 * nruns and want < cap_hi:
            want *= 2
        if want > self._rcap:
            self._rcap = want
        elif want < self._rcap:
            self._rcap = max(want, self._rcap // 2)

    def span_cap(self) -> int:
        """Current bitmap span window: learned pow2 bucket, clamped to the
        segment (and byte-aligned by construction: pow2 >= 65536)."""
        if self._span_cap == 0:
            return self.n_padded
        return min(self._span_cap, self.n_padded)

    def remember_span(self, span: int) -> None:
        """Adapt the bitmap span window to the widest query span of a
        stream (called once per batch): grow immediately, decay gently."""
        want = min(_pow2_at_least(max(int(span * 1.25), 1), 1 << 16),
                   self.n_padded)
        cur = self._span_cap or self.n_padded
        if want > cur:
            self._span_cap = want
        elif want < cur:
            self._span_cap = max(want, cur // 2)

    def shard_n(self) -> int:
        return self.n_padded // max(1, self.mesh.devices.size)

    def shard_span_cap(self) -> int:
        """Per-shard bitmap window (pow2 bucket, multiple of 8 because
        n_padded divides by 8*n_devices by construction)."""
        if self._shard_span_cap == 0:
            return self.shard_n()
        return min(self._shard_span_cap, self.shard_n())

    def remember_shard_span(self, span: int) -> None:
        """Adapt the per-shard window to the widest LOCAL span observed
        across a stream's (shard, query) windows."""
        want = min(
            _pow2_at_least(max(int(span * 1.25), 1), 1 << 13), self.shard_n()
        )
        cur = self._shard_span_cap or self.shard_n()
        if want > cur:
            self._shard_span_cap = want
        elif want < cur:
            self._shard_span_cap = max(want, cur // 2)
        else:
            self._shard_span_cap = want  # observed == window: pin it

    def seed_span(self, span: int) -> None:
        """Seed the bitmap span windows from the PLAN before the first
        device stream (only when unlearned): the host's decomposed
        z-ranges conservatively cover every hit row, so the widest
        planned candidate span bounds the true hit span — killing the
        full-window first stream (n_padded/8 bytes per query per plane)
        that an unlearned segment otherwise pays. The same global bound
        also caps every shard's LOCAL span, so the shard-extract window
        seeds too. Learned values are never overridden; observation
        stays the source of truth."""
        if self._span_cap == 0:
            self._span_cap = min(
                _pow2_at_least(max(int(span), 1), 1 << 16), self.n_padded
            )
        if self._shard_span_cap == 0:
            self._shard_span_cap = min(
                _pow2_at_least(max(int(span), 1), 1 << 13), self.shard_n()
            )

    def dispatch_hits(self, boxes_dev, windows_dev) -> "_PendingHits":
        """Start the device scan WITHOUT blocking: the fused RLE buffer
        begins computing and copying host-ward immediately. Call .rows()
        on the returned handle to block and decode."""
        mode = self._mode()
        args = self._mask_args(boxes_dev, windows_dev)
        rcap = self._rcap
        buf = _runs_fn(self.kind, rcap, mode, self.mesh)(*args)
        _start_d2h(buf)
        return _PendingHits(
            self,
            rcap,
            buf,
            refetch=lambda rc: _runs_fn(self.kind, rc, mode, self.mesh)(*args),
            packed=lambda: _packed_fn(self.kind, mode, self.mesh)(*args),
        )

    def load_exact(self, table: IndexTable) -> bool:
        """Pack f64/i64 SORT-KEY limb columns for the EXACT device
        predicate path (zkernels.f64_sort_keys — u32 limb compares give
        exact f64 semantics without jax x64); False when unsupported."""
        if self.kind not in ("z2", "z3"):
            return False
        if getattr(self, "_exact_loaded", False):
            return True
        from geomesa_tpu.ops.zkernels import (
            f64_sort_keys,
            i64_sort_keys,
            split_u64_to_limbs,
        )

        ft = table.ft
        geom = ft.default_geometry.name

        def pack_keys(keys: np.ndarray):
            hi, lo = split_u64_to_limbs(keys)
            # pad with max-key: never inside a finite range (valid also
            # masks pads, this is belt+braces)
            return (
                self._pack([hi], np.uint32, np.uint32(0xFFFFFFFF)),
                self._pack([lo], np.uint32, np.uint32(0xFFFFFFFF)),
            )

        xs = np.concatenate([b.full_col(geom + "__x") for b in self.blocks])
        ys = np.concatenate([b.full_col(geom + "__y") for b in self.blocks])
        self.xk_hi, self.xk_lo = pack_keys(f64_sort_keys(xs))
        self.yk_hi, self.yk_lo = pack_keys(f64_sort_keys(ys))
        if self.kind == "z3":
            dtg = ft.default_date.name
            ts = np.concatenate(
                [b.full_col(dtg).astype(np.int64) for b in self.blocks]
            )
            self.tk_hi, self.tk_lo = pack_keys(i64_sort_keys(ts))
            # null dates are stored as 0 + a __null mask: the host evaluator
            # rejects them for any temporal predicate, so the exact TEMPORAL
            # mask needs its own valid column (bbox-only queries keep them)
            nulls = np.concatenate(
                [b.full_col(dtg + "__null") for b in self.blocks]
            )
            self._t_nulls_host = nulls if nulls.any() else None
            if self._t_nulls_host is not None:
                self.tvalid = self._pack([self._valid_host & ~nulls], bool, False)
            else:
                self.tvalid = self.valid
        else:
            self.tk_hi = self.tk_lo = None
            self.tvalid = None
        self._exact_loaded = True
        return True

    def load_exact_xz(self, table: IndexTable) -> bool:
        """Pack f64 sort-key limbs of the envelope companions (+ isrect
        flags; + dtg i64 limbs and a temporal-valid mask for xz3) for the
        extent device-assisted seek; False when this is not an extent
        segment or blocks lack companions."""
        if self.kind not in ("xz2", "xz3"):
            return False
        if getattr(self, "_exact_xz_loaded", False):
            return True
        from geomesa_tpu.ops.zkernels import (
            f64_sort_keys,
            i64_sort_keys,
            split_u64_to_limbs,
        )

        ft = table.ft
        geom = ft.default_geometry.name
        cols = []
        for suffix in ("__bxmin", "__bymin", "__bxmax", "__bymax"):
            parts = []
            for b in self.blocks:
                col = b.columns.get(geom + suffix)
                if col is None:
                    return False  # legacy blocks without companions
                parts.append(np.asarray(col, dtype=np.float64))
            hi, lo = split_u64_to_limbs(f64_sort_keys(np.concatenate(parts)))
            cols.append(self._pack([hi], np.uint32, np.uint32(0)))
            cols.append(self._pack([lo], np.uint32, np.uint32(0)))
        self.xz_limbs = tuple(cols)
        irs = np.concatenate(
            [
                np.asarray(
                    b.columns.get(geom + "__isrect", np.zeros(b.n, np.uint8))
                ).astype(bool)
                for b in self.blocks
            ]
        ) if self.blocks else np.empty(0, dtype=bool)
        self.xz_isrect = self._pack([irs], bool, False)
        if self.kind == "xz3" and ft.default_date is not None:
            dtg = ft.default_date.name
            ts = np.concatenate(
                [np.asarray(b.columns[dtg], dtype=np.int64) for b in self.blocks]
            )
            thi, tlo = split_u64_to_limbs(i64_sort_keys(ts))
            self.xz_tk = (
                self._pack([thi], np.uint32, np.uint32(0)),
                self._pack([tlo], np.uint32, np.uint32(0)),
            )
            nulls = np.concatenate(
                [b.full_col(dtg + "__null") for b in self.blocks]
            )
            # keep the host mask so apply_tombstones can rebuild xz_tvalid
            self._xz_t_nulls_host = nulls if nulls.any() else None
            self.xz_tvalid = (
                self._pack([self._valid_host & ~nulls], bool, False)
                if self._xz_t_nulls_host is not None
                else None  # falls back to the segment valid mask
            )
        else:
            self.xz_tk = None
            self.xz_tvalid = None
            self._xz_t_nulls_host = None
        self._exact_xz_loaded = True
        return True

    def _exact_args(
        self, box_dev, win_dev, has_time: bool,
        codes_dev=None, qcode_dev=None,
    ) -> tuple:
        """The one place that knows the exact-scan argument layout (shared
        by single dispatch, batch dispatch, and escalation refetches).
        ``codes_dev``/``qcode_dev`` add the attribute-equality plane."""
        if has_time:
            base = (
                self.xk_hi, self.xk_lo, self.yk_hi, self.yk_lo,
                self.tk_hi, self.tk_lo, self.tvalid,
            )
        else:
            base = (self.xk_hi, self.xk_lo, self.yk_hi, self.yk_lo, self.valid)
        if codes_dev is not None:
            base = base + (codes_dev,)
        base = base + (box_dev,)
        if has_time:
            base = base + (win_dev,)
        if qcode_dev is not None:
            base = base + (qcode_dev,)
        return base

    def load_attr_codes(self, attr: str) -> bool:
        """Unified rank-code column for one attribute: every block's
        values re-encode into ONE segment-wide SORTED value space, so
        the device decides ``attr = literal`` (one i32 compare per row)
        and ``attr`` range predicates (one interval test — code order ==
        value order) — the device half of the reference's join attribute
        strategy (AttributeIndex.scala:42,392: evaluate the attribute
        predicate at the data instead of post-filtering on the client).

        Two per-block sources feed the same unified space:
        - dictionary-coded string blocks: sorted vocab, remapped with
          one searchsorted pass per block;
        - raw typed columns (int/long/float/double/date-ms, plus the
          high-cardinality fixed-width-unicode string fallback):
          np.unique over the block values — the ranks ARE the codes.
        Null rows (and float NaN, which the oracle's valid mask also
        excludes) carry -1; pad rows carry -1."""
        cache = getattr(self, "_attr_codes", None)
        if cache is None:
            cache = self._attr_codes = {}
        if attr in cache:
            return cache[attr] is not None
        def raw_vocab(b):
            # vocabs are NOT row-aligned: bypass full_col's record gather
            v = b.columns.get(attr + "__vocab")
            if v is None and b.record is not None:
                v = b.record.columns.get(attr + "__vocab")
            return v

        per = []  # (codes, vocab) | (values, nulls_or_None)
        vocab_pool = []  # value arrays feeding the unified space
        try:
            for b in self.blocks:
                col = b.full_col(attr)
                vocab = raw_vocab(b)
                if vocab is not None and col.dtype.kind in "iu":
                    per.append(("dict", col, vocab))
                    vocab_pool.append(vocab)
                elif col.dtype.kind in "iufU":
                    # (datetime64 'M' deliberately excluded: DATE columns
                    # are int64 epoch-ms — an 'M' column could not compare
                    # against the planner's ms literals and would decide
                    # "no rows" instead of falling back to the host)
                    nulls = b.full_col(attr + "__null")
                    if col.dtype.kind == "f":
                        nulls = nulls | np.isnan(col)
                    live = col[~nulls] if nulls.any() else col
                    per.append(("raw", col, nulls))
                    vocab_pool.append(np.unique(live))
                else:
                    raise KeyError(attr)  # object column: host-only
        except KeyError:
            cache[attr] = None  # no device-codable layout in some block
            return False
        unified = (
            np.unique(np.concatenate(vocab_pool))
            if vocab_pool else np.empty(0, dtype=object)
        )
        parts = []
        for kind, col, aux in per:
            if kind == "dict":
                remap = np.searchsorted(unified, aux).astype(np.int32)
                parts.append(
                    np.where(
                        col >= 0, remap[np.maximum(col, 0)], np.int32(-1)
                    ).astype(np.int32)
                )
            else:
                # null/NaN rows get arbitrary ranks here (NaN sorts past
                # the end) and are overwritten with -1 below
                codes = np.searchsorted(unified, col).astype(np.int32)
                codes[aux] = -1
                parts.append(codes)
        dev = self._pack(parts, np.int32, -1)
        cache[attr] = (dev, unified)
        return True

    def attr_qcode(self, attr: str, value) -> int:
        """Segment-local code of ``value`` (-2 when absent OR not
        comparable with the column's value space: matches no row,
        including nulls at -1)."""
        _dev, unified = self._attr_codes[attr]
        try:
            i = int(np.searchsorted(unified, value))
        except (TypeError, ValueError):
            return -2
        if i < len(unified) and unified[i] == value:
            return i
        return -2

    def attr_qrange(self, attr: str, preds) -> np.ndarray:
        """i32[2] inclusive code interval = the INTERSECTION of ``preds``
        mapped into this segment's sorted unified value space. Each pred
        is (op, literal): op in =, <, <=, >, >=, between (inclusive
        pair), the exclusive temporal forms during/before/after
        (FilterHelper.scala:366,427,440 bound rules), prefix (LIKE with
        one trailing %), and isnull/notnull (IS [NOT] NULL — isnull is
        the interval [-1, -1]: nulls AND float NaN both rank -1, exactly
        the oracle's ~valid). searchsorted left/right gives EXACTLY the
        oracle's code-space semantics (filter/evaluate.py:_eval_cmp);
        incomparable literals produce an empty interval, matching the
        oracle's per-row TypeError -> False. Every value op clamps its
        own lower bound to >= 0, so nulls never match ordinary ranges;
        empty = lo > hi."""
        _dev, unified = self._attr_codes[attr]
        u = len(unified)
        lo, hi = -1, u - 1  # -1 reachable ONLY via isnull
        for op, lit in preds:
            try:
                if op in ("between", "during"):
                    a_side, b_side = (
                        ("left", "right") if op == "between"
                        else ("right", "left")  # during: exclusive ends
                    )
                    a = np.searchsorted(unified, lit[0], side=a_side)
                    b = np.searchsorted(unified, lit[1], side=b_side) - 1
                elif op == "=":
                    a = np.searchsorted(unified, lit, side="left")
                    b = np.searchsorted(unified, lit, side="right") - 1
                elif op == ">=":
                    a, b = np.searchsorted(unified, lit, side="left"), u - 1
                elif op in (">", "after"):
                    a, b = np.searchsorted(unified, lit, side="right"), u - 1
                elif op in ("<", "before"):
                    a, b = 0, np.searchsorted(unified, lit, side="left") - 1
                elif op == "<=":
                    a, b = 0, np.searchsorted(unified, lit, side="right") - 1
                elif op == "prefix":
                    a = np.searchsorted(unified, lit, side="left")
                    succ = _str_successor(lit)
                    b = (
                        np.searchsorted(unified, succ, side="left") - 1
                        if succ is not None else u - 1
                    )
                elif op == "isnull":
                    a, b = -1, -1
                elif op == "notnull":
                    a, b = 0, u - 1
                else:  # unknown op: claim nothing (planner should gate)
                    a, b = 0, -1
            except (TypeError, ValueError):
                a, b = 0, -1
            lo, hi = max(lo, int(a)), min(hi, int(b))
        return np.array([lo, hi], dtype=np.int32)

    def attr_qcodes(self, attr: str, values, k: int) -> np.ndarray:
        """i32[k] code vector for an IN-list (equality = length 1),
        padded with the match-nothing sentinel."""
        out = np.full(k, -2, dtype=np.int32)
        for j, v in enumerate(values[:k]):
            out[j] = self.attr_qcode(attr, v)
        return out

    # the vocab-mask plane declines above this many distinct values: the
    # u8 lookup vector rides the replicated arg path per query, and the
    # host regex pass over the vocab stops being "one cheap pass"
    ATTR_VOCAB_MASK_CAP = 1 << 16

    def attr_vocab_ok(self, attr: str, cap: Optional[int] = None) -> bool:
        """Can the vocab-mask edition run here? (codes loaded AND the
        unified space small enough for a per-query lookup vector)."""
        info = getattr(self, "_attr_codes", {}).get(attr)
        return info is not None and len(info[1]) <= (
            cap if cap is not None else self.ATTR_VOCAB_MASK_CAP
        )

    def attr_qmask(self, attr: str, payload) -> np.ndarray:
        """u8[U_pad] membership mask over this segment's sorted unified
        value space for a LIKE/ILIKE pattern — built with the ORACLE's
        exact matcher (filter/evaluate.py:_eval_like's regex), so device
        results equal host results by construction, wildcards and case
        folding included. ``payload`` = (pattern, case_insensitive)."""
        from geomesa_tpu.filter.evaluate import like_regex

        pattern, ci = payload
        _dev, unified = self._attr_codes[attr]
        u = len(unified)
        rx = like_regex(pattern, ci)
        out = np.zeros(_pow2_at_least(max(u, 1), 8), dtype=np.uint8)
        for i in range(u):
            v = unified[i]
            if isinstance(v, (str, np.str_)) and rx.match(str(v)):
                out[i] = 1
        return out

    def dispatch_exact_attr(
        self, box_dev, win_dev, attr: str, payload, kind: str = "member"
    ) -> "_PendingHits":
        """Single-query edition of the attr plane (a lone query must not
        lose device exactness to the conservative fallback). ``payload``
        is the literal tuple for ``kind="member"`` (equality = len 1) or
        the (op, literal) predicate tuple for ``kind="range"``."""
        has_time = self.tk_hi is not None and win_dev is not None
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        aflag, codes_dev, qc = self._attr_plane_args(attr, payload, kind)
        args = self._exact_args(box_dev, win_dev, has_time, codes_dev, qc)
        rcap = self._rcap
        buf = _exact_runs_fn(has_time, rcap, mode, self.mesh, aflag)(*args)
        _start_d2h(buf)
        return _PendingHits(
            self,
            rcap,
            buf,
            refetch=lambda rc: _exact_runs_fn(
                has_time, rc, mode, self.mesh, aflag
            )(*args),
            packed=lambda: _exact_packed_fn(
                has_time, mode, self.mesh, aflag
            )(*args),
        )

    def _attr_batch_vectors(self, attr, attr_kind, payloads, qpad):
        """(is_attr, codes_dev, qcodes_dev) for one batch's attr-plane
        payload list — the BATCH edition of _attr_plane_args (one home
        for the K-bucket vs [lo, hi] split across the point, extent, and
        polygon dispatchers, so they can never diverge). Pad entries
        repeat the last payload's vector."""
        # is_attr IS the plane edition and the kernel cache-key value:
        # "member" | "notmember" (both qcode vectors) | "range" ([lo, hi])
        is_attr = False if attr is None else attr_kind
        if not is_attr:
            return False, None, None
        codes_dev = self._attr_codes[attr][0]
        if is_attr == "range":
            def qvec(payload):
                return self.attr_qrange(attr, payload)
        elif is_attr == "vocabmask":
            def qvec(payload):
                return self.attr_qmask(attr, payload)
        else:
            kk = _pow2_at_least(max(len(p) for p in payloads), 1)

            def qvec(payload):
                return self.attr_qcodes(attr, payload, kk)
        q = len(payloads)
        qcodes_np = np.stack(
            [qvec(p) for p in payloads] + [qvec(payloads[-1])] * (qpad - q)
        )
        return is_attr, codes_dev, replicate(self.mesh, qcodes_np)

    def _attr_plane_args(self, attr, payload, kind):
        """(aflag, codes_dev, qc_dev) for one attr-plane query — THE
        shared member/range split (K-bucket vs [lo, hi] interval) used
        by extraction dispatches AND the count path, so the two can
        never diverge. attr None -> the plain exact plane."""
        if attr is None:
            return False, None, None
        codes_dev = self._attr_codes[attr][0]
        if kind == "range":
            return "range", codes_dev, replicate(
                self.mesh, self.attr_qrange(attr, payload)
            )
        if kind == "vocabmask":
            return "vocabmask", codes_dev, replicate(
                self.mesh, self.attr_qmask(attr, payload)
            )
        return kind, codes_dev, replicate(
            self.mesh,
            self.attr_qcodes(attr, payload, _pow2_at_least(len(payload), 1)),
        )

    def count_exact_start(
        self, box_dev, win_dev, attr=None, payload=None, kind="member"
    ):
        """DISPATCH a filtered count (no row extraction): the
        exact(+attr) mask sums on device; returns the in-flight scalar —
        int() it to collect. One i32 crosses the link per segment,
        independent of hit count; callers replicate box/window ONCE and
        dispatch every segment before collecting, so S segments pay one
        upload + one link round-trip of latency, not S (the device
        edition of an EXACT_COUNT scan; count_scan wires it to
        store.count). Per-segment attr vectors stay per segment — codes
        are segment-local."""
        has_time = self.tk_hi is not None and win_dev is not None
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        aflag, codes_dev, qc = self._attr_plane_args(attr, payload, kind)
        args = self._exact_args(box_dev, win_dev, has_time, codes_dev, qc)
        out = _exact_count_fn(has_time, mode, self.mesh, aflag)(*args)
        _start_d2h(out)
        return out

    def stat_hist_start(self, box_dev, win_dev, attr: str):
        """DISPATCH a filtered per-code count histogram for ``attr``
        (load_attr_codes must have succeeded): returns (in-flight
        i32[1 + u_pad] buffer, sorted unified value space). Collect with
        np.asarray; [0] is the total hit count (nulls included), [1:] the
        per-code hit counts aligned to the vocab. Callers replicate
        box/window once and dispatch every segment before collecting."""
        has_time = self.tk_hi is not None and win_dev is not None
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        tcodes, unified = self._attr_codes[attr]
        u_pad = _pow2_at_least(len(unified), 8)
        args = self._exact_args(box_dev, win_dev, has_time)
        out = _exact_stat_hist_fn(has_time, mode, self.mesh, u_pad)(tcodes, *args)
        _start_d2h(out)
        return out, unified

    def dispatch_exact(self, box_dev, win_dev) -> "_PendingHits":
        """Exact predicate scan (see TpuScanExecutor._exact_descriptor)."""
        has_time = self.tk_hi is not None and win_dev is not None
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        args = self._exact_args(box_dev, win_dev, has_time)
        rcap = self._rcap
        buf = _exact_runs_fn(has_time, rcap, mode, self.mesh)(*args)
        _start_d2h(buf)
        return _PendingHits(
            self,
            rcap,
            buf,
            refetch=lambda rc: _exact_runs_fn(has_time, rc, mode, self.mesh)(*args),
            packed=lambda: _exact_packed_fn(has_time, mode, self.mesh)(*args),
        )

    def dispatch_exact_batch(
        self, descs: Sequence[tuple], has_time: bool,
        attr: Optional[str] = None, attr_kind: str = "member",
    ) -> List["_PendingHits"]:
        """Q exact scans in ONE device execution (see
        _exact_bitmap_batch_fn and _exact_shard_bitmap_batch_fn).

        ``descs`` = [(box_np u32[8], win_np u32[4]|None)] — or, with
        ``attr`` set, [(box, win, payload)]: the device then also
        decides the attribute predicate per row via unified rank codes
        (load_attr_codes), the join attribute strategy evaluated at the
        data. ``attr_kind`` picks the plane edition: "member" payloads
        are literal tuples (equality/IN), "range" payloads are (op,
        literal) predicate tuples intersected into one [lo, hi] code
        interval per segment. All entries of a batch share ``has_time``
        (and ``attr_kind`` — the two editions jit separately). Returns one
        pending handle per desc, all resolving from a single shared
        buffer fetch. The query list is padded (repeating the last
        descriptor) so jit shape buckets stay bounded. Overflow
        refetches escalate per query through the single-query path.
        The wire format follows the mesh: span-framed bitmaps, framed
        per shard on a multi-device mesh (no collective at all) and over
        the whole segment on a single device.
        """
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        q = len(descs)
        # bitmap rows are span_cap/8 bytes each — pad the query axis to a
        # multiple of 4 (bounded waste)
        qpad = (q + 3) // 4 * 4
        boxes_np = np.stack(
            [d[0] for d in descs] + [descs[-1][0]] * (qpad - q)
        )
        boxes_dev = replicate(self.mesh, boxes_np)
        if has_time:
            wins_np = np.stack(
                [d[1] for d in descs] + [descs[-1][1]] * (qpad - q)
            )
            wins_dev = replicate(self.mesh, wins_np)
        else:
            wins_dev = None
        # attr plane: descs carry LITERALS (codes are segment-local); map
        # each to this segment's unified code space here — member: K-padded
        # qcode vectors (equality = K 1); range: [lo, hi] code intervals
        is_attr, codes_dev, qcodes_dev = self._attr_batch_vectors(
            attr, attr_kind,
            [d[2] for d in descs] if attr is not None else None, qpad,
        )
        args = self._exact_args(
            boxes_dev, wins_dev, has_time, codes_dev, qcodes_dev
        )

        def single_args_for(box_np, win_np, values):
            def build():
                _aflag, _codes, qc = self._attr_plane_args(
                    attr if is_attr else None,
                    values,
                    is_attr,
                )
                return self._exact_args(
                    replicate(self.mesh, box_np),
                    None if win_np is None else replicate(self.mesh, win_np),
                    has_time,
                    codes_dev,
                    qc,
                )
            return build

        if self.mesh.devices.size > 1:
            # per-shard extraction: each chip frames its LOCAL window,
            # the host stitches with shard row offsets — no collectives
            span_cap = self.shard_span_cap()
            hdr, bits = _exact_shard_bitmap_batch_fn(
                has_time, span_cap, qpad, self.mesh, is_attr
            )(*args)
            _start_d2h(hdr, bits)
            batch = _ShardBitmapBatch(
                hdr, bits, span_cap, self.mesh.devices.size, qpad,
                self.shard_n(), seg=self, proto="bitmap_shard",
            )
            pending = _PendingShardBitmapHits
        else:
            span_cap = self.span_cap()
            hdr, bits = _exact_bitmap_batch_fn(
                has_time, span_cap, qpad, mode, self.mesh, is_attr
            )(*args)
            _start_d2h(hdr, bits)
            batch = _BitmapBatch(hdr, bits, span_cap, seg=self, proto="bitmap")
            pending = _PendingBitmapHits
        out = []
        for i, d in enumerate(descs):
            # escalation/bitmap fallbacks re-dispatch the SINGLE-query fns
            # with this query's own descriptor (rare: capacities adapt)
            sa = single_args_for(d[0], d[1], d[2] if is_attr else None)
            refetch = lambda rc, sa=sa: _exact_runs_fn(  # noqa: E731
                has_time, rc, mode, self.mesh, is_attr
            )(*sa())
            packed = lambda sa=sa: _exact_packed_fn(  # noqa: E731
                has_time, mode, self.mesh, is_attr
            )(*sa())
            out.append(pending(self, batch, i, refetch, packed))
        return out

    def dispatch_exact_mask_batch(
        self, descs: Sequence[tuple], has_time: bool,
        attr: Optional[str] = None, attr_kind: str = "member",
    ) -> list:
        """Q exact predicates, ONE full-table sweep, ONE packed
        u8[q, n/8] bitmap back — no span framing, no RLE, no capacity
        escalation (the coalescer's kernel; see _exact_mask_batch_fn).
        ``descs`` = [(box_np u32[8], win_np u32[4]|None)] — or, with
        ``attr`` set, [(box, win, payload)]: the rank-code attribute
        plane ANDs into the stacked mask exactly like the span-framed
        batch editions (the coalescer's attr fold). Padded to the pow2 query
        bucket by repeating the last descriptor.

        On a multi-device mesh the PER-SHARD edition dispatches instead
        (_exact_shard_mask_batch_fn): each chip packs its local plane
        inside shard_map with no collective anywhere — a coalesced
        group on an SPMD mesh is rendezvous-safe by construction, not
        by fencing."""
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        q = len(descs)
        qpad = _pow2_at_least(q, 4)
        boxes_np = np.stack([d[0] for d in descs] + [descs[-1][0]] * (qpad - q))
        boxes_dev = replicate(self.mesh, boxes_np)
        if has_time:
            wins_np = np.stack(
                [d[1] for d in descs] + [descs[-1][1]] * (qpad - q)
            )
            wins_dev = replicate(self.mesh, wins_np)
        else:
            wins_dev = None
        is_attr, codes_dev, qcodes_dev = self._attr_batch_vectors(
            attr, attr_kind,
            [d[2] for d in descs] if attr is not None else None, qpad,
        )
        args = self._exact_args(
            boxes_dev, wins_dev, has_time, codes_dev, qcodes_dev
        )
        n_sh = self.mesh.devices.size
        if n_sh > 1:
            buf = _exact_shard_mask_batch_fn(
                has_time, qpad, self.mesh, is_attr
            )(*args)
            _start_d2h(buf)
            batch = _ShardMaskBatch(
                buf, self.n, n_sh, qpad, q, self.shard_n(), proto="mask_shard"
            )
            return [_PendingShardMaskHits(batch, i) for i in range(q)]
        buf = _exact_mask_batch_fn(
            has_time, qpad, mode, self.mesh, is_attr
        )(*args)
        _start_d2h(buf)
        batch = _MaskBatch(buf, self.n, q, proto="mask")
        return [_PendingMaskHits(batch, i) for i in range(q)]

    def dispatch_dual_mask_batch(
        self, kind: str, descs: Sequence[tuple], has_time: bool,
        attr: Optional[str] = None, attr_kind: str = "member",
    ) -> List["_PendingDualMaskHits"]:
        """Dual-plane (hit/decided) edition of dispatch_exact_mask_batch
        for the coalescer's extent ('xz') and banded-polygon ('poly')
        folds: Q stacked descriptors, ONE sweep, two full-table packed
        planes per query. ``descs`` = [(qbox u32[12], win u32[4]
        [, payload])] for 'xz', [(edges f32[E,4], box u32[8],
        win u32[4]|None [, payload])] for 'poly' (edge counts pad to the
        batch's shared pow2 bucket with degenerate zero edges). Resolves
        through _XZBatchScan — decided rows final, the ring/band host-
        certified — identical to the span-framed batch paths minus the
        framing. Multi-device meshes take the per-shard collective-free
        kernel (_dual_shard_mask_batch_fn)."""
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        q = len(descs)
        qpad = _pow2_at_least(q, 4)
        padded = list(descs) + [descs[-1]] * (qpad - q)
        if kind == "poly":
            ecap = _pow2_at_least(max(len(d[0]) for d in descs), 8)

            def pad_edges(e):
                out = np.zeros((ecap, 4), np.float32)
                out[: len(e)] = e
                return out

            edges_np = np.stack([pad_edges(d[0]) for d in padded])
            boxes_np = np.stack([d[1] for d in padded])
            wins_np = np.stack(
                [
                    d[2] if d[2] is not None else np.zeros(4, np.uint32)
                    for d in padded
                ]
            )
            is_attr, codes_dev, qcodes_dev = self._attr_batch_vectors(
                attr, attr_kind,
                [d[3] for d in descs] if attr is not None else None, qpad,
            )
            args = self._poly_args(
                replicate(self.mesh, edges_np),
                replicate(self.mesh, boxes_np),
                replicate(self.mesh, wins_np),
                has_time, codes_dev, qcodes_dev,
            )
        else:
            boxes_np = np.stack([d[0] for d in padded])
            wins_np = np.stack([d[1] for d in padded])
            is_attr, codes_dev, qcodes_dev = self._attr_batch_vectors(
                attr, attr_kind,
                [d[2] for d in descs] if attr is not None else None, qpad,
            )
            args = self._xz_args(
                replicate(self.mesh, boxes_np),
                replicate(self.mesh, wins_np),
                has_time, codes_dev, qcodes_dev,
            )
        n_sh = self.mesh.devices.size
        if n_sh > 1:
            hit, dec = _dual_shard_mask_batch_fn(
                kind, has_time, qpad, self.mesh, is_attr
            )(*args)
            shard_n = self.shard_n()
        else:
            hit, dec = _dual_mask_batch_fn(
                kind, has_time, qpad, mode, self.mesh, is_attr
            )(*args)
            shard_n = self.n_padded
        _start_d2h(hit, dec)
        batch = _DualMaskBatch(
            hit, dec, self.n, n_sh, qpad, q, shard_n,
            proto=f"mask_shard_{kind}" if n_sh > 1 else f"mask_{kind}",
        )
        return [_PendingDualMaskHits(batch, i) for i in range(q)]

    def load_poly(self, table: IndexTable) -> bool:
        """Exact limbs + f32 coords for the banded polygon path (point
        z-indices only)."""
        if self.kind not in ("z2", "z3"):
            return False
        if not self.load_exact(table):
            return False
        if self.xf is None:
            # load_raw's bool gates the t_ms aggregation column; the poly
            # path only needs the coords it packs unconditionally
            self.load_raw(table)
        return self.xf is not None

    def _poly_args(
        self, edges_dev, box_dev, win_dev, has_time: bool,
        codes_dev=None, qcode_dev=None,
    ) -> tuple:
        """Polygon-scan argument layout (single + batch + refetch) —
        must track _poly_arg_counts. A dummy window rides along when
        has_time is False (ignored). ``codes_dev``/``qcode_dev`` add the
        rank-code attribute plane."""
        if has_time:
            base = (
                self.xk_hi, self.xk_lo, self.yk_hi, self.yk_lo,
                self.tk_hi, self.tk_lo, self.tvalid, self.xf, self.yf,
            )
        else:
            base = (
                self.xk_hi, self.xk_lo, self.yk_hi, self.yk_lo,
                self.valid, self.xf, self.yf,
            )
        if codes_dev is not None:
            base = base + (codes_dev,)
        base = base + (edges_dev, box_dev, win_dev)
        if qcode_dev is not None:
            base = base + (qcode_dev,)
        return base

    def _dual_shard_batch(self, kind: str, has_time: bool, qpad: int,
                          args, attr=False) -> "_ShardBitmapBatch":
        """Shared shard-extract dispatch for the dual-plane batches
        ('xz' | 'poly'): per-shard windows + proto name in one place."""
        span_cap = self.shard_span_cap()
        hdr, bits = _dual_shard_bitmap_batch_fn(
            kind, has_time, span_cap, qpad, self.mesh, attr
        )(*args)
        _start_d2h(hdr, bits)
        return _ShardBitmapBatch(
            hdr, bits, span_cap, self.mesh.devices.size, qpad,
            self.shard_n(), seg=self, proto=f"bitmap_shard_{kind}",
        )

    def dispatch_poly_batch(
        self, descs: Sequence[tuple], has_time: bool,
        attr: Optional[str] = None, attr_kind: str = "member",
    ) -> list:
        """Q banded polygon scans in ONE device execution (dual
        hit/decided planes, xz resolve contract). ``descs`` =
        [(edges f32[E,4], box u32[8], win u32[4]|None)] — or, with
        ``attr`` set, [(edges, box, win, payload)]: the rank-code attr
        test ANDs into the hit plane (point-edition contract). Edge
        counts pad to the batch's shared pow2 bucket with degenerate
        zero edges."""
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        q = len(descs)
        qpad = (q + 3) // 4 * 4
        ecap = _pow2_at_least(max(len(d[0]) for d in descs), 8)
        padded = descs + [descs[-1]] * (qpad - q)

        def pad_edges(e):
            out = np.zeros((ecap, 4), np.float32)
            out[: len(e)] = e
            return out

        edges_np = np.stack([pad_edges(d[0]) for d in padded])
        boxes_np = np.stack([d[1] for d in padded])
        wins_np = np.stack(
            [d[2] if d[2] is not None else np.zeros(4, np.uint32) for d in padded]
        )
        is_attr, codes_dev, qcodes_dev = self._attr_batch_vectors(
            attr, attr_kind,
            [d[3] for d in descs] if attr is not None else None, qpad,
        )
        args = self._poly_args(
            replicate(self.mesh, edges_np),
            replicate(self.mesh, boxes_np),
            replicate(self.mesh, wins_np),
            has_time, codes_dev, qcodes_dev,
        )
        if self.mesh.devices.size > 1:
            batch = self._dual_shard_batch(
                "poly", has_time, qpad, args, attr=is_attr
            )
            pending = _PendingDualShardBitmapHits
        else:
            span_cap = self.span_cap()
            hdr, bits = _poly_bitmap_batch_fn(
                has_time, span_cap, qpad, mode, self.mesh, is_attr
            )(*args)
            _start_d2h(hdr, bits)
            batch = _BitmapBatch(hdr, bits, span_cap, seg=self,
                                 proto="bitmap_poly")
            pending = _PendingXZBitmapHits
        out = []
        for i, d in enumerate(descs):
            edges, box_np, win_np = d[0], d[1], d[2]
            payload = d[3] if is_attr else None

            def single_args(edges=edges, box_np=box_np, win_np=win_np,
                            payload=payload):
                _aflag, codes, qc = self._attr_plane_args(
                    attr if is_attr else None,
                    payload,
                    is_attr,
                )
                return self._poly_args(
                    replicate(self.mesh, pad_edges(edges)),
                    replicate(self.mesh, box_np),
                    replicate(
                        self.mesh,
                        win_np if win_np is not None else np.zeros(4, np.uint32),
                    ),
                    has_time, codes, qc,
                )

            refetch = lambda rc, sa=single_args: _poly_runs_fn(  # noqa: E731
                has_time, rc, mode, self.mesh, is_attr
            )(*sa())
            packed = lambda sa=single_args: _poly_packed_fn(  # noqa: E731
                has_time, mode, self.mesh, is_attr
            )(*sa())
            out.append(pending(self, batch, i, refetch, packed))
        return out

    def _xz_args(
        self, qbox_dev, win_dev, has_time: bool,
        codes_dev=None, qcode_dev=None,
    ) -> tuple:
        """Extent exact-scan argument layout (single + batch + refetch) —
        must track _xz_arg_counts. Dummies stand in for the time columns
        when has_time is False (the mask body ignores them; shard_map
        still needs row-sharded args). ``codes_dev``/``qcode_dev`` add
        the rank-code attribute plane."""
        valid = self.valid
        th = tl = self.xz_limbs[0]  # placeholder columns
        if has_time:
            th, tl = self.xz_tk
            if self.xz_tvalid is not None:
                valid = self.xz_tvalid
        base = (*self.xz_limbs, self.xz_isrect, valid, th, tl)
        if codes_dev is not None:
            base = base + (codes_dev,)
        base = base + (qbox_dev, win_dev)
        if qcode_dev is not None:
            base = base + (qcode_dev,)
        return base

    def count_poly_start(self, edges_dev, box_dev, win_dev, has_time: bool,
                         attr=None, payload=None, kind="member"):
        """Banded-polygon edition of count_xz_start: the ray cast's dual
        (hit, decided) planes answer COUNT as |decided hits| + the host-
        certified error band — same resolve contract, point-table
        geometry (the band materializes Points from the columnar
        coords). ``edges_dev`` is replicated ONCE by the caller (S
        segments pay one upload, like the box/window args)."""
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        aflag, codes, qc = self._attr_plane_args(attr, payload, kind)
        args = self._poly_args(edges_dev, box_dev, win_dev, has_time,
                               codes, qc)
        rcap = self._rcap
        buf = _poly_runs_fn(has_time, rcap, mode, self.mesh, aflag)(*args)
        _start_d2h(buf)
        return _PendingXZHits(
            self, rcap, buf,
            refetch=lambda rc: _poly_runs_fn(
                has_time, rc, mode, self.mesh, aflag
            )(*args),
            packed=lambda: _poly_packed_fn(
                has_time, mode, self.mesh, aflag
            )(*args),
        )

    def count_xz_start(self, qbox_dev, win_dev, has_time: bool,
                       attr=None, payload=None, kind="member"):
        """Dispatch ONE extent scan's dual (hit, decided) planes for a
        COUNT: the decided total needs no row extraction at all (the
        wire carries bounded RLE runs either way), and only the boundary
        ring takes the host's per-geometry test. Returns the pending
        dual handle; the executor sums len(decided) + certified ring."""
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        aflag, codes, qc = self._attr_plane_args(attr, payload, kind)
        args = self._xz_args(qbox_dev, win_dev, has_time, codes, qc)
        rcap = self._rcap
        buf = _xz_runs_fn(has_time, rcap, mode, self.mesh, aflag)(*args)
        _start_d2h(buf)
        return _PendingXZHits(
            self, rcap, buf,
            refetch=lambda rc: _xz_runs_fn(
                has_time, rc, mode, self.mesh, aflag
            )(*args),
            packed=lambda: _xz_packed_fn(
                has_time, mode, self.mesh, aflag
            )(*args),
        )

    def dispatch_exact_xz_batch(
        self, descs: Sequence[tuple], has_time: bool,
        attr: Optional[str] = None, attr_kind: str = "member",
    ) -> List["_PendingXZHits"]:
        """Q extent exact scans in ONE device execution (dual hit/decided
        planes per query; see _xz_exact_mask_body). ``descs`` =
        [(qbox_np u32[12], win_np u32[4])] — or, with ``attr`` set,
        [(qbox, win, payload)]: the attr test ANDs into the hit plane
        (member literal tuples or range (op, literal) predicate tuples,
        exactly the point edition's contract). The wire format follows
        the mesh exactly like the point edition."""
        mode = "spmd" if _mask_mode(self.mesh) == "pallas_spmd" else "local"
        q = len(descs)
        qpad = (q + 3) // 4 * 4
        boxes_np = np.stack([d[0] for d in descs] + [descs[-1][0]] * (qpad - q))
        wins_np = np.stack([d[1] for d in descs] + [descs[-1][1]] * (qpad - q))
        is_attr, codes_dev, qcodes_dev = self._attr_batch_vectors(
            attr, attr_kind,
            [d[2] for d in descs] if attr is not None else None, qpad,
        )
        args = self._xz_args(
            replicate(self.mesh, boxes_np), replicate(self.mesh, wins_np),
            has_time, codes_dev, qcodes_dev,
        )
        if self.mesh.devices.size > 1:
            batch = self._dual_shard_batch(
                "xz", has_time, qpad, args, attr=is_attr
            )
            pending = _PendingDualShardBitmapHits
        else:
            span_cap = self.span_cap()
            hdr, bits = _xz_bitmap_batch_fn(
                has_time, span_cap, qpad, mode, self.mesh, is_attr
            )(*args)
            _start_d2h(hdr, bits)
            batch = _BitmapBatch(hdr, bits, span_cap, seg=self,
                                 proto="bitmap_xz")
            pending = _PendingXZBitmapHits
        out = []
        for i, d in enumerate(descs):
            qbox_np, win_np = d[0], d[1]
            payload = d[2] if is_attr else None

            def single_args(qbox_np=qbox_np, win_np=win_np, payload=payload):
                _aflag, codes, qc = self._attr_plane_args(
                    attr if is_attr else None,
                    payload,
                    is_attr,
                )
                return self._xz_args(
                    replicate(self.mesh, qbox_np),
                    replicate(self.mesh, win_np),
                    has_time, codes, qc,
                )

            refetch = lambda rc, sa=single_args: _xz_runs_fn(  # noqa: E731
                has_time, rc, mode, self.mesh, is_attr
            )(*sa())
            packed = lambda sa=single_args: _xz_packed_fn(  # noqa: E731
                has_time, mode, self.mesh, is_attr
            )(*sa())
            out.append(pending(self, batch, i, refetch, packed))
        return out

    def hit_rows(self, boxes_dev, windows_dev) -> np.ndarray:
        """Sorted candidate row indices, compacted ON DEVICE (sync)."""
        return self.dispatch_hits(boxes_dev, windows_dev).rows()

    def to_block_rows(self, rows: np.ndarray) -> List[Tuple[FeatureBlock, np.ndarray]]:
        """Segment-local candidate rows -> [(block, local rows)]."""
        if not len(rows):
            return []
        starts = np.asarray(self.block_starts + [self.n], dtype=np.int64)
        out = []
        which = np.searchsorted(starts, rows, side="right") - 1
        for blk in np.unique(which):
            local = rows[which == blk] - starts[blk]
            out.append((self.blocks[int(blk)], local))
        return out


class _PendingHits:
    """A dispatched segment scan: one fused RLE buffer en route to host.

    rows() blocks on the transfer and decodes; run-capacity overflow
    recomputes at the escalated pow2 capacity (remembered on the segment),
    and pathologically fragmented dense results degrade to the packed
    bitmap — the only case where a second round trip is paid.
    """

    __slots__ = ("seg", "rcap", "buf", "_refetch", "_packed", "_rows")

    def __init__(self, seg: DeviceSegment, rcap: int, buf, refetch, packed):
        self.seg = seg
        self.rcap = rcap
        self.buf = buf
        self._refetch = refetch  # rcap -> new runs buffer (device)
        self._packed = packed  # () -> packed bitmap (device), or None
        self._rows: Optional[np.ndarray] = None

    def rows(self) -> np.ndarray:
        if self._rows is None:  # cached: shared pendings resolve once
            self._rows = self._resolve()
        return self._rows

    def _resolve(self) -> np.ndarray:
        seg = self.seg
        buf = _np_local(self.buf)
        cnt, nruns = int(buf[0]), int(buf[1])
        seg.remember_rcap(nruns)
        if cnt == 0:
            return np.empty(0, dtype=np.int64)
        rcap = self.rcap
        if nruns > rcap:
            if self._packed is not None and nruns > max(
                1, seg.n_padded // DENSE_BITMAP_FACTOR
            ):
                # fragmented + dense: the bitmap is the smaller transfer
                mask = np.unpackbits(_np_local(self._packed()))[: seg.n].astype(bool)
                return np.flatnonzero(mask)
            while rcap < nruns:
                rcap *= 2
            buf = _np_local(self._refetch(rcap))
        starts = buf[2 : 2 + nruns].astype(np.int64)
        lens = buf[2 + rcap : 2 + rcap + nruns].astype(np.int64)
        return _expand_runs(starts, lens)


def _expand_runs(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """RLE runs -> sorted row indices."""
    if not len(starts):
        return np.empty(0, dtype=np.int64)
    out = np.repeat(starts, lens)
    base = np.concatenate(([0], np.cumsum(lens[:-1])))
    return out + (np.arange(len(out), dtype=np.int64) - np.repeat(base, lens))


def _xz_query_limbs(qenv, rect: bool, t_lo, t_hi):
    """(qbox u32[12], win u32[4], has_time): the ONE place that encodes an
    extent query's envelope + placeholder-zero sort-key limbs, rect flag,
    and time-window limbs. Must stay bit-identical with the unpacking in
    _xz_exact_mask_body / _devseek_xz_fn."""
    from geomesa_tpu.ops.zkernels import (
        f64_sort_keys,
        i64_sort_keys,
        split_u64_to_limbs,
    )

    keys = f64_sort_keys(
        np.asarray([qenv.xmin, qenv.ymin, qenv.xmax, qenv.ymax, 0.0])
    )
    hi, lo = split_u64_to_limbs(keys)
    qbox = np.zeros(12, dtype=np.uint32)
    qbox[0:10:2] = hi
    qbox[1:10:2] = lo
    qbox[10] = 1 if rect else 0
    win = np.zeros(4, dtype=np.uint32)
    has_time = t_lo is not None or t_hi is not None
    if has_time:
        lo_ms = np.iinfo(np.int64).min + 1 if t_lo is None else t_lo
        hi_ms = np.iinfo(np.int64).max if t_hi is None else t_hi
        thi, tlo = split_u64_to_limbs(i64_sort_keys(np.asarray([lo_ms, hi_ms])))
        win[:] = (thi[0], tlo[0], thi[1], tlo[1])
    return qbox, win, has_time


def _count_dual_resolve(pendings, node, geom) -> int:
    """Shared COUNT resolve for every dual-plane dispatch (extent
    envelopes AND banded polygons): len(decided) needs no extraction;
    only the ring/band takes the host's exact per-geometry test."""
    total = 0
    none_dec = np.empty(0, dtype=np.int64)
    for seg, ph in pendings:
        hit_rows, dec_rows = ph.rows()
        total += len(dec_rows)
        ring = _ring_split(hit_rows, dec_rows)
        for _block, local in _yield_xz_rows(seg, none_dec, ring, node, geom):
            total += len(local)
    return total


def _ring_split(hit_rows: np.ndarray, dec_rows: np.ndarray) -> np.ndarray:
    """Ring = hits not device-decided (both inputs sorted): membership
    via one searchsorted merge — THE shared split every extent resolve
    uses (extraction and count must never diverge on it)."""
    if not len(hit_rows):
        return hit_rows
    in_dec = np.zeros(len(hit_rows), dtype=bool)
    if len(dec_rows):
        pos = np.searchsorted(dec_rows, hit_rows)
        pos = np.minimum(pos, len(dec_rows) - 1)
        in_dec = dec_rows[pos] == hit_rows
    return hit_rows[~in_dec]


def _yield_xz_rows(seg, dec_rows: np.ndarray, ring: np.ndarray, node, geom):
    """Shared tail of every extent device scan: ring rows (hit but not
    device-decided) take the host's exact per-geometry test, decided rows
    are final. Yields (block, local_rows)."""
    from geomesa_tpu.filter.evaluate import _geom_predicate

    if len(ring):
        for block, local in seg.to_block_rows(np.sort(ring)):
            try:
                geoms = block.gather(geom, local)
            except KeyError:
                # point schemas store coords columnar (geom__x/__y), not
                # geometry objects — materialize Points for the (small)
                # band only
                from geomesa_tpu.geom.base import Point

                xs = block.gather(geom + "__x", local)
                ys = block.gather(geom + "__y", local)
                nulls = block.gather(geom + "__null", local)
                geoms = [
                    None if nl else Point(float(x), float(y))
                    for x, y, nl in zip(xs, ys, nulls)
                ]
            m = np.fromiter(
                (g is not None and _geom_predicate(node, g) for g in geoms),
                bool,
                len(local),
            )
            if m.any():
                yield block, local[m]
    if len(dec_rows):
        yield from seg.to_block_rows(np.sort(dec_rows))


class _PendingXZHits:
    """A dispatched extent segment scan: dual fused RLE buffers (hit +
    decided runs) en route to host. rows() -> (hit_rows, decided_rows),
    both sorted; decided_rows is a subset of hit_rows. Overflow of either
    run set escalates; fragmented dense results degrade to dual packed
    bitmaps."""

    __slots__ = ("seg", "rcap", "buf", "_refetch", "_packed", "_rows")

    def __init__(self, seg: DeviceSegment, rcap: int, buf, refetch, packed):
        self.seg = seg
        self.rcap = rcap
        self.buf = buf
        self._refetch = refetch
        self._packed = packed
        self._rows = None

    def rows(self):
        if self._rows is None:
            self._rows = self._resolve()
        return self._rows

    def _one(self, buf, rcap):
        nruns = int(buf[1])
        starts = buf[2 : 2 + nruns].astype(np.int64)
        lens = buf[2 + rcap : 2 + rcap + nruns].astype(np.int64)
        return _expand_runs(starts, lens)

    def _resolve(self):
        seg = self.seg
        buf = _np_local(self.buf)
        rcap = self.rcap
        half = 2 + 2 * rcap
        hit_b, dec_b = buf[:half], buf[half:]
        nruns = max(int(hit_b[1]), int(dec_b[1]))
        seg.remember_rcap(nruns)
        if int(hit_b[0]) == 0:
            empty = np.empty(0, dtype=np.int64)
            return empty, empty
        if nruns > rcap:
            if self._packed is not None and nruns > max(
                1, seg.n_padded // DENSE_BITMAP_FACTOR
            ):
                both = _np_local(self._packed())
                h = len(both) // 2
                hm = np.unpackbits(both[:h])[: seg.n].astype(bool)
                dm = np.unpackbits(both[h:])[: seg.n].astype(bool)
                return np.flatnonzero(hm), np.flatnonzero(dm)
            while rcap < nruns:
                rcap *= 2
            buf = _np_local(self._refetch(rcap))
            half = 2 + 2 * rcap
            hit_b, dec_b = buf[:half], buf[half:]
        return self._one(hit_b, rcap), self._one(dec_b, rcap)


class _XZBatchScan:
    """Batched extent scans resolved against the plan's own spatial node:
    decided rows are final; the ring (hit minus decided) takes the host's
    exact per-geometry test. ``exact`` is True — yielded rows ARE the
    result set (the valid masks bake tombstones and time-nulls)."""

    __slots__ = ("pending", "node", "geom", "exact", "seek")

    def __init__(self, pending, node, geom):
        self.pending = pending  # [(seg, _PendingXZHits)]
        self.node = node
        self.geom = geom
        self.exact = True
        self.seek = True

    def prefetch(self) -> None:
        """Resolve prefetchable shared buffers NOW (the _PendingScan
        contract): a coalesced dual-mask group's shared D2H lands in the
        leader's cost collector and apportions across members instead of
        hitting the first resolver's receipt. Span-framed pendings have
        no hook and resolve lazily as before."""
        for _seg, ph in self.pending:
            fn = getattr(ph, "prefetch", None)
            if fn is not None:
                fn()

    def __iter__(self):
        for seg, ph in self.pending:
            hit_rows, dec_rows = ph.rows()
            if not len(hit_rows):
                continue
            ring = _ring_split(hit_rows, dec_rows)
            yield from _yield_xz_rows(seg, dec_rows, ring, self.node, self.geom)


class _PendingScan:
    """All of one table's dispatched segment scans; iterating resolves them
    in order and maps segment-local rows back to (block, local rows).

    ``exact=True`` marks hit lists computed by the EXACT f64 predicate on
    device (no conservative over-coverage): the caller may skip its host
    post-filter entirely for the primary spatio-temporal predicate.
    """

    __slots__ = ("pending", "exact")

    def __init__(self, pending, exact: bool = False):
        self.pending = pending
        self.exact = exact

    def prefetch(self) -> None:
        """Resolve any prefetchable shared device buffers NOW (coalescer
        seam): the shared sweep's D2H lands in the CALLER's cost
        collector instead of whichever member resolves first. Pendings
        without a prefetch hook resolve lazily as before."""
        for _seg, ph in self.pending:
            fn = getattr(ph, "prefetch", None)
            if fn is not None:
                fn()

    def __iter__(self):
        for seg, ph in self.pending:
            for block, local in seg.to_block_rows(ph.rows()):
                yield block, local


def _merge_overlapping_intervals(starts, ends, flags):
    """Coalesce overlapping [start, end) row intervals (flags AND-merge —
    False is safe in both kernel modes: the row merely takes the test it
    would pass anyway). Disjoint inputs return unchanged."""
    if len(starts) <= 1:
        return starts, ends, flags
    order = np.argsort(starts, kind="stable")
    s, e, f = starts[order], ends[order], flags[order]
    run_end = np.maximum.accumulate(e)
    if (s[1:] >= run_end[:-1]).all():
        return s, e, f  # already disjoint (sorted)
    new_grp = np.concatenate(([True], s[1:] >= run_end[:-1]))
    heads = np.flatnonzero(new_grp)
    gs = s[heads]
    ge = np.maximum.reduceat(e, heads)
    gf = np.minimum.reduceat(f.astype(np.int8), heads).astype(bool)
    return gs, ge, gf


class _HostSeekScan:
    """A host searchsorted block seek wrapped in the _PendingScan shape:
    the executor chose seeking over device dispatch for a selective plan.
    ``exact`` is False (candidates are range-granular — the caller post-
    filters) and ``seek`` is True (range-granular rows are never eligible
    for the loose-bbox shortcut, which promises int-domain granularity).
    Yields (block, rows, covered) triples: ``covered`` rows came from
    ``contained`` ranges and provably satisfy the exact primary predicate,
    so the caller applies only the residual (secondary) filter to them.

    Carries the per-block (starts, ends, flags) intervals the chooser's
    cost probe already computed — row expansion happens lazily at
    iteration, so the seek runs exactly once per query.

    With ``pred`` set — the query reduced to one exact bbox(+interval)
    predicate (_exact_predicate_shape) and the native lib is available —
    iteration runs the one-pass C++ seek-scan (native/seekscan.cpp, the
    tserver Z3Iterator hot-loop analog): final filtered rows come straight
    out, ``exact`` flips True, and the caller skips its post-filter."""

    __slots__ = ("table", "per_block", "pred", "exact", "seek")

    def __init__(self, table: IndexTable, per_block, pred=None):
        self.exact = pred is not None
        self.seek = True
        self.table = table
        self.per_block = per_block
        self.pred = pred

    def __iter__(self):
        if self.pred is not None:
            if self.pred[0] == "xz":
                yield from self._iter_native_xz()
            else:
                yield from self._iter_native()
            return
        for block, starts, ends, flags in self.per_block:
            rows, covered = self.table.expand_covered(block, starts, ends, flags)
            if len(rows):
                yield block, rows, covered

    def _iter_native_xz(self):
        """Extent plans: the C++ envelope kernel decides overlap/inside per
        candidate row; only the boundary-straddling ring takes the exact
        per-row geometry test. exact=True — rows ARE the result set."""
        from geomesa_tpu.filter.evaluate import _geom_predicate
        from geomesa_tpu.native import env_seek_scan_native

        _, geom, node, qenv, rect = self.pred
        qbox = (qenv.xmin, qenv.ymin, qenv.xmax, qenv.ymax)
        for block, starts, ends, flags in self.per_block:
            bx = block.columns[geom + "__bxmin"]
            by = block.columns[geom + "__bymin"]
            cx = block.columns[geom + "__bxmax"]
            cy = block.columns[geom + "__bymax"]
            got = env_seek_scan_native(
                bx, by, cx, cy, starts, ends, qbox, rect,
                isrect=block.columns.get(geom + "__isrect"),
            )
            if got is None:
                # lib raced away: same semantics via the shared vectorized
                # prescreen in _eval_spatial (no third copy of the rules)
                from geomesa_tpu.filter.evaluate import _eval_spatial

                cand, _cov = self.table.expand_covered(block, starts, ends, flags)
                if not len(cand):
                    continue
                sub = {
                    geom: block.gather(geom, cand),
                    geom + "__bxmin": bx[cand],
                    geom + "__bymin": by[cand],
                    geom + "__bxmax": cx[cand],
                    geom + "__bymax": cy[cand],
                }
                final = cand[_eval_spatial(node, self.table.ft, sub)]
                if len(final):  # expand_covered already stripped tombstones
                    yield block, final
                continue
            rows, decided = got
            if not len(rows):
                continue
            ring = rows[~decided]
            if len(ring):
                geoms = block.gather(geom, ring)
                keep = np.fromiter(
                    (g is not None and _geom_predicate(node, g) for g in geoms),
                    bool,
                    len(ring),
                )
                final = np.sort(np.concatenate([rows[decided], ring[keep]]))
            else:
                final = rows[decided]
            keepmask = self.table.tombstone_keep(block, final)
            if keepmask is not None:
                final = final[keepmask]
            if len(final):
                yield block, final

    def _iter_native(self):
        from geomesa_tpu.native import seek_scan_native

        _z, geom, dtg, box, t_lo, t_hi, use_covered = self.pred
        want_t = t_lo is not None or t_hi is not None
        lo = np.iinfo(np.int64).min + 1 if t_lo is None else t_lo
        hi = np.iinfo(np.int64).max if t_hi is None else t_hi
        for block, starts, ends, flags in self.per_block:
            if not use_covered:
                flags = np.zeros(len(starts), dtype=bool)
            # the kernel iterates intervals verbatim: overlapping candidate
            # intervals (OR'd attr ranges, duplicate IN values) would emit
            # shared rows once per interval — merge them first (z ranges
            # arrive merged-disjoint; attr ranges carry no such guarantee)
            starts, ends, flags = _merge_overlapping_intervals(starts, ends, flags)
            cand = None
            if geom + "__x" in block.columns:
                # z-index blocks own contiguous x/y(/t): the kernel streams
                # candidate intervals straight off the sorted columns
                xs = block.columns[geom + "__x"]
                ys = block.columns[geom + "__y"]
                t = block.columns.get(dtg) if want_t else None
                kstarts, kends, kflags = starts, ends, flags
            else:
                # reduced index blocks (attr/id residual plans): gather the
                # candidate rows' coords from the record table — O(cands),
                # and candidates are value-exact so the set is small
                cand, _cov = self.table.expand_covered(block, starts, ends, flags)
                if not len(cand):
                    continue
                xs = block.gather(geom + "__x", cand)
                ys = block.gather(geom + "__y", cand)
                t = block.gather(dtg, cand) if want_t else None
                kstarts = np.zeros(1, dtype=np.int64)
                kends = np.full(1, len(cand), dtype=np.int64)
                kflags = np.zeros(1, dtype=bool)
            if want_t and t is None:
                t = block.full_col(dtg)
            rows = seek_scan_native(
                xs, ys, t, kstarts, kends, kflags, box, lo, hi
            )
            if rows is None:
                # lib raced away: numpy equivalent of the same exact test
                # (exact=True promises FILTERED rows — never raw candidates)
                if cand is None:
                    cand, _cov = self.table.expand_covered(block, starts, ends, flags)
                    if not len(cand):
                        continue
                    xs = xs[cand]
                    ys = ys[cand]
                    t = t[cand] if t is not None else None
                m = (xs >= box[0]) & (xs <= box[2]) & (ys >= box[1]) & (ys <= box[3])
                if want_t:
                    m &= (t >= lo) & (t <= hi)
                rows = cand[m]  # expand_covered already stripped tombstones
            elif cand is not None:
                rows = cand[rows]  # kernel positions -> block rows
            else:
                keep = self.table.tombstone_keep(block, rows)
                if keep is not None:
                    rows = rows[keep]
            if len(rows):
                yield block, rows


# device-assisted seek jit cache: one entry per
# (has_time, n_interval_bucket, candidate_bucket, mode)
_DEVSEEK_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _devseek_fn(has_time: bool, n_iv: int, cand_cap: int, mesh=None):
    """Candidate-interval exact test on device.

    The device-assisted seek protocol (the round-3 answer to the tserver
    Z3Iterator hot loop, accumulo/iterators/Z3Iterator.scala:42-65): the
    HOST plans ranges and seeks them into the sorted key columns
    (searchsorted — tiny), ships only the candidate INTERVALS (~KBs) to
    the device, and the device expands them, gathers the candidate rows'
    f64/i64 sort-key limbs from its resident mirror, evaluates the
    query's own exact predicate, and returns a packed bitmap over the
    candidate space (cand_cap/8 bytes — the "~32KB back" transfer).
    Per-query device work is O(candidates), not O(N)."""
    key = (has_time, n_iv, cand_cap, mesh)
    fn = _DEVSEEK_FNS.get(key)
    if fn is not None:
        return fn
    from geomesa_tpu.ops.filters import exact_st_mask

    def run(xh, xl, yh, yl, th, tl, valid, starts, lens, box, win):
        seg_end = jnp.cumsum(lens)
        total = seg_end[-1]
        j = jnp.arange(cand_cap, dtype=jnp.int32)
        seg = jnp.searchsorted(seg_end, j, side="right")
        segc = jnp.clip(seg, 0, n_iv - 1)
        prev = seg_end[segc] - lens[segc]
        rows = starts[segc] + (j - prev)
        ok = j < total
        rows = jnp.where(ok, rows, 0)
        gxh = jnp.take(xh, rows)
        gxl = jnp.take(xl, rows)
        gyh = jnp.take(yh, rows)
        gyl = jnp.take(yl, rows)
        gvalid = jnp.take(valid, rows) & ok
        if has_time:
            gth = jnp.take(th, rows)
            gtl = jnp.take(tl, rows)
            m = exact_st_mask(gxh, gxl, gyh, gyl, gvalid, box, gth, gtl, win)
        else:
            m = exact_st_mask(gxh, gxl, gyh, gyl, gvalid, box)
        return jnp.packbits(m)

    # the candidate gathers from row-sharded mirrors lower with
    # cross-device collectives on a multi-device mesh: gated like every
    # other collective-bearing kernel (the rendezvous fence)
    fn = _mesh_gated(instrumented_jit("devseek", run), mesh)
    _DEVSEEK_FNS[key] = fn
    return fn


def _pow2_at_least(n: int, floor: int = 256) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def _str_successor(s: str):
    """Smallest string greater than EVERY string with prefix ``s`` (the
    LIKE-prefix upper bound): increment the last incrementable code
    point, dropping any trailing U+10FFFF. None = unbounded (every
    vocab entry past the searchsorted lower bound matches)."""
    while s and ord(s[-1]) >= 0x10FFFF:
        s = s[:-1]
    if not s:
        return None
    return s[:-1] + chr(ord(s[-1]) + 1)


_DEVSEEK_XZ_FNS: Dict[tuple, "jax.stages.Wrapped"] = {}


def _devseek_xz_fn(n_iv: int, cand_cap: int, has_time: bool = False,
                   mesh=None):
    """Extent (xz2/xz3) device-assisted seek: exact f64 envelope tests on
    the candidates via sort-key limb compares (the device edition of
    native/seekscan.cpp geomesa_env_seek_scan), plus — for xz3 — the
    exact i64 ms time-window test. Returns TWO packed bitmaps over the
    candidate space: ``hit`` (envelope overlaps the query box and the
    time window matches — exact) and ``decided`` (provably satisfies the
    exact predicate: envelope inside a rectangle query, or an isrect
    feature overlapping one). Only hit & ~decided rows — the boundary-
    straddling ring — need the host's per-geometry test."""
    key = (n_iv, cand_cap, has_time, mesh)
    fn = _DEVSEEK_XZ_FNS.get(key)
    if fn is not None:
        return fn
    from geomesa_tpu.ops.zkernels import limbs_in_range, limbs_leq

    def run(limbs, isrect, valid, starts, lens, qbox, rect, th, tl, win):
        # limbs: tuple of 8 arrays (bxmin, bymin, bxmax, bymax) x (hi, lo)
        seg_end = jnp.cumsum(lens)
        total = seg_end[-1]
        j = jnp.arange(cand_cap, dtype=jnp.int32)
        seg = jnp.searchsorted(seg_end, j, side="right")
        segc = jnp.clip(seg, 0, n_iv - 1)
        prev = seg_end[segc] - lens[segc]
        rows = starts[segc] + (j - prev)
        ok = j < total
        rows = jnp.where(ok, rows, 0)
        g = [jnp.take(a, rows) for a in limbs]
        bxmin_h, bxmin_l, bymin_h, bymin_l, bxmax_h, bxmax_l, bymax_h, bymax_l = g
        ir = jnp.take(isrect, rows)
        va = jnp.take(valid, rows) & ok
        # qbox: u32[16] = (qxmin, qymin, qxmax, qymax) x (hi, lo) twice-
        # packed: [xmin_h, xmin_l, ymin_h, ymin_l, xmax_h, xmax_l,
        # ymax_h, ymax_l, zero_h, zero_l, ...pad]
        qxmin_h, qxmin_l = qbox[0], qbox[1]
        qymin_h, qymin_l = qbox[2], qbox[3]
        qxmax_h, qxmax_l = qbox[4], qbox[5]
        qymax_h, qymax_l = qbox[6], qbox[7]
        zero_h, zero_l = qbox[8], qbox[9]
        overlap = (
            limbs_leq(qxmin_h, qxmin_l, bxmax_h, bxmax_l)
            & limbs_leq(bxmin_h, bxmin_l, qxmax_h, qxmax_l)
            & limbs_leq(qymin_h, qymin_l, bymax_h, bymax_l)
            & limbs_leq(bymin_h, bymin_l, qymax_h, qymax_l)
        )
        placeholder = (
            (bxmin_h == zero_h) & (bxmin_l == zero_l)
            & (bymin_h == zero_h) & (bymin_l == zero_l)
            & (bxmax_h == zero_h) & (bxmax_l == zero_l)
            & (bymax_h == zero_h) & (bymax_l == zero_l)
        )
        inside = (
            limbs_leq(qxmin_h, qxmin_l, bxmin_h, bxmin_l)
            & limbs_leq(bxmax_h, bxmax_l, qxmax_h, qxmax_l)
            & limbs_leq(qymin_h, qymin_l, bymin_h, bymin_l)
            & limbs_leq(bymax_h, bymax_l, qymax_h, qymax_l)
        )
        hit = overlap & va
        if has_time:
            gth = jnp.take(th, rows)
            gtl = jnp.take(tl, rows)
            hit = hit & limbs_in_range(gth, gtl, win[0], win[1], win[2], win[3])
        decided = hit & rect & ~placeholder & (inside | ir)
        return jnp.concatenate([jnp.packbits(hit), jnp.packbits(decided)])

    # sharded-mirror candidate gathers: same rendezvous fence as the
    # point edition above
    fn = _mesh_gated(instrumented_jit("devseek_xz", run), mesh)
    _DEVSEEK_XZ_FNS[key] = fn
    return fn


class _DeviceSeekXZScan:
    """Dispatched xz2 device seeks: decided rows are final; the ring
    (hit & ~decided) takes the host's exact per-geometry test. ``exact``
    is True — yielded rows ARE the result set."""

    __slots__ = ("pending", "node", "geom", "exact", "seek")

    def __init__(self, pending, node, geom):
        self.pending = pending  # [(segment, starts, lens, total, buf)]
        self.node = node  # the spatial ast node for ring tests
        self.geom = geom
        self.exact = True
        self.seek = True

    def __iter__(self):
        for seg, starts, lens, total, buf in self.pending:
            raw = _np_local(buf)
            half = len(raw) // 2
            hit = np.unpackbits(raw[:half])[:total].astype(bool)
            decided = np.unpackbits(raw[half:])[:total].astype(bool)
            j = np.flatnonzero(hit)
            if not len(j):
                continue
            seg_end = np.cumsum(lens)
            which = np.searchsorted(seg_end, j, side="right")
            prev = seg_end[which] - lens[which]
            rows = starts[which] + (j - prev)
            dec = decided[j]
            yield from _yield_xz_rows(
                seg, rows[dec], rows[~dec], self.node, self.geom
            )


class _DeviceSeekScan:
    """Device-assisted seek: dispatched per segment, resolved lazily.

    ``exact`` is True — the device evaluated the query's own f64/ms
    semantics on the candidates, so hits ARE the result set (tombstones
    ride the device valid mask; null dates ride tvalid)."""

    __slots__ = ("pending", "exact", "seek")

    def __init__(self, pending):
        self.pending = pending  # [(segment, starts, lens, total, buf)]
        self.exact = True
        self.seek = True

    def __iter__(self):
        for seg, starts, lens, total, buf in self.pending:
            bits = np.unpackbits(_np_local(buf))[:total].astype(bool)
            j = np.flatnonzero(bits)
            if not len(j):
                continue
            # candidate index -> segment row (same arithmetic as on device)
            seg_end = np.cumsum(lens)
            which = np.searchsorted(seg_end, j, side="right")
            prev = seg_end[which] - lens[which]
            rows = starts[which] + (j - prev)
            yield from seg.to_block_rows(rows)


class DeviceIndex:
    """Segmented device-resident mirror of one index table.

    ``refresh`` reconciles against the host table incrementally: appended
    blocks become new segments, new tombstones flip valid bits, and a
    compaction (block identity mismatch) triggers a full rebuild. Segments
    merge device-side once fragmentation exceeds MAX_SEGMENTS.
    """

    def __init__(self, mesh, table: IndexTable):
        self.mesh = mesh
        self.kind = table.index.name
        self.segments: List[DeviceSegment] = []
        self.version = -1
        self._n_tombstones = 0
        self.refresh(table)

    @property
    def n(self) -> int:
        return sum(s.n for s in self.segments)

    def refresh(self, table: IndexTable) -> None:
        if table.version == self.version:
            return
        synced: List[int] = []
        for s in self.segments:
            synced.extend(s.block_ids)
        ids = [id(b) for b in table.blocks]
        if ids[: len(synced)] != synced:
            # blocks were rewritten (compact) — rebuild from scratch
            self.segments = []
            self._n_tombstones = 0
            synced = []
        new_blocks = table.blocks[len(synced):]
        if new_blocks and len(self.segments) >= MAX_SEGMENTS:
            # fragmentation limit: rebuild one merged segment up front
            # instead of uploading a per-batch segment just to discard it
            merged = DeviceSegment(self.mesh, table, table.blocks)
            if table.tombstones:
                merged.apply_tombstones(table.tombstones)
            self.segments = [merged]
            self._n_tombstones = len(table.tombstones)
        elif new_blocks:
            seg = DeviceSegment(self.mesh, table, new_blocks)
            if table.tombstones:
                seg.apply_tombstones(table.tombstones)
            self.segments.append(seg)
        if len(table.tombstones) != self._n_tombstones:
            for s in self.segments:
                s.apply_tombstones(table.tombstones)
            self._n_tombstones = len(table.tombstones)
        self.version = table.version


class TpuScanExecutor:
    """Pluggable executor for TpuDataStore: device pre-filter for point
    indices, host fallback elsewhere. Also evaluates the exact post-filter
    (numpy) on candidates, like HostScanExecutor."""

    def __init__(self, mesh=None, breaker=None):
        import weakref

        from geomesa_tpu.utils.breaker import CircuitBreaker

        self.mesh = mesh if mesh is not None else default_mesh()
        # id() keys can be recycled after GC, so each entry holds a weakref
        # to its table: identity is re-checked on hit and dead entries are
        # evicted (frees the device-resident shards)
        self._cache: Dict[int, Tuple["weakref.ref", DeviceIndex]] = {}
        self._density_fns: Dict[tuple, tuple] = {}
        # aggregate-pyramid build reductions, one per cell-bits setting
        self._pyramid_fns: Dict[int, Any] = {}
        # circuit breaker over device.dispatch/fetch: a PERSISTENTLY
        # failing link short-circuits queries straight to the host scan
        # (zero per-query dispatch/retry cost) until a half-open probe
        # succeeds — the probe query itself rebuilds the evicted mirror
        self.breaker = breaker if breaker is not None else CircuitBreaker("device")

    def device_index(self, table: IndexTable) -> DeviceIndex:
        import weakref

        # sweep dead entries on EVERY lookup — segments pin host block
        # columns strongly, so a dropped table must not stay resident until
        # the next cache miss happens to evict it
        for k in [k for k, (ref, _) in self._cache.items() if ref() is None]:
            del self._cache[k]
        entry = self._cache.get(id(table))
        cached = None
        if entry is not None and entry[0]() is table:
            cached = entry[1]
        if cached is None:
            cached = DeviceIndex(self.mesh, table)
            self._cache[id(table)] = (weakref.ref(table), cached)
        elif cached.version != table.version:
            cached.refresh(table)
        return cached

    def supports(self, table: IndexTable, plan: QueryPlan) -> bool:
        return (
            table.index.name in ("z3", "z2", "xz2", "xz3")
            and not plan.values.disjoint
            and bool(plan.values.spatial_envelopes)
        )

    @staticmethod
    def _has_visibilities(table: IndexTable) -> bool:
        return any(b.has_col("__vis__") for b in table.blocks)

    def _seek_scan(self, table: IndexTable, plan) -> Optional[_HostSeekScan]:
        """Cost-based execution choice (the StrategyDecider's cost model
        applied at the execution layer): when the plan's decomposed ranges
        cover a small fraction of the sorted blocks, a host searchsorted
        seek touches only candidate rows and beats dispatching a device
        full-scan — especially over a high-latency device link. This is
        the reference's own architecture: BatchScanPlan scans only the
        decomposed ranges (AccumuloQueryPlan.scala:113-140), it never
        full-scans the table. GEOMESA_SEEK: auto (default) | 0 (never) |
        1 (whenever ranges exist); GEOMESA_SEEK_FRAC tunes the cutoff."""
        import os

        mode = os.environ.get("GEOMESA_SEEK", "auto")
        if mode == "0" or not plan.ranges:
            return None
        nrows = table.num_rows
        if nrows == 0:
            return None
        # one searchsorted pass serves both the cost probe and (if the seek
        # wins) the scan itself — _HostSeekScan expands rows lazily from
        # these intervals
        per_block = []
        total = 0
        for b in table.blocks:
            starts, ends, flags = b.scan_intervals(plan.ranges)
            if len(starts):
                total += int(np.maximum(ends - starts, 0).sum())
                per_block.append((b, starts, ends, flags))
        if mode != "1":
            frac = float(os.environ.get("GEOMESA_SEEK_FRAC", "0.4"))
            if total > frac * nrows:
                return None
        dev = self._device_seek(table, plan, per_block, total)
        if dev is None:
            dev = self._device_seek_xz(table, plan, per_block, total)
        if dev is not None:
            return dev
        pred = self._native_seek_pred(table, plan)
        if pred is None:
            pred = self._xz_native_pred(table, plan)
        return _HostSeekScan(table, per_block, pred)

    @staticmethod
    def _devseek_enabled() -> bool:
        """GEOMESA_DEVSEEK: 1 (force) | auto/0 (off).

        Auto is OFF since round 3's silicon session: the candidate-gather
        protocol measured ~500 ms/query on TPU v5e (random 2M-row gathers
        from a 20M-row mirror) while the streaming full-scan exact mask is
        ~1 ms — TPU gathers are not HBM-bandwidth-bound, streaming compares
        are. The batched exact path (dispatch_exact_batch's span-framed
        bitmaps) supersedes this protocol; it stays forceable for parity
        tests and for hardware where gathers win."""
        import os

        return os.environ.get("GEOMESA_DEVSEEK", "auto") == "1"

    def _device_seek_xz(self, table: IndexTable, plan, per_block, total: int):
        """Extent edition of the device-assisted seek: exact f64 envelope
        tests (sort-key limb compares) + isrect decisions on device; only
        the boundary-straddling ring takes the host's per-geometry test.
        Qualifies exactly like the native envelope kernel (one spatial
        predicate on the default geometry of an xz2 plan)."""
        if not self._devseek_enabled():
            return None
        if total == 0 or total > (1 << 22):
            return None
        shape = self._xz_pred_shape(table, plan)
        if shape is None:
            return None
        geom, node, qenv, rect, t_lo, t_hi = shape
        has_time = t_lo is not None or t_hi is not None
        dev = self.device_index(table)
        if not dev.segments or not all(
            seg.load_exact_xz(table) for seg in dev.segments
        ):
            return None
        if has_time and any(seg.xz_tk is None for seg in dev.segments):
            return None
        synced = set()
        for seg in dev.segments:
            synced.update(seg.block_ids)
        if any(id(b) not in synced for b, _s, _e, _f in per_block):
            return None
        qbox12, win, _ht = _xz_query_limbs(qenv, rect, t_lo, t_hi)
        qbox_dev = replicate(self.mesh, qbox12[:10])
        rect_dev = replicate(self.mesh, np.asarray(bool(qbox12[10])))
        win_dev = replicate(self.mesh, win) if has_time else None
        pending = []
        for seg, starts, lens, tot, n_iv, cand, starts_p, lens_p in (
            self._candidate_batches(dev, per_block)
        ):
            fn = _devseek_xz_fn(n_iv, cand, has_time, mesh=self.mesh)
            valid = seg.valid
            th = tl = win = qbox_dev  # unused placeholders when no time
            if has_time:
                th, tl = seg.xz_tk
                win = win_dev
                if seg.xz_tvalid is not None:
                    valid = seg.xz_tvalid
            buf = fn(
                seg.xz_limbs, seg.xz_isrect, valid,
                replicate(self.mesh, starts_p), replicate(self.mesh, lens_p),
                qbox_dev, rect_dev, th, tl, win,
            )
            _start_d2h(buf)
            pending.append((seg, starts, lens, tot, buf))
        if not pending:
            return None
        return _DeviceSeekXZScan(pending, node, geom)

    def _device_seek(self, table: IndexTable, plan, per_block, total: int):
        """Device-assisted seek (see _devseek_fn): host-planned candidate
        intervals shipped to the device, exact per-candidate test there,
        packed bitmap back. The accelerator path for SELECTIVE plans —
        O(candidates) device work where the full-scan mask is O(N).

        GEOMESA_DEVSEEK: auto (accelerator backends only, default) | 1 | 0.
        On the CPU jax backend "device" compute is host compute plus
        dispatch overhead, so auto declines (the native seek-scan wins).
        Declines when the plan is not one exact bbox(+interval) predicate
        or candidates exceed the bitmap budget — host paths take over."""
        if not self._devseek_enabled():
            return None
        if total == 0 or total > (1 << 22):
            return None
        shape = self._exact_predicate_shape(table, plan)
        if shape is None:
            return None
        box_np, win_np = self._shape_limbs(shape)
        has_time = win_np is not None
        dev = self.device_index(table)
        if not dev.segments or not all(
            seg.load_exact(table) for seg in dev.segments
        ):
            return None
        synced = set()
        for seg in dev.segments:
            synced.update(seg.block_ids)
        if any(id(b) not in synced for b, _s, _e, _f in per_block):
            return None  # a block the mirror hasn't synced would be DROPPED
        box_d = replicate(self.mesh, box_np)
        win_d = replicate(self.mesh, win_np) if has_time else None
        pending = []
        for seg, starts, lens, tot, n_iv, cand, starts_p, lens_p in (
            self._candidate_batches(dev, per_block)
        ):
            fn = _devseek_fn(has_time, n_iv, cand, mesh=self.mesh)
            valid = seg.tvalid if has_time else seg.valid
            th = seg.tk_hi if has_time else seg.xk_hi  # unused when no time
            tl = seg.tk_lo if has_time else seg.xk_lo
            buf = fn(
                seg.xk_hi, seg.xk_lo, seg.yk_hi, seg.yk_lo, th, tl, valid,
                replicate(self.mesh, starts_p), replicate(self.mesh, lens_p),
                box_d, win_d if has_time else box_d,
            )
            _start_d2h(buf)
            pending.append((seg, starts, lens, tot, buf))
        if not pending:
            # every candidate fell on rows the mirror hasn't synced — the
            # host path answers from the blocks directly
            return None
        return _DeviceSeekScan(pending)

    @staticmethod
    def _candidate_batches(dev, per_block):
        """Per-segment candidate-interval assembly shared by both devseek
        dispatchers: maps per-block seek intervals into segment row space
        (overlap-MERGED first — overlapping intervals would emit shared
        rows once per interval in the flat candidate space, where the
        host paths dedupe in expand_intervals), pads to pow2 buckets, and
        yields (seg, starts, lens, tot, n_iv, cand, starts_p, lens_p)."""
        for seg in dev.segments:
            offsets = {
                bid: off for bid, off in zip(seg.block_ids, seg.block_starts)
            }
            sts, lns = [], []
            for block, starts, ends, flags in per_block:
                off = offsets.get(id(block))
                if off is None:
                    continue
                starts, ends, _f = _merge_overlapping_intervals(
                    starts, ends, flags
                )
                keep = ends > starts
                if keep.any():
                    sts.append(starts[keep] + off)
                    lns.append((ends - starts)[keep])
            if not sts:
                continue
            starts = np.concatenate(sts).astype(np.int32)
            lens = np.concatenate(lns).astype(np.int32)
            tot = int(lens.sum())
            if tot == 0:
                continue
            n_iv = _pow2_at_least(len(starts), 64)
            cand = _pow2_at_least(tot, 1024)
            starts_p = np.zeros(n_iv, np.int32)
            starts_p[: len(starts)] = starts
            lens_p = np.zeros(n_iv, np.int32)
            lens_p[: len(lens)] = lens
            yield seg, starts, lens, tot, n_iv, cand, starts_p, lens_p

    @staticmethod
    def _shape_limbs(shape):
        """(box u32[8], window u32[4] | None) limb descriptors from a
        _box_window_shape tuple (shared by the full-scan exact path and
        the device-assisted seek)."""
        from geomesa_tpu.ops.zkernels import (
            f64_sort_keys,
            i64_sort_keys,
            split_u64_to_limbs,
        )

        xmin, ymin, xmax, ymax, t_lo, t_hi = shape
        bk = f64_sort_keys(np.asarray([xmin, xmax, ymin, ymax]))
        hi, lo = split_u64_to_limbs(bk)
        box_np = np.asarray(
            [hi[0], lo[0], hi[1], lo[1], hi[2], lo[2], hi[3], lo[3]],
            dtype=np.uint32,
        )
        win_np = None
        if t_lo is not None or t_hi is not None:
            lo_ms = np.iinfo(np.int64).min + 1 if t_lo is None else t_lo
            hi_ms = np.iinfo(np.int64).max if t_hi is None else t_hi
            tk = i64_sort_keys(np.asarray([lo_ms, hi_ms]))
            thi, tlo = split_u64_to_limbs(tk)
            win_np = np.asarray(
                [thi[0], tlo[0], thi[1], tlo[1]], dtype=np.uint32
            )
        return box_np, win_np

    def _native_seek_pred(self, table: IndexTable, plan):
        """(geom, dtg, box, t_lo, t_hi, use_covered) for the one-pass
        native seek-scan when the remaining per-row work reduces to one
        exact bbox(+interval) test and the C++ lib is available; None ->
        covered-split numpy path. ``use_covered`` marks full-filter mode,
        where range ``contained`` flags let the kernel skip whole runs.

        Two plan shapes qualify:
          * point z-index, FULL filter = bbox(+interval), no residual —
            the kernel evaluates the whole query;
          * value-exact attr/id plan (every range ``contained``: equality
            bounds in value space) whose residual secondary = bbox(+interval)
            — candidates already satisfy the primary, the kernel evaluates
            the residual (the z2-tiebreak attribute scan of the reference,
            AttributeIndex.scala:43-46, with the spatial recheck in C++).
        """
        shape = self._exact_predicate_shape(table, plan)
        # full-filter mode: range ``contained`` flags mean "satisfies the
        # whole predicate" and the kernel may skip those runs. In residual
        # mode they only mean "satisfies the primary" — every candidate
        # still takes the box test.
        use_covered = shape is not None
        if shape is None:
            shape = self._residual_shape(table, plan)
        if shape is None:
            return None
        from geomesa_tpu.native import load_seek

        if load_seek() is None:
            return None
        xmin, ymin, xmax, ymax, t_lo, t_hi = shape
        ft = table.ft
        dtg = ft.default_date.name if ft.default_date is not None else None
        if t_lo is not None or t_hi is not None:
            # stored null dates are 0 + a __null mask; the exact test would
            # wrongly admit them if the window covers the epoch — fall back
            # (has_nulls memoizes per immutable block: no per-query scans)
            if any(b.has_nulls(dtg) for b in table.blocks):
                return None
        return (
            "z",
            ft.default_geometry.name,
            dtg,
            (xmin, ymin, xmax, ymax),
            t_lo,
            t_hi,
            use_covered,
        )

    @staticmethod
    def _xz_pred_shape(table: IndexTable, plan, extra_match=None):
        """(geom, node, qenv, rect, t_lo, t_hi) when the FULL filter is
        exactly one spatial predicate on the default geometry of an
        xz2/xz3 plan — plus, for xz3, AND-combined temporal bounds on the
        default date — and the blocks carry envelope companion columns;
        None otherwise. t_lo/t_hi are inclusive ms or None.

        Only a SINGLE spatial node qualifies: an AND of two bboxes is NOT
        equivalent to one test against their envelope intersection for
        extent features (a geometry can straddle both boxes yet miss the
        intersection). ``extra_match`` may claim additional node shapes
        (the attr plane's predicates) — the plan may then carry a
        secondary (the attr residual the device decides instead)."""
        if table.index.name not in ("xz2", "xz3"):
            return None
        if extra_match is None and plan.secondary is not None:
            return None
        f = plan.full_filter
        if f is None:
            return None
        from geomesa_tpu.filter import ast as A

        ft = table.ft
        geom = ft.default_geometry.name
        spatial: List = []

        def match(node) -> bool:
            if isinstance(node, (A.BBox, A.Intersects)) and node.prop == geom:
                spatial.append(node)
                return True
            return extra_match(node) if extra_match is not None else False

        ok, t_lo, t_hi = TpuScanExecutor._and_walk_temporal(ft, f, match)
        if not ok or len(spatial) != 1:
            return None
        if table.index.name == "xz2" and (t_lo is not None or t_hi is not None):
            return None  # xz2 blocks carry no time column
        node = spatial[0]
        if isinstance(node, A.BBox):
            qenv, rect = node.envelope, True
        else:
            g = node.geometry
            qenv = g.envelope
            rect = hasattr(g, "is_rectangle") and g.is_rectangle()
        blocks = table.blocks
        if not blocks or any(
            geom + "__bxmin" not in b.columns for b in blocks
        ):
            return None  # legacy blocks without envelope companions
        return (geom, node, qenv, rect, t_lo, t_hi)

    def _xz_native_pred(self, table: IndexTable, plan):
        """("xz", geom, node, qenv, rect) for the C++ extent envelope
        kernel (xz2 only — see _xz_pred_shape); None when unavailable."""
        shape = self._xz_pred_shape(table, plan)
        if shape is None or table.index.name != "xz2":
            return None
        geom, node, qenv, rect, _t_lo, _t_hi = shape
        from geomesa_tpu.native import load_env_seek

        if load_env_seek() is None:
            return None
        return ("xz", geom, node, qenv, rect)

    def _residual_shape(self, table: IndexTable, plan):
        """Box(+window) shape of a value-exact plan's residual secondary.

        Requires every scan range to be ``contained`` (attr equality / id
        ranges, exact in value space: primary provably satisfied by every
        candidate) and full_filter = primary AND secondary, so testing only
        the secondary box(+window) yields the query's own result set."""
        name = table.index.name
        if not (name == "id" or name.startswith("attr")):
            return None
        if plan.primary is None or plan.secondary is None:
            return None
        if not plan.ranges or not all(r.contained for r in plan.ranges):
            return None
        return self._box_window_shape(table.ft, plan.secondary)

    def dispatch_candidates(self, table: IndexTable, plan: QueryPlan):
        """Start the device pre-filter WITHOUT blocking; None -> caller
        falls back to host ranges. Every segment's fused RLE buffer begins
        computing/transferring before the first blocking decode, so many
        dispatches pipeline over the device link and the round-trip latency
        is paid once per batch, not once per scan (the BatchScanner
        thread-pool analog, AccumuloQueryPlan.scala:113-140).

        Pure bbox(+interval) filters take the EXACT predicate path: the
        device evaluates the query's own f64/ms semantics (sort-key limb
        compares), so hits need no host post-filter at all — the full
        tserver-iterator role (Z3Iterator + KryoLazyFilterTransformIterator
        combined) on device."""
        seek = self._seek_scan(table, plan)
        if seek is not None:
            return seek
        return self._dispatch_nonseek(table, plan)

    def _scan_eligible(self, table: IndexTable, plan: QueryPlan) -> bool:
        """Shared gate for any full-scan device dispatch (single or
        batched): index family supported and bin-keyed tables have bins."""
        if not self.supports(table, plan):
            return False
        return not (
            table.index.name in ("z3", "xz3") and not plan.values.bins
        )

    _DESC_UNSET = object()  # sentinel: caller did not precompute desc

    def _dispatch_nonseek(
        self, table: IndexTable, plan: QueryPlan, desc=_DESC_UNSET
    ):
        """Device dispatch AFTER the seek-path choice declined (the
        full-scan tail of dispatch_candidates). ``desc`` lets dispatch_many
        pass an already-computed exact descriptor (avoids re-walking the
        filter AST on its fallback paths)."""
        if not self._scan_eligible(table, plan):
            return None
        if desc is TpuScanExecutor._DESC_UNSET:
            desc = self._exact_descriptor(table, plan)
        if desc is not None:
            dev = self.device_index(table)
            if all(seg.load_exact(table) for seg in dev.segments):
                box_np, win_np = desc
                box_dev = replicate(self.mesh, box_np)
                win_dev = None if win_np is None else replicate(self.mesh, win_np)
                pending = []
                for seg in dev.segments:
                    # per-segment cooperative check: a many-segment
                    # dispatch over a stalling link stops mid-stream
                    # instead of paying every segment's latency first
                    deadline.check("device.dispatch")
                    pending.append((seg, seg.dispatch_exact(box_dev, win_dev)))
                return _PendingScan(pending, exact=True)
        dev = self.device_index(table)
        boxes_dev, windows_dev = self._query_descriptor(table, plan)
        pending = []
        for seg in dev.segments:
            deadline.check("device.dispatch")
            pending.append((seg, seg.dispatch_hits(boxes_dev, windows_dev)))
        return _PendingScan(pending)

    def scan_candidates(self, table: IndexTable, plan: QueryPlan):
        """Device candidate scan; None -> caller falls back to host ranges.
        Returns the iterable _PendingScan (carrying .exact) directly.

        Graceful degradation: ANY dispatch-side failure (mirror upload,
        descriptor placement, kernel launch — a lost device, OOM, or an
        injected fault) degrades this query to the host scan path by
        returning None, with identical results (the host path evaluates
        the full filter). The table's mirror is marked unhealthy and
        evicted so the next query triggers a rebuild; fetch-side failures
        during resolution are handled the same way by the datastore's
        scan loop (store/datastore.py _scan_parts).

        While the device circuit breaker is OPEN, the dispatch is not
        even attempted: the query takes the host path immediately, with
        none of the dispatch/retry latency a dead link would charge."""
        if not self.breaker.allow():
            trace.event("breaker.short_circuit", breaker=self.breaker.name)
            return None
        try:
            scan = self.dispatch_candidates(table, plan)
        except Exception as e:  # noqa: BLE001 - device failure
            from geomesa_tpu.utils.audit import QueryTimeout

            if isinstance(e, QueryTimeout):
                # an expired budget is the QUERY's failure, not the
                # link's: no degrade, no breaker strike, no mirror
                # eviction — the timeout propagates crisply. A half-open
                # probe slot taken by allow() must not stay latched on a
                # verdict-free exit.
                self.breaker.cancel_probe()
                raise
            self.degrade(table, e)
            return None
        if scan is None or isinstance(scan, _HostSeekScan):
            # no device boundary was exercised — a half-open probe slot
            # taken by allow() must not stay latched on a host-only path
            self.breaker.cancel_probe()
        return scan

    def degrade(self, table: Optional[IndexTable], exc: BaseException) -> None:
        """Record a device->host degradation: evict the failed table's
        device mirror (None evicts every mirror — a batched dispatch
        failed mid-stream) so the next query that wants it rebuilds from
        the host table, and count the event in
        ``utils.audit.robustness_metrics`` (``degrade.*``)."""
        import sys

        from geomesa_tpu.utils.audit import robustness_metrics

        evicted = 0
        if table is None:
            evicted = len(self._cache)
            self._cache.clear()
        elif self._cache.pop(id(table), None) is not None:
            evicted = 1
        # every degradation is a breaker failure: enough of them inside
        # the rolling window opens the circuit and later queries skip
        # the (doomed) dispatch entirely
        self.breaker.record_failure()
        m = robustness_metrics()
        m.inc("degrade.device_to_host")
        if evicted:
            m.inc("degrade.mirror_rebuilds", evicted)
        # the degrade reason lands on the degraded query's OWN span tree,
        # joining the process-wide degrade.* counters to per-query blame
        trace.event(
            "degrade.device_to_host",
            reason=f"{type(exc).__name__}: {exc}",
            mirrors_evicted=evicted,
        )
        # reason-coded decision audit (utils/audit.decision): counter +
        # span event + a tally on the degraded query's plan fingerprint
        audit.decision(
            "degrade", "device_to_host",
            error=type(exc).__name__, mirrors_evicted=evicted,
        )
        sys.stderr.write(
            f"[executor] device scan failed ({type(exc).__name__}: {exc}); "
            "host path answers; mirror marked for rebuild\n"
        )

    def record_device_success(self) -> None:
        """A device scan resolved cleanly end-to-end (the datastore calls
        this after consuming a device scan without degradation). Closes a
        half-open circuit: the successful probe query just proved the
        link healthy AND rebuilt the mirror its dispatch needed."""
        self.breaker.record_success()

    # one batched execution answers at most this many queries; longer
    # streams chunk (bounds the [q, 2+2*rcap] transfer and compile shapes)
    BATCH_MAX = 64

    @staticmethod
    def _batch_enabled() -> bool:
        """GEOMESA_DEVBATCH: auto (accelerator backends) | 1 | 0."""
        import os

        env = os.environ.get("GEOMESA_DEVBATCH", "auto")
        if env == "0":
            return False
        return env == "1" or jax.default_backend() != "cpu"

    def dispatch_many(self, items: Sequence[Tuple[IndexTable, QueryPlan]]):
        """Dispatch a query stream; returns {id(plan): scan | None}.

        Plans whose full filter reduces to one exact box(+window) test on
        the same z-index table — after the cost-based seek choice declines
        them — fuse into ONE batched device execution per segment
        (DeviceSegment.dispatch_exact_batch), so the per-execution link
        cost of a remote accelerator amortizes across the whole stream.
        Everything else takes the same path dispatch_candidates would.
        """
        out: Dict[int, object] = {}
        if not self.breaker.allow():
            # open circuit: the WHOLE batch takes the host path (None
            # placeholders resolve to host scans in the datastore) with
            # zero dispatch cost — exactly what per-query short-circuit
            # does, amortized
            trace.event("breaker.short_circuit", breaker=self.breaker.name)
            return out
        try:
            return self._dispatch_many_batches(items, out)
        except Exception as e:
            from geomesa_tpu.utils.audit import QueryTimeout

            if isinstance(e, QueryTimeout):
                # budget death mid-batch is no verdict on the link: a
                # half-open probe slot must not stay latched (non-timeout
                # failures reach degrade() in the caller, which resolves
                # the probe via record_failure)
                self.breaker.cancel_probe()
            raise

    @staticmethod
    def _spmd_coalesce_enabled() -> bool:
        """geomesa.batch.spmd.enabled — the multi-chip stacked-mask kill
        switch: off routes every coalesced plan on an SPMD mesh to the
        dispatch_many batch paths (per-plan ``coalesce/spmd_disabled``
        declines), identical answers. Single-device meshes ignore it."""
        from geomesa_tpu.utils.config import BATCH_SPMD_ENABLED

        return bool(BATCH_SPMD_ENABLED.to_bool())

    @staticmethod
    def _attr_codes_loaded(dev, extra) -> bool:
        """Group-level attr-plane load check shared by the coalesced
        mask folds: ``extra`` is None (no attr plane) or (attr, kind)."""
        if extra is None:
            return True
        attr, akind = extra
        return all(
            seg.load_attr_codes(attr) for seg in dev.segments
        ) and (
            akind != "vocabmask"
            or all(seg.attr_vocab_ok(attr) for seg in dev.segments)
        )

    def dispatch_coalesced(self, items: Sequence[Tuple[IndexTable, QueryPlan]]):
        """Dispatch a COALESCED query group; returns {id(plan): scan | None}.

        The admission-point coalescer's seam (parallel/batch.py): plans
        whose full filter the device can evaluate exactly stack their
        compiled descriptors into ONE packed-mask sweep per segment — no
        per-query RLE/span framing, the whole point of coalescing. Four
        editions share the layout: plain box(+window) predicates, the
        rank-code attribute plane, extent envelopes (xz), and banded
        polygons — the latter two as dual hit/decided planes resolving
        through _XZBatchScan. On a single chip that is one [N, rows]
        sweep (dispatch_exact_mask_batch); on an SPMD mesh each chip
        sweeps its RESIDENT rows inside shard_map with no collective
        anywhere (_exact_shard_mask_batch_fn — rendezvous-safe by
        construction) and the host stitches shard planes by row offset.

        Plans that cannot ride a stacked sweep decline with a PER-PLAN
        reason code (``decision("coalesce", <reason>)`` — /debug/plans
        explains why a member missed the sweep):

        * ``seek_cheaper``     the cost chooser picked a selective host
                               seek — cheaper than ANY full sweep
        * ``kernel_ineligible``no mask edition matches the plan's shape
        * ``lone_member``      nothing shares its group (stacking gains
                               nothing; the single dispatch answers)
        * ``mirror_unloadable``a segment lacks the mirror/codes the
                               edition needs
        * ``spmd_disabled``    geomesa.batch.spmd.enabled=0 on a
                               multi-chip mesh

        Declined plans take exactly the dispatch_many path a query_many
        batch would. Same breaker envelope as dispatch_many: an open
        circuit answers the whole group from the host path."""
        out: Dict[int, object] = {}
        if not self.breaker.allow():
            trace.event("breaker.short_circuit", breaker=self.breaker.name)
            return out
        try:
            # (id(table), has_time, extra) -> (table, has_time, extra,
            # [(pid, plan, desc)]); extra = None | (attr, kind)
            mask_groups: Dict[tuple, tuple] = {}
            # ("xz"|"poly", id(table), has_time, extra) -> (kind, table,
            # has_time, extra, [(pid, plan, desc, geom, node)])
            dual_groups: Dict[tuple, tuple] = {}
            rest: List[Tuple[IndexTable, QueryPlan]] = []
            seen: set = set()
            # plans whose seek probe already ran (and declined) here:
            # the rest route must not pay the O(blocks x ranges) cost
            # probe a second time in _dispatch_many_batches
            seek_probed: set = set()
            reg = devstats_metrics()
            reg.set_gauge(
                "batch.coalesce.devices", int(self.mesh.devices.size)
            )
            spmd_ok = (
                self.mesh.devices.size == 1 or self._spmd_coalesce_enabled()
            )
            for table, plan in items:
                if id(plan) in seen:
                    continue
                seen.add(id(plan))
                deadline.check("device.dispatch")
                if not spmd_ok:
                    audit.decision(
                        "coalesce", "spmd_disabled",
                        devices=int(self.mesh.devices.size),
                    )
                    rest.append((table, plan))
                    continue
                seek = self._seek_scan(table, plan)
                seek_probed.add(id(plan))
                if seek is not None:
                    # the cost chooser picked a selective host seek:
                    # cheaper than ANY full sweep, coalesced or not
                    audit.decision(
                        "coalesce", "seek_cheaper", index=table.index.name
                    )
                    out[id(plan)] = seek
                    continue
                if self._scan_eligible(table, plan):
                    # NOT gated on _exact_device_enabled (unlike the
                    # single/RLE-batch exact paths): that gate exists
                    # because on the CPU backend the wider limb columns
                    # cost more than the host post-filter saves — but
                    # the stacked mask also deletes the per-query RLE/
                    # span extraction, which IS the dominant sweep cost
                    # there, so coalesced stacking wins on every backend
                    # (the attr/poly descs take gated=False for the same
                    # reason)
                    shape = self._exact_predicate_shape(table, plan)
                    desc = None if shape is None else self._shape_limbs(shape)
                    if desc is not None:
                        has_time = desc[1] is not None
                        key = (id(table), has_time, None)
                        if key not in mask_groups:
                            mask_groups[key] = (table, has_time, None, [])
                        mask_groups[key][3].append((id(plan), plan, desc))
                        continue
                    adesc = self._attr_batch_desc(table, plan, gated=False)
                    if adesc is not None:
                        attr, akind, d = adesc
                        has_time = d[1] is not None
                        key = (id(table), has_time, (attr, akind))
                        if key not in mask_groups:
                            mask_groups[key] = (
                                table, has_time, (attr, akind), [],
                            )
                        mask_groups[key][3].append((id(plan), plan, d))
                        continue
                    poly = self._poly_batch_desc(table, plan, gated=False)
                    if poly is not None:
                        edges, box_np, win_np, has_time, geom, node, ai = poly
                        extra = None if ai is None else (ai[0], ai[1])
                        desc = (
                            (edges, box_np, win_np)
                            if ai is None
                            else (edges, box_np, win_np, ai[2])
                        )
                        key = ("poly", id(table), has_time, extra)
                        if key not in dual_groups:
                            dual_groups[key] = (
                                "poly", table, has_time, extra, [],
                            )
                        dual_groups[key][4].append(
                            (id(plan), plan, desc, geom, node)
                        )
                        continue
                xz = self._xz_batch_desc(table, plan)
                if xz is not None:
                    qbox, win, has_time, geom, node, ai = xz
                    extra = None if ai is None else (ai[0], ai[1])
                    desc = (
                        (qbox, win) if ai is None else (qbox, win, ai[2])
                    )
                    key = ("xz", id(table), has_time, extra)
                    if key not in dual_groups:
                        dual_groups[key] = ("xz", table, has_time, extra, [])
                    dual_groups[key][4].append(
                        (id(plan), plan, desc, geom, node)
                    )
                    continue
                audit.decision(
                    "coalesce", "kernel_ineligible", index=table.index.name
                )
                rest.append((table, plan))
            stacked = 0

            def decline_group(table, lst, reason: str):
                audit.decision("coalesce", reason, n=len(lst))
                rest.extend((table, item[1]) for item in lst)

            for table, has_time, extra, lst in mask_groups.values():
                dev = self.device_index(table)
                if len(lst) < 2:
                    # a lone member gains nothing from the mask layout:
                    # the ordinary batch/single dispatch answers
                    decline_group(table, lst, "lone_member")
                    continue
                if not dev.segments or not all(
                    seg.load_exact(table) for seg in dev.segments
                ) or not self._attr_codes_loaded(dev, extra):
                    decline_group(table, lst, "mirror_unloadable")
                    continue
                attr = None if extra is None else extra[0]
                akind = "member" if extra is None else extra[1]
                for i in range(0, len(lst), self.BATCH_MAX):
                    chunk = lst[i : i + self.BATCH_MAX]
                    deadline.check("device.dispatch")
                    descs = [d for _pid, _p, d in chunk]
                    per_seg = [
                        seg.dispatch_exact_mask_batch(
                            descs, has_time, attr=attr, attr_kind=akind
                        )
                        for seg in dev.segments
                    ]
                    for qi, (pid, _plan, _d) in enumerate(chunk):
                        out[pid] = _PendingScan(
                            [
                                (seg, phs[qi])
                                for seg, phs in zip(dev.segments, per_seg)
                            ],
                            exact=True,
                        )
                    stacked += len(chunk)
            for kind, table, has_time, extra, lst in dual_groups.values():
                dev = self.device_index(table)
                if len(lst) < 2:
                    decline_group(table, lst, "lone_member")
                    continue
                if kind == "poly":
                    loaded = bool(dev.segments) and all(
                        seg.load_poly(table) for seg in dev.segments
                    )
                else:
                    loaded = bool(dev.segments) and all(
                        seg.load_exact_xz(table) for seg in dev.segments
                    ) and not (
                        has_time
                        and any(seg.xz_tk is None for seg in dev.segments)
                    )
                if not loaded or not self._attr_codes_loaded(dev, extra):
                    decline_group(table, lst, "mirror_unloadable")
                    continue
                attr = None if extra is None else extra[0]
                akind = "member" if extra is None else extra[1]
                for i in range(0, len(lst), self.BATCH_MAX):
                    chunk = lst[i : i + self.BATCH_MAX]
                    deadline.check("device.dispatch")
                    descs = [item[2] for item in chunk]
                    per_seg = [
                        seg.dispatch_dual_mask_batch(
                            kind, descs, has_time,
                            attr=attr, attr_kind=akind,
                        )
                        for seg in dev.segments
                    ]
                    for qi, item in enumerate(chunk):
                        pid, geom, node = item[0], item[3], item[4]
                        out[pid] = _XZBatchScan(
                            [
                                (seg, phs[qi])
                                for seg, phs in zip(dev.segments, per_seg)
                            ],
                            node,
                            geom,
                        )
                    stacked += len(chunk)
            # the stacked-vs-rest split feeds the /debug/device coalesce
            # block (the timeline/SLO layer's "coalescer reach" signal)
            if stacked:
                reg.inc("batch.coalesce.plans.stacked", stacked)
            if rest:
                reg.inc("batch.coalesce.plans.rest", len(rest))
                self._dispatch_many_batches(
                    rest, out, seek_declined=seek_probed
                )
            return out
        except Exception as e:
            from geomesa_tpu.utils.audit import QueryTimeout

            if isinstance(e, QueryTimeout):
                # budget death is no verdict on the link (see
                # dispatch_many): release a half-open probe slot
                self.breaker.cancel_probe()
            raise

    def _dispatch_many_batches(
        self, items: Sequence[Tuple[IndexTable, QueryPlan]],
        out: Dict[int, object], seek_declined=frozenset(),
    ):
        """dispatch_many's body, split out so the breaker wrapper above
        can resolve the half-open probe slot on every exit path.
        ``seek_declined`` carries plan ids whose seek cost probe already
        ran (and declined) in dispatch_coalesced — the rest route skips
        re-probing them."""
        seen: set = set()
        batchable: Dict[tuple, Tuple[IndexTable, bool, list]] = {}
        attr_batchable: Dict[tuple, Tuple[IndexTable, bool, str, list]] = {}
        xz_batchable: Dict[tuple, Tuple[IndexTable, bool, list]] = {}
        poly_batchable: Dict[tuple, Tuple[IndexTable, bool, list]] = {}
        for table, plan in items:
            if id(plan) in seen:
                continue
            seen.add(id(plan))
            deadline.check("device.dispatch")
            seek = (
                None if id(plan) in seek_declined
                else self._seek_scan(table, plan)
            )
            if seek is not None:
                out[id(plan)] = seek
                continue
            if not self._batch_enabled():
                out[id(plan)] = self._dispatch_nonseek(table, plan)
                continue
            desc = (
                self._exact_descriptor(table, plan)
                if self._scan_eligible(table, plan)
                else None
            )
            if desc is not None:
                has_time = desc[1] is not None
                key = (id(table), has_time)
                if key not in batchable:
                    batchable[key] = (table, has_time, [])
                batchable[key][2].append((id(plan), plan, desc))
                continue
            adesc = (
                self._attr_batch_desc(table, plan)
                if self._scan_eligible(table, plan)
                else None
            )
            if adesc is not None:
                attr, akind, d = adesc
                has_time = d[1] is not None
                key = (id(table), has_time, attr, akind)
                if key not in attr_batchable:
                    attr_batchable[key] = (table, has_time, attr, akind, [])
                attr_batchable[key][4].append((id(plan), plan, d))
                continue
            poly = self._poly_batch_desc(table, plan)
            if poly is not None:
                edges, box_np, win_np, has_time, geom, node, ainfo = poly
                if ainfo is None:
                    key = (id(table), has_time)
                    if key not in poly_batchable:
                        poly_batchable[key] = (table, has_time, None, [])
                    poly_batchable[key][3].append(
                        (id(plan), plan, edges, box_np, win_np, geom, node)
                    )
                else:
                    attr, akind, payload = ainfo
                    key = (id(table), has_time, attr, akind)
                    if key not in poly_batchable:
                        poly_batchable[key] = (
                            table, has_time, (attr, akind), []
                        )
                    poly_batchable[key][3].append(
                        (id(plan), plan, edges, box_np, win_np, payload,
                         geom, node)
                    )
                continue
            xz = self._xz_batch_desc(table, plan)
            if xz is not None:
                qbox, win, has_time, geom, node, ainfo = xz
                if ainfo is None:
                    key = (id(table), has_time)
                    if key not in xz_batchable:
                        xz_batchable[key] = (table, has_time, None, [])
                    xz_batchable[key][3].append(
                        (id(plan), plan, qbox, win, geom, node)
                    )
                else:
                    # attr edition: its own batch group (different
                    # kernel); the payload rides in the desc slice
                    attr, akind, payload = ainfo
                    key = (id(table), has_time, attr, akind)
                    if key not in xz_batchable:
                        xz_batchable[key] = (
                            table, has_time, (attr, akind), []
                        )
                    xz_batchable[key][3].append(
                        (id(plan), plan, qbox, win, payload, geom, node)
                    )
                continue
            out[id(plan)] = self._dispatch_nonseek(table, plan, desc=None)
        for table, has_time, lst in batchable.values():
            dev = self.device_index(table)
            if not dev.segments or not all(
                seg.load_exact(table) for seg in dev.segments
            ):
                for pid, plan, d in lst:
                    out[pid] = self._dispatch_nonseek(table, plan, desc=d)
                continue
            # seed once from the WHOLE stream's plans (not per chunk): a
            # later chunk's wider query must not overflow a window seeded
            # from an earlier, narrower chunk
            self._seed_spans(dev, [p for _pid, p, _d in lst])
            for i in range(0, len(lst), self.BATCH_MAX):
                chunk = lst[i : i + self.BATCH_MAX]
                if len(chunk) == 1:
                    # a lone query pads to the pow2 floor in the batch fn
                    # (x4 scan work) — the cached single-query dispatch is
                    # strictly better
                    pid, plan, d = chunk[0]
                    out[pid] = self._dispatch_nonseek(table, plan, desc=d)
                    continue
                descs = [d for _pid, _p, d in chunk]
                per_seg = [
                    seg.dispatch_exact_batch(descs, has_time)
                    for seg in dev.segments
                ]
                for qi, (pid, _plan, _d) in enumerate(chunk):
                    out[pid] = _PendingScan(
                        [
                            (seg, phs[qi])
                            for seg, phs in zip(dev.segments, per_seg)
                        ],
                        exact=True,
                    )
        for table, has_time, attr, akind, lst in attr_batchable.values():
            dev = self.device_index(table)
            ok = (
                bool(dev.segments)
                and all(seg.load_exact(table) for seg in dev.segments)
                and all(seg.load_attr_codes(attr) for seg in dev.segments)
                and (
                    akind != "vocabmask"
                    or all(seg.attr_vocab_ok(attr) for seg in dev.segments)
                )
            )
            if not ok:
                # no dictionary codes in some segment (or a vocab too
                # large for the mask edition): the conservative mask +
                # host post-filter answers (the attribute predicate runs
                # host-side, same results)
                for pid, plan, _d in lst:
                    out[pid] = self._dispatch_nonseek(table, plan, desc=None)
                continue

            def single_attr(pid, d):
                box_np, win_np, value = d
                box_dev = replicate(self.mesh, box_np)
                win_dev = (
                    None if win_np is None else replicate(self.mesh, win_np)
                )
                out[pid] = _PendingScan(
                    [
                        (seg, seg.dispatch_exact_attr(
                            box_dev, win_dev, attr, value, kind=akind))
                        for seg in dev.segments
                    ],
                    exact=True,
                )

            self._seed_spans(dev, [p for _pid, p, _d in lst])
            for i in range(0, len(lst), self.BATCH_MAX):
                chunk = lst[i : i + self.BATCH_MAX]
                if len(chunk) == 1:
                    # lone query keeps device exactness via the cached
                    # single-query attr dispatch (the batch fn would pad
                    # to the pow2 floor: x4 scan work)
                    single_attr(chunk[0][0], chunk[0][2])
                    continue
                descs = [d for _pid, _p, d in chunk]
                per_seg = [
                    seg.dispatch_exact_batch(
                        descs, has_time, attr=attr, attr_kind=akind
                    )
                    for seg in dev.segments
                ]
                for qi, (pid, _plan, _d) in enumerate(chunk):
                    out[pid] = _PendingScan(
                        [
                            (seg, phs[qi])
                            for seg, phs in zip(dev.segments, per_seg)
                        ],
                        exact=True,
                    )

        def xz_loaded(dev, table, has_time, extra):
            ok = all(
                seg.load_exact_xz(table) for seg in dev.segments
            ) and not (
                has_time and any(seg.xz_tk is None for seg in dev.segments)
            )
            if ok and extra is not None:  # attr edition: codes too
                ok = all(
                    seg.load_attr_codes(extra[0]) for seg in dev.segments
                ) and (
                    extra[1] != "vocabmask"
                    or all(
                        seg.attr_vocab_ok(extra[0]) for seg in dev.segments
                    )
                )
            return ok

        self._drain_dual_batches(
            out, xz_batchable, xz_loaded,
            lambda seg, descs, ht, extra: seg.dispatch_exact_xz_batch(
                descs, ht,
                attr=None if extra is None else extra[0],
                attr_kind="member" if extra is None else extra[1],
            ),
        )
        def poly_loaded(dev, table, _ht, extra):
            ok = all(seg.load_poly(table) for seg in dev.segments)
            if ok and extra is not None:  # attr edition: codes too
                ok = all(
                    seg.load_attr_codes(extra[0]) for seg in dev.segments
                ) and (
                    extra[1] != "vocabmask"
                    or all(
                        seg.attr_vocab_ok(extra[0]) for seg in dev.segments
                    )
                )
            return ok

        self._drain_dual_batches(
            out, poly_batchable, poly_loaded,
            lambda seg, descs, ht, extra: seg.dispatch_poly_batch(
                descs, ht,
                attr=None if extra is None else extra[0],
                attr_kind="member" if extra is None else extra[1],
            ),
        )
        if not any(
            v is not None and not isinstance(v, _HostSeekScan)
            for v in out.values()
        ):
            # every plan resolved host-side: a half-open probe slot taken
            # by the batch's allow() must not stay latched
            self.breaker.cancel_probe()
        return out

    @staticmethod
    def _seed_spans(dev, plans) -> None:
        """Plan-derived span seeding for unlearned segments: each plan's
        decomposed z-ranges searchsort into the sorted blocks (the same
        tiny pass the host-seek cost probe pays), giving
        a conservative candidate row-interval cover per segment; the
        widest planned span across the stream seeds the segment's bitmap
        window so the first device stream never transfers the full
        n_padded/8-byte plane (VERDICT r3 #2 / ADVICE: unlearned
        first-stream cost)."""
        for seg in dev.segments:
            if seg._span_cap != 0 or not seg.n:
                continue
            offsets = np.cumsum([0] + [b.n for b in seg.blocks[:-1]])
            widest = 0
            ok = True
            for plan in plans:
                if not getattr(plan, "ranges", None):
                    ok = False  # no range cover -> cannot bound the span
                    break
                lo = hi = None
                for off, b in zip(offsets, seg.blocks):
                    starts, ends, _flags = b.scan_intervals(plan.ranges)
                    live = ends > starts  # drop degenerate empty intervals
                    if live.any():
                        blo = int(off + starts[live].min())
                        bhi = int(off + ends[live].max() - 1)
                        lo = blo if lo is None else min(lo, blo)
                        hi = bhi if hi is None else max(hi, bhi)
                if lo is not None:
                    widest = max(widest, hi - lo + 1)
            if ok and widest:
                # +8: the device window start aligns down to a byte
                # boundary, so an exactly-pow2 candidate span could
                # otherwise overflow by the alignment slack
                seg.seed_span(widest + 8)

    def _drain_dual_batches(self, out, groups, loaded, dispatch) -> None:
        """Shared drain for the dual-plane (hit/decided) batch groups
        (extent envelopes — plain and attr editions — and banded
        polygons): chunked batched dispatch per segment resolving
        through _XZBatchScan. Group values are ``(table, has_time,
        extra, items)`` where ``extra`` threads group-level context
        ((attr, kind) for the attr edition, None otherwise) into
        ``loaded`` and ``dispatch``; items are ``(plan_id, plan,
        *desc_parts, geom, node)``. Lone queries route to the
        single-query path BEFORE any device column upload; these plans
        provably have no exact point descriptor (that's why they took a
        dual-plane branch), so nonseek gets desc=None."""
        for table, has_time, extra, lst in groups.values():
            dev = self.device_index(table)
            ok = (
                len(lst) > 1
                and bool(dev.segments)
                and loaded(dev, table, has_time, extra)
            )
            if not ok:
                for pid, plan, *_rest in lst:
                    out[pid] = self._dispatch_nonseek(table, plan, desc=None)
                continue
            for i in range(0, len(lst), self.BATCH_MAX):
                chunk = lst[i : i + self.BATCH_MAX]
                if len(chunk) == 1:
                    pid, plan, *_rest = chunk[0]
                    out[pid] = self._dispatch_nonseek(table, plan, desc=None)
                    continue
                descs = [tuple(item[2:-2]) for item in chunk]
                per_seg = [
                    dispatch(seg, descs, has_time, extra)
                    for seg in dev.segments
                ]
                for qi, item in enumerate(chunk):
                    pid, geom, node = item[0], item[-2], item[-1]
                    out[pid] = _XZBatchScan(
                        [
                            (seg, phs[qi])
                            for seg, phs in zip(dev.segments, per_seg)
                        ],
                        node,
                        geom,
                    )

    def _poly_batch_desc(self, table: IndexTable, plan: QueryPlan,
                         gated: bool = True):
        """(edges f32[E,4], box u32[8], win u32[4]|None, has_time, geom,
        node, attr_info) when this point z-index plan's FULL filter is
        one non-rect INTERSECTS(polygon) on the default geometry (+ z3
        temporal bounds), optionally AND attr predicates on ONE eligible
        attribute (attr_info per the _attr_pred_collector contract; the
        rank-code test ANDs into the hit plane so the band ring only
        carries attr-passing rows) — the banded-raycast batch
        descriptor; None otherwise. Same GEOMESA_EXACT_DEVICE gate as
        the box path (the kernel rides the exact limb columns);
        ``gated=False`` skips it — the coalescer's mask fold wins on
        every backend (see _attr_batch_desc)."""
        if gated and not self._exact_device_enabled():
            return None
        if table.index.name not in ("z2", "z3"):
            return None
        ft = table.ft
        if ft.default_geometry is None or not ft.is_points:
            return None
        f = plan.full_filter
        if f is None:
            return None
        from geomesa_tpu.filter import ast as A
        from geomesa_tpu.geom.base import MultiPolygon, Polygon

        geom = ft.default_geometry.name
        spatial: List = []

        def match(node) -> bool:
            if isinstance(node, A.Intersects) and node.prop == geom:
                spatial.append(node)
                return True
            return False

        match_attr, finalize = self._attr_pred_collector(ft)
        ok, t_lo, t_hi = self._and_walk_temporal(
            ft, f, lambda n: match(n) or match_attr(n)
        )
        attr_info = finalize()
        if not ok or len(spatial) != 1:
            return None
        if attr_info is None and plan.secondary is not None:
            return None  # residual present but not a claimable attr set
        has_time = t_lo is not None or t_hi is not None
        if has_time and table.index.name != "z3":
            return None
        node = spatial[0]
        g = node.geometry
        if hasattr(g, "is_rectangle") and g.is_rectangle():
            return None  # the box path handles rects exactly
        if isinstance(g, Polygon):
            polys = [g]
        elif isinstance(g, MultiPolygon):
            polys = list(g.geoms)
            # crossing parity is only valid for disjoint members; envelope
            # overlap (conservative) sends such queries down the old path
            envs = [p.envelope for p in polys]
            for i in range(len(envs)):
                for j in range(i + 1, len(envs)):
                    if envs[i].intersects(envs[j]):
                        return None
        else:
            return None
        rings = []
        for p in polys:
            rings.append(p.shell)
            rings.extend(p.holes)
        segs = []
        for r in rings:
            r = np.asarray(r, np.float64)
            if len(r) < 3:
                return None
            if not np.array_equal(r[0], r[-1]):
                r = np.vstack([r, r[:1]])
            segs.append(
                np.stack([r[:-1, 0], r[:-1, 1], r[1:, 0], r[1:, 1]], axis=1)
            )
        edges = np.concatenate(segs).astype(np.float32)
        e = g.envelope
        box_np, win_np = self._shape_limbs(
            (e.xmin, e.ymin, e.xmax, e.ymax, t_lo, t_hi)
        )
        return edges, box_np, win_np, has_time, geom, node, attr_info

    def _xz_batch_desc(self, table: IndexTable, plan: QueryPlan):
        """(qbox u32[12], win u32[4], has_time, geom, node, attr_info)
        when this extent plan's full filter reduces to one spatial
        predicate (+ xz3 time bounds), optionally AND attr predicates on
        ONE eligible attribute — the batched extent scan's descriptor;
        None otherwise. attr_info is None (plain) or (attr, kind,
        payload) per the _attr_pred_collector contract: the rank-code
        test ANDs into the device hit plane, so decided rows are final
        for spatial-AND-attr and the ring needs only the host geometry
        test. qbox = envelope + placeholder-zero sort-key limbs + a rect
        flag (see _xz_exact_mask_body)."""
        if table.index.name not in ("xz2", "xz3"):
            return None
        shape = self._xz_pred_shape(table, plan)
        attr_info = None
        if shape is None:
            match_attr, finalize = self._attr_pred_collector(table.ft)
            shape = self._xz_pred_shape(table, plan, extra_match=match_attr)
            attr_info = finalize()
            if shape is None or attr_info is None:
                return None
        geom, node, qenv, rect, t_lo, t_hi = shape
        qbox, win, has_time = _xz_query_limbs(qenv, rect, t_lo, t_hi)
        return qbox, win, has_time, geom, node, attr_info

    @staticmethod
    def _box_window_shape(ft, f):
        """(xmin, ymin, xmax, ymax, t_lo, t_hi) raw f64 / inclusive-ms
        bounds when filter ``f`` is exactly one AND-combination of
        inclusive-envelope spatial tests on the default point geometry plus
        interval tests on the default date — i.e. its semantics reduce to
        one box(+window) test. None otherwise. t_lo/t_hi are None when the
        filter has no temporal part."""
        if f is None or ft.default_geometry is None or not ft.is_points:
            return None
        return TpuScanExecutor._walk_box_window(ft, f)

    @staticmethod
    def _exact_predicate_shape(table: IndexTable, plan: QueryPlan):
        """Box(+window) shape of the FULL filter for point z-index plans
        with no residual (see _box_window_shape)."""
        if table.index.name not in ("z2", "z3") or plan.secondary is not None:
            return None
        shape = TpuScanExecutor._box_window_shape(table.ft, plan.full_filter)
        if shape is None:
            return None
        t_lo, t_hi = shape[4], shape[5]
        if (t_lo is not None or t_hi is not None) and table.index.name != "z3":
            return None  # temporal test needs the time column (z3 tables)
        return shape

    @staticmethod
    def _and_walk_temporal(ft, f, match_spatial):
        """Shared AND-only filter walker: temporal predicates on the
        default date clamp the (inclusive-ms) window with the exclusive-
        bound rules (DURING/AFTER/BEFORE are exclusive, TEQUALS is a
        point); every other node must be accepted by ``match_spatial``.
        Returns (ok, t_lo, t_hi) — THE single home of the bound rules for
        the box, xz, and polygon device descriptors."""
        from geomesa_tpu.filter import ast as A

        dtg = ft.default_date.name if ft.default_date is not None else None
        t_lo, t_hi = None, None  # inclusive ms, None = open

        def clamp_lo(v):
            nonlocal t_lo
            t_lo = v if t_lo is None else max(t_lo, v)

        def clamp_hi(v):
            nonlocal t_hi
            t_hi = v if t_hi is None else min(t_hi, v)

        def walk(node) -> bool:
            if isinstance(node, A.And):
                return all(walk(c) for c in node.children())
            if dtg is not None and isinstance(node, A.During) and node.prop == dtg:
                clamp_lo(node.lo_ms + 1)
                clamp_hi(node.hi_ms - 1)
                return True
            if dtg is not None and isinstance(node, A.After) and node.prop == dtg:
                clamp_lo(node.t_ms + 1)
                return True
            if dtg is not None and isinstance(node, A.Before) and node.prop == dtg:
                clamp_hi(node.t_ms - 1)
                return True
            if dtg is not None and isinstance(node, A.TEquals) and node.prop == dtg:
                clamp_lo(node.t_ms)
                clamp_hi(node.t_ms)
                return True
            return match_spatial(node)

        return walk(f), t_lo, t_hi

    @staticmethod
    def _walk_boxes(ft, f, extra_match=None):
        """AND-only walk collecting bbox / rect-INTERSECTS tests on the
        default geometry plus temporal clamps — THE single home of the
        box-shape rules for the plain exact AND attr device planes.
        ``extra_match`` may claim additional node shapes. Returns
        ((xmin, ymin, xmax, ymax), t_lo, t_hi) or None."""
        if f is None:
            return None
        from geomesa_tpu.filter import ast as A

        geom = ft.default_geometry.name
        boxes: List = []

        def match(node) -> bool:
            if isinstance(node, A.BBox) and node.prop == geom:
                boxes.append(node.envelope)
                return True
            if isinstance(node, A.Intersects) and node.prop == geom:
                g = node.geometry
                if hasattr(g, "is_rectangle") and g.is_rectangle():
                    boxes.append(g.envelope)
                    return True
            return extra_match(node) if extra_match is not None else False

        ok, t_lo, t_hi = TpuScanExecutor._and_walk_temporal(ft, f, match)
        if not ok or not boxes:
            return None
        env = boxes[0]
        xmin, ymin, xmax, ymax = env.xmin, env.ymin, env.xmax, env.ymax
        for e in boxes[1:]:  # AND of boxes = envelope intersection
            xmin, ymin = max(xmin, e.xmin), max(ymin, e.ymin)
            xmax, ymax = min(xmax, e.xmax), min(ymax, e.ymax)
        return (xmin, ymin, xmax, ymax), t_lo, t_hi

    @staticmethod
    def _walk_box_window(ft, f):
        got = TpuScanExecutor._walk_boxes(ft, f)
        if got is None:
            return None
        (xmin, ymin, xmax, ymax), t_lo, t_hi = got
        return xmin, ymin, xmax, ymax, t_lo, t_hi

    @staticmethod
    def _exact_device_enabled() -> bool:
        """GEOMESA_EXACT_DEVICE gate, shared by every exact-descriptor
        builder: auto means accelerator backends only — on the CPU
        backend "device" compute IS host compute and the wider limb
        columns cost more than the post-filter saves; on real
        accelerators the exact mask is memory-bound free and eliminates
        the host post-filter entirely."""
        import os

        env = os.environ.get("GEOMESA_EXACT_DEVICE", "auto")
        if env == "0":
            return False
        return env == "1" or jax.default_backend() != "cpu"

    def _exact_descriptor(self, table: IndexTable, plan: QueryPlan):
        """(box key limbs u32[8], window key limbs u32[4] | None) when the
        device can evaluate the query's own semantics (see
        _exact_predicate_shape). None otherwise (conservative mask + host
        post-filter)."""
        if not self._exact_device_enabled():
            return None
        shape = self._exact_predicate_shape(table, plan)
        if shape is None:
            return None
        return self._shape_limbs(shape)

    def _attr_batch_desc(self, table: IndexTable, plan: QueryPlan,
                         gated: bool = True):
        """(attr_name, kind, (box_limbs, win_limbs|None, payload)) when
        the plan's FULL filter is one box(+window) AND attribute
        predicates on exactly ONE eligible attribute that the unified
        code space can decide — so the device answers everything,
        including the secondary attribute predicate (the join attribute
        strategy evaluated at the data, AttributeIndex.scala:42,392).
        None otherwise.

        kind "member": ``attr = 'x'`` or ``attr IN (...)`` with at most
        8 distinct values — payload is the literal tuple. kind "range":
        any AND of order predicates (<, <=, >, >=, =, BETWEEN; DURING/
        BEFORE/AFTER on secondary date attributes; single-trailing-%
        LIKE prefixes; IS [NOT] NULL) — payload is the (op,
        coerced_literal) tuple, intersected per segment in code space
        (code order == value order; null/NaN rank -1, which IS NULL's
        [-1, -1] interval selects). Eligible attribute types: String
        (non-json), Integer, Long, Float, Double, Date (the default dtg
        stays with the window plane).

        ``gated=False`` skips the GEOMESA_EXACT_DEVICE backend gate —
        the coalescer's posture: that gate exists because the wider limb
        columns lose to the host post-filter on the CPU backend, but the
        stacked MASK layout also deletes the per-query RLE/span
        extraction (the dominant cost there), so coalesced stacking
        wins on every backend (same rationale as the plain shape in
        dispatch_coalesced)."""
        if gated and not self._exact_device_enabled():
            return None
        if table.index.name not in ("z2", "z3"):
            return None
        ft = table.ft
        if ft.default_geometry is None or not ft.is_points:
            return None
        match_attr, finalize = self._attr_pred_collector(ft)
        got = self._walk_boxes(ft, plan.full_filter, extra_match=match_attr)
        found = finalize()
        if got is None or found is None:
            return None
        attr, kind, payload = found
        (xmin, ymin, xmax, ymax), t_lo, t_hi = got
        if (t_lo is not None or t_hi is not None) and table.index.name != "z3":
            return None
        limbs = self._shape_limbs((xmin, ymin, xmax, ymax, t_lo, t_hi))
        return attr, kind, (limbs[0], limbs[1], payload)

    @staticmethod
    def _attr_pred_collector(ft):
        """(match, finalize) pair — THE shared attr-predicate recognizer
        for the device attr planes (point boxes AND extent envelopes).
        ``match(node)`` claims eligible predicates during an AND-walk;
        ``finalize()`` returns None or (attr, kind, payload) per the
        _attr_batch_desc contract (kind "member" | "range")."""
        from geomesa_tpu.filter import ast as A
        from geomesa_tpu.filter.evaluate import _coerce
        from geomesa_tpu.schema.featuretype import AttributeType

        dtg = ft.default_date.name if ft.default_date is not None else None
        OK_TYPES = (
            AttributeType.STRING, AttributeType.INT, AttributeType.LONG,
            AttributeType.FLOAT, AttributeType.DOUBLE, AttributeType.DATE,
        )
        inlists: List = []  # (prop, values_tuple)
        ranges: List = []  # (prop, op, coerced_literal); includes '='
        excluded: List = []  # (prop, coerced_literal) from '<>' chains
        likes: List = []  # (prop, pattern, ci) needing the vocab mask

        def eligible(prop) -> bool:
            return (
                not prop.startswith("$.")
                and prop != dtg
                and ft.has(prop)
                and ft.attr(prop).type in OK_TYPES
                and not ft.attr(prop).json
            )

        def usable(lit) -> bool:
            # NaN literals break the code-space mapping (NaN sorts past
            # the end but compares false everywhere); None never matches
            return lit is not None and not (
                isinstance(lit, float) and lit != lit
            )

        def coerced(prop, lit):
            v = _coerce(ft, prop, lit)
            return v if usable(v) else None

        def match_attr(node) -> bool:
            if isinstance(node, A.Cmp) and node.op in (
                "=", "<", "<=", ">", ">="
            ) and eligible(node.prop):
                lit = coerced(node.prop, node.literal)
                if lit is None:
                    return False
                ranges.append((node.prop, node.op, lit))
                return True
            if (
                isinstance(node, A.Cmp) and node.op == "<>"
                and eligible(node.prop)
            ):
                # complement membership: `a <> x [AND a <> y ...]` rides
                # the notmember kernel edition (null-excluding, like the
                # oracle's null-is-false comparison semantics)
                lit = coerced(node.prop, node.literal)
                if lit is None:
                    return False
                excluded.append((node.prop, lit))
                return True
            if isinstance(node, A.Between) and eligible(node.prop):
                lo = coerced(node.prop, node.lo)
                hi = coerced(node.prop, node.hi)
                if lo is None or hi is None:
                    return False
                ranges.append((node.prop, "between", (lo, hi)))
                return True
            if isinstance(node, A.InList) and eligible(node.prop):
                # dedup BEFORE the bucket cap (duplicate literals must
                # not push a small distinct set off the device plane)
                raw = [coerced(node.prop, v) for v in node.values]
                if any(v is None for v in raw):
                    return False
                vals = tuple(dict.fromkeys(raw))
                if 0 < len(vals) <= _ATTR_K_CAP:
                    inlists.append((node.prop, vals))
                    return True
                return False
            if isinstance(node, A.IsNull) and eligible(node.prop):
                ranges.append(
                    (node.prop, "notnull" if node.negate else "isnull", None)
                )
                return True
            if (
                isinstance(node, A.Like)
                and eligible(node.prop)
                and ft.attr(node.prop).type == AttributeType.STRING
                and not node.case_insensitive
                and "_" not in node.pattern
                and (
                    "%" not in node.pattern
                    or (
                        node.pattern.count("%") == 1
                        and node.pattern.endswith("%")
                    )
                )
            ):
                # prefix LIKE is a code range on the sorted value space;
                # a wildcard-free pattern is equality (oracle: ^pat$)
                if node.pattern.endswith("%"):
                    ranges.append((node.prop, "prefix", node.pattern[:-1]))
                else:
                    ranges.append((node.prop, "=", node.pattern))
                return True
            if (
                isinstance(node, A.Like)
                and eligible(node.prop)
                and ft.attr(node.prop).type == AttributeType.STRING
            ):
                # everything the prefix range can't take — ILIKE, '_',
                # interior '%' — rides the vocab-mask edition (the
                # oracle's regex evaluated over the segment vocab)
                likes.append((node.prop, node.pattern, node.case_insensitive))
                return True
            if (
                isinstance(node, (A.During, A.Before, A.After))
                and eligible(node.prop)
                and ft.attr(node.prop).type == AttributeType.DATE
            ):
                # secondary date attribute (the default dtg was already
                # claimed by _and_walk_temporal's window clamps)
                if isinstance(node, A.During):
                    ranges.append(
                        (node.prop, "during", (node.lo_ms, node.hi_ms))
                    )
                elif isinstance(node, A.Before):
                    ranges.append((node.prop, "before", node.t_ms))
                else:
                    ranges.append((node.prop, "after", node.t_ms))
                return True
            return False

        def finalize():
            if not (inlists or ranges or excluded or likes):
                return None
            props = (
                {p for p, *_ in inlists}
                | {p for p, *_ in ranges}
                | {p for p, *_ in excluded}
                | {p for p, *_ in likes}
            )
            if len(props) != 1:
                return None  # one device codes column per batch
            if likes:
                if inlists or ranges or excluded or len(likes) > 1:
                    return None  # pattern mixed with others: host path
                prop, pattern, ci = likes[0]
                return prop, "vocabmask", (pattern, ci)
            if excluded:
                if inlists or ranges:
                    return None  # complement mixed with others: host path
                vals = tuple(dict.fromkeys(lit for _p, lit in excluded))
                if len(vals) > _ATTR_K_CAP:
                    return None
                return props.pop(), "notmember", vals
            if inlists and (ranges or len(inlists) > 1):
                return None  # IN combined with other preds: host path
            attr = props.pop()
            if inlists:
                return attr, "member", inlists[0][1]
            if len(ranges) == 1 and ranges[0][1] == "=":
                # a lone equality rides the membership edition (shares
                # the K=1 kernel with equality batches already in flight)
                return attr, "member", (ranges[0][2],)
            # AND of order predicates (any mix, incl. repeated '='):
            # intersected per segment in code space
            return attr, "range", tuple((op, lit) for _p, op, lit in ranges)

        return match_attr, finalize

    def _query_descriptor(self, table: IndexTable, plan: QueryPlan):
        """(boxes, windows) device-replicated arrays for this plan."""
        windows = None
        if table.index.name in ("xz2", "xz3"):
            # raw-domain overlap test: query boxes widened outward one f32
            # ulp so the cast can never exclude a true overlap
            boxes = pad_boxes(
                [
                    (
                        np.nextafter(np.float32(env.xmin), np.float32(-np.inf)),
                        np.nextafter(np.float32(env.ymin), np.float32(-np.inf)),
                        np.nextafter(np.float32(env.xmax), np.float32(np.inf)),
                        np.nextafter(np.float32(env.ymax), np.float32(np.inf)),
                    )
                    for env in plan.values.spatial_envelopes
                ],
                dtype=np.float32,
            )
            if table.index.name == "xz3":
                # unit-resolution offsets; widen one unit each side so the
                # floor never drops a boundary candidate
                windows = pad_windows(
                    [
                        (b, max(0, lo - 1), hi + 1)
                        for b, (lo, hi) in sorted(plan.values.bins.items())
                    ]
                )
        else:
            sfc = table.index.sfc(table.ft)
            boxes = pad_boxes(
                [
                    (
                        int(sfc.lon.normalize(env.xmin)[()]),
                        int(sfc.lat.normalize(env.ymin)[()]),
                        int(sfc.lon.normalize(env.xmax)[()]),
                        int(sfc.lat.normalize(env.ymax)[()]),
                    )
                    for env in plan.values.spatial_envelopes
                ]
            )
            if table.index.name == "z3":
                # plan.values.bins came from SECOND-rounded intervals (the
                # reference's handleExclusiveBounds narrows inward,
                # FilterHelper.scala:267-287) — fine for ranges, which the
                # BFS loosens back into supersets, but a DIRECT window mask
                # would drop true matches inside the rounded-off second.
                # Rebuild per-bin windows from the UNROUNDED intervals
                # (times_by_bin applies the exact ±1ms exclusive shift);
                # floor-normalization keeps both ends conservative.
                bins = plan.values.bins
                if plan.full_filter is not None and table.ft.default_date is not None:
                    from geomesa_tpu.filter.extract import extract_intervals
                    from geomesa_tpu.index.keyspace import times_by_bin

                    iv = extract_intervals(
                        plan.full_filter, table.ft.default_date.name
                    )
                    if iv is not None and iv.values and not iv.disjoint:
                        bins = times_by_bin(iv, table.ft.z3_interval)
                windows = pad_windows(
                    [
                        (
                            b,
                            int(sfc.time.normalize(lo)[()]),
                            int(sfc.time.normalize(hi)[()]),
                        )
                        for b, (lo, hi) in sorted(bins.items())
                    ]
                )
        boxes_dev = replicate(self.mesh, boxes)
        windows_dev = replicate(self.mesh, windows) if windows is not None else None
        return boxes_dev, windows_dev

    def post_filter(self, ft, plan: QueryPlan, columns) -> np.ndarray:
        from geomesa_tpu.filter.evaluate import evaluate

        return evaluate(plan.post_filter, ft, columns)

    _BIN_MS = {TimePeriod.DAY: 86400000, TimePeriod.WEEK: 604800000}

    def _ms_windows(self, ft, plan: QueryPlan):
        """Per-bin inclusive ms windows, exact vs the query's ms semantics.

        Re-extracts intervals from the full filter WITHOUT exclusive-bound
        rounding (plan.values.intervals were widened to whole seconds for
        range planning, extract.py handle_exclusive_bounds) so the ±1ms
        adjustment here matches the host post-filter exactly. Requires a
        single interval (multiple intervals can merge into over-wide per-bin
        windows) and a uniform day/week bin length; returns None when the
        device temporal test cannot be exact.
        """
        from geomesa_tpu.filter.extract import extract_intervals

        if plan.full_filter is None:
            return None
        iv = extract_intervals(plan.full_filter, ft.default_date.name)
        if iv is None or not iv.precise or len(iv.values) != 1:
            return None
        bin_ms = self._BIN_MS.get(ft.z3_interval)
        if bin_ms is None:
            return None
        b = iv.values[0]
        lo_ms = None if b.lower.value is None else int(b.lower.value)
        hi_ms = None if b.upper.value is None else int(b.upper.value)
        if lo_ms is not None and not b.lower.inclusive:
            lo_ms += 1
        if hi_ms is not None and not b.upper.inclusive:
            hi_ms -= 1
        out = []
        for bn in sorted(plan.values.bins):
            start = int(
                binned_to_time(np.asarray([bn]), np.asarray([0]), ft.z3_interval)[0]
            )
            wlo = 0 if lo_ms is None else max(lo_ms - start, 0)
            whi = bin_ms - 1 if hi_ms is None else min(hi_ms - start, bin_ms - 1)
            if whi >= wlo:
                out.append((bn, wlo, whi))
        return out

    # -- device kNN ----------------------------------------------------------

    def knn_candidates(self, table: IndexTable, x: float, y: float, k: int):
        """Device top-k nearest candidates to (x, y); None -> host fallback.

        The KNNQuery/GeoHashSpiral analog gone TPU-native: instead of
        spiraling geohash cells outward, every chip ranks ITS resident rows
        by f32 haversine distance in one fused pass (lax.top_k per shard
        under shard_map) and ships back only k candidates per shard — a
        fixed, tiny transfer independent of N. Candidates are a superset
        ranked in f32; callers re-rank exactly in f64 (process/knn.py), so
        results match the host path. Returns [(block, local_rows)] of the
        per-segment candidates.
        """
        if table.index.name not in ("z2", "z3"):
            return None
        if self._has_visibilities(table):
            # per-feature auth checks need the row-wise host path
            return None
        dev = self.device_index(table)
        out = []
        pend = []
        for seg in dev.segments:
            if not seg.n:
                continue
            if not seg.load_raw(table) and seg.xf is None:
                return None
            kk = min(k, seg.n)
            mode = seg._mode()
            fn = _knn_fn(kk, mode, self.mesh)
            idx_d = fn(seg.xf, seg.yf, seg.valid,
                       jnp.float32(x), jnp.float32(y))
            try:
                idx_d.copy_to_host_async()
            except Exception:  # pragma: no cover
                pass
            pend.append((seg, idx_d))
        for seg, idx_d in pend:
            rows = np.unique(np.asarray(idx_d).ravel())
            rows = rows[(rows >= 0) & (rows < seg.n)].astype(np.int64)
            # drop padded/invalid slots that leaked through top_k
            rows = rows[seg._valid_host[rows]]
            out.extend(seg.to_block_rows(np.sort(rows)))
        return out

    # -- fused aggregation push-down ----------------------------------------

    def count_scan(self, table: IndexTable, plan: QueryPlan):
        """Exact filtered count with no row extraction (the EXACT_COUNT
        edition of the exact device scans): when the plan's FULL filter
        is device-decidable — precise box(+window), optionally with one
        attr predicate set (member or range) — each segment sums its
        mask on device and ships ONE scalar, transfer independent of
        hit count. None -> host path (len(query) over the normal scan).

        GEOMESA_COUNT_DEVICE: auto (accelerators with a sub-10ms link;
        over a high-latency link the per-execution floor loses to the
        host seek's sub-ms answer) | 1 | 0. Reference role: the
        EXACT_COUNT hint / GeoMesaStats.getCount split
        (index-api .../stats/GeoMesaStats.scala, QueryProperties)."""
        from geomesa_tpu.parallel.mesh import device_auto_declines

        if device_auto_declines("GEOMESA_COUNT_DEVICE"):
            return None
        if table.index.name in ("xz2", "xz3"):
            return self._count_xz_scan(table, plan)
        if table.index.name not in ("z2", "z3"):
            return None
        if not self._scan_eligible(table, plan):
            return None
        if self._has_visibilities(table):
            return None  # per-feature auth checks need the host path
        attr = akind = payload = None
        desc = self._exact_descriptor(table, plan)
        if desc is not None:
            box_np, win_np = desc
        else:
            got = self._attr_batch_desc(table, plan)
            if got is None:
                # non-rect INTERSECTS on a point table: the banded
                # ray-cast dual planes count like the extent tables do
                return self._count_poly_scan(table, plan)
            attr, akind, (box_np, win_np, payload) = got
        dev = self.device_index(table)
        if not dev.segments:
            return None
        if not all(seg.load_exact(table) for seg in dev.segments):
            return None
        if attr is not None and not all(
            seg.load_attr_codes(attr) for seg in dev.segments
        ):
            return None
        if akind == "vocabmask" and not all(
            seg.attr_vocab_ok(attr) for seg in dev.segments
        ):
            return None
        # replicate once, dispatch ALL segments, then collect: S segments
        # pay one upload + one link round-trip of latency, not S
        box_dev = replicate(self.mesh, box_np)
        win_dev = None if win_np is None else replicate(self.mesh, win_np)
        pending = [
            seg.count_exact_start(
                box_dev, win_dev, attr, payload, akind or "member"
            )
            for seg in dev.segments
        ]
        return sum(int(p) for p in pending)

    # value-distribution sketches reconstructable exactly from per-code
    # counts (observe_counts contract); GroupBy/Z3*/Descriptive and
    # geometry-attribute stats stay on the host extraction path
    _STAT_HIST_KINDS = ("minmax", "enumeration", "topk", "histogram", "frequency")

    def stats_scan(self, table: IndexTable, plan: QueryPlan, spec: str):
        """Device stats push-down (the KryoLazyStatsIterator / StatsScan
        compute-at-data analog, index-api iterators/AggregatingScan.scala:
        22-168): when the plan's FULL filter is a precise box(+window) on
        a point table and every combinator in ``spec`` is a value-
        distribution sketch over a rank-codable attribute, each segment
        ships ONE per-code count histogram (u_pad i32 — transfer sized by
        the attribute's cardinality, not the hit count) and the host
        reconstructs the EXACT sketches through the observe_counts
        contract: identical state to extracting the rows and observing
        them, including MinMax's HLL registers (multiplicity-insensitive,
        so distinct-value observation reproduces them bit-for-bit).
        None -> host path (extract + run_stats).

        GEOMESA_STATS_DEVICE: auto (accelerators with a sub-10ms link) |
        1 | 0 — same cost shape as GEOMESA_COUNT_DEVICE."""
        from geomesa_tpu.parallel.mesh import device_auto_declines
        from geomesa_tpu.stats.parser import parse_stat
        from geomesa_tpu.stats.sketches import CountStat, SeqStat

        if device_auto_declines("GEOMESA_STATS_DEVICE"):
            return None
        if table.index.name not in ("z2", "z3"):
            return None
        if not self._scan_eligible(table, plan):
            return None
        if self._has_visibilities(table):
            return None
        desc = self._exact_descriptor(table, plan)
        if desc is None:
            return None  # attr predicates / non-rect filters: host path
        try:
            stat = parse_stat(spec)
        except Exception:
            return None
        stats = stat.stats if isinstance(stat, SeqStat) else [stat]
        geom = table.ft.default_geometry.name if table.ft.default_geometry else None
        attrs = []
        for s in stats:
            if isinstance(s, CountStat):
                continue
            target = getattr(s, "attribute", None)
            if target is None or target == geom:
                return None
            if s.kind == "groupby":
                # GroupBy(a, Count()) IS the per-code histogram — one
                # CountStat group per present value; any other sub-stat
                # needs joint distributions and stays on the host
                import json as _json

                if _json.loads(s.example).get("kind") != "count":
                    return None
            elif s.kind not in self._STAT_HIST_KINDS:
                return None
            attrs.append(target)
        dev = self.device_index(table)
        if not dev.segments:
            return None
        if not all(seg.load_exact(table) for seg in dev.segments):
            return None
        for a in set(attrs):
            for seg in dev.segments:
                # the histogram buffer rides the vocab-mask size gate:
                # past it the per-query u_pad transfer stops being small
                if not seg.load_attr_codes(a) or not seg.attr_vocab_ok(a):
                    return None
        box_np, win_np = desc
        box_dev = replicate(self.mesh, box_np)
        win_dev = None if win_np is None else replicate(self.mesh, win_np)
        if attrs:
            pending = {
                a: [seg.stat_hist_start(box_dev, win_dev, a) for seg in dev.segments]
                for a in set(attrs)
            }
            merged: Dict[str, tuple] = {}
            total = None
            for a, per_seg in pending.items():
                vals: List[np.ndarray] = []
                cnts: List[np.ndarray] = []
                t = 0
                for buf, unified in per_seg:
                    out = np.asarray(buf)
                    t += int(out[0])
                    h = out[1 : 1 + len(unified)]
                    present = h > 0
                    if present.any():
                        vals.append(np.asarray(unified)[present])
                        cnts.append(h[present].astype(np.int64))
                if vals:
                    allv = np.concatenate(vals)
                    allc = np.concatenate(cnts)
                    uniq, inv = np.unique(allv, return_inverse=True)
                    summed = np.zeros(len(uniq), dtype=np.int64)
                    np.add.at(summed, inv, allc)
                    merged[a] = (uniq, summed)
                else:
                    merged[a] = (np.empty(0), np.empty(0, dtype=np.int64))
                total = t if total is None else total
        else:
            # Count()-only spec: the scalar count edition answers directly
            # (count_scan's own env gate must not double-gate a stats
            # request that already passed GEOMESA_STATS_DEVICE)
            pend = [
                seg.count_exact_start(box_dev, win_dev)
                for seg in dev.segments
            ]
            total = sum(int(p) for p in pend)
        for s in stats:
            if isinstance(s, CountStat):
                s.count = int(total)
                continue
            vals, cnts = merged[getattr(s, "attribute")]
            if s.kind == "groupby":
                for v, c in zip(vals, cnts):
                    sub = s._new()
                    sub.count = int(c)
                    s.groups[v.item() if isinstance(v, np.generic) else v] = sub
            elif len(vals):
                s.observe_counts(vals, cnts)
        return stat

    def _count_xz_scan(self, table: IndexTable, plan: QueryPlan):
        """Extent edition of count_scan (round-4 idea #5): the dual
        (hit, decided) planes answer COUNT as |decided| + the host-
        certified boundary ring — decided rows (the bulk, for rect-heavy
        data) never extract; only ring rows gather geometry objects.
        Matches the point edition's gates; None -> host path."""
        if not self._scan_eligible(table, plan):
            return None
        if self._has_visibilities(table):
            return None
        got = self._xz_batch_desc(table, plan)
        if got is None:
            return None
        qbox, win, has_time, geom, node, attr_info = got
        attr = akind = payload = None
        if attr_info is not None:
            attr, akind, payload = attr_info
        dev = self.device_index(table)
        if not dev.segments:
            return None
        if not all(seg.load_exact_xz(table) for seg in dev.segments):
            return None
        if has_time and any(seg.xz_tk is None for seg in dev.segments):
            return None
        if attr is not None and not all(
            seg.load_attr_codes(attr) for seg in dev.segments
        ):
            return None
        if akind == "vocabmask" and not all(
            seg.attr_vocab_ok(attr) for seg in dev.segments
        ):
            return None
        qbox_dev = replicate(self.mesh, qbox)
        win_dev = replicate(self.mesh, win)
        # dispatch EVERY segment before resolving any (one link round
        # trip of latency for S segments, like the point edition)
        pendings = [
            (seg, seg.count_xz_start(
                qbox_dev, win_dev, has_time, attr, payload,
                akind or "member",
            ))
            for seg in dev.segments
        ]
        return _count_dual_resolve(pendings, node, geom)

    def _count_poly_scan(self, table: IndexTable, plan: QueryPlan):
        """Banded-polygon edition of _count_xz_scan (point z-tables, one
        non-rect INTERSECTS + optional window/attr predicates): |decided
        ray-cast hits| + the host-certified error band."""
        got = self._poly_batch_desc(table, plan)
        if got is None:
            return None
        edges, box_np, win_np, has_time, geom, node, attr_info = got
        attr = akind = payload = None
        if attr_info is not None:
            attr, akind, payload = attr_info
        dev = self.device_index(table)
        if not dev.segments:
            return None
        if not all(seg.load_poly(table) for seg in dev.segments):
            return None
        if attr is not None and not all(
            seg.load_attr_codes(attr) for seg in dev.segments
        ):
            return None
        if akind == "vocabmask" and not all(
            seg.attr_vocab_ok(attr) for seg in dev.segments
        ):
            return None
        box_dev = replicate(self.mesh, box_np)
        win_dev = replicate(
            self.mesh,
            win_np if win_np is not None else np.zeros(4, np.uint32),
        )
        ecap = _pow2_at_least(len(edges), 8)
        padded = np.zeros((ecap, 4), np.float32)
        padded[: len(edges)] = edges
        edges_dev = replicate(self.mesh, padded)
        pendings = [
            (seg, seg.count_poly_start(
                edges_dev, box_dev, win_dev, has_time, attr, payload,
                akind or "member",
            ))
            for seg in dev.segments
        ]
        return _count_dual_resolve(pendings, node, geom)

    def pyramid_counts(self, table: IndexTable, bits: int) -> Optional[np.ndarray]:
        """[H, W] int64 per-cell row counts for the aggregate pyramid
        (ops/pyramid.py), reduced on device straight off the existing z2
        segment mirrors — the rows' integer grid coordinates (seg.xi/yi)
        are already HBM-resident, so a build moves one small mask up and
        one [H, W] grid back per segment. Integer shifts + sort counting
        make the grid bit-identical to the host build over the same
        keys. None -> the host build (non-z2 table, no mirrors)."""
        if table.index.name != "z2":
            return None
        dev = self.device_index(table)
        if not dev.segments:
            return None
        fn = self._pyramid_fns.get(bits)
        if fn is None:
            from geomesa_tpu.ops.aggregations import make_pyramid_counts

            fn = make_pyramid_counts(self.mesh, bits)
            self._pyramid_fns[bits] = fn
        n = 1 << bits
        total = np.zeros((n, n), dtype=np.int64)
        for seg in dev.segments:
            if seg.n == 0:
                continue
            grid = fn(seg.xi, seg.yi, seg.agg_mask(table))
            total += np.asarray(_np_local(grid), dtype=np.int64)
        return total

    def density_scan(self, table: IndexTable, plan: QueryPlan, spec):
        """Fused filter + density grid on device (the server-side
        KryoLazyDensityIterator analog); None -> host fallback.

        Supported when the full filter is precise rectangles (+ one time
        interval over uniform day/week bins, evaluated at ms precision) with
        no residual CQL. The grid is EXACTLY host-parity: the device counts
        rows it can certify in f32 and returns the indices of rows within
        f32 error of a cell boundary or box edge (the band), which the host
        decides from its f64 columns with the plan's full filter + the f64
        GridSnap — the density analog of the banded-polygon ring. The band
        comes back as indices while every shard's fits its buffer
        (``DENSITY_BAND_CAP``); a segment whose band outgrew it keeps its
        grid and fetches its band whole as a packed bitmap (the ``density``
        span's ``band``: ``indices`` or ``bitmap``). {"exact": True} still
        forces the host path outright.

        GEOMESA_DENSITY_DEVICE: auto (accelerators only, default) | 1 | 0 —
        on the CPU backend the fused full-scan has no advantage over the
        host seek + bincount path, so auto declines there.
        """
        import os

        from geomesa_tpu.parallel.mesh import device_auto_declines

        # cost choice (like GEOMESA_KNN_DEVICE): the fused kernel full-
        # scans every resident row — free on an accelerator, while the
        # CPU backend's host path seeks candidates and bincounts them;
        # over a high-latency link the dispatch round trip alone loses
        if device_auto_declines("GEOMESA_DENSITY_DEVICE"):
            return None
        if table.index.name not in ("z2", "z3") or not self.supports(table, plan):
            return None
        if plan.secondary is not None or spec.get("weight") or spec.get("exact"):
            return None
        if self._has_visibilities(table):
            # per-feature visibility needs the row-wise auth check
            return None
        gv = plan.values.geometries
        if not gv.values or not gv.precise or not all(g.is_rectangle() for g in gv.values):
            return None
        dev = self.device_index(table)
        windows = None
        if table.index.name == "z3":
            if not plan.values.bins:
                return None
            windows = self._ms_windows(table.ft, plan)
            if windows is None:
                return None
        if not dev.segments:
            return None
        for seg in dev.segments:
            if not seg.load_raw(table):
                return None
            if not getattr(seg.valid, "is_fully_addressable", True):
                # a mesh over several processes: the grid is global, but
                # each process could read only its own shards' band
                return None
        width, height = int(spec["width"]), int(spec["height"])
        # the kernel mode tracks the mask mode. A kernel that fails
        # raises: no edition is swapped in behind the caller's back
        mode = _mask_mode(self.mesh)
        if mode != "xla" and not all(s._pallas_ok for s in dev.segments):
            # some segment lacks the per-shard tile granule the pallas
            # kernel needs: decided from the layout, before any dispatch
            mode = "xla"
        from geomesa_tpu.ops.aggregations import DENSITY_BAND_CAP

        # ONE read of the cap: both the compiled band buffer size and
        # the overflow check below must see the same value (a runtime
        # change to the constant re-keys the fns cache instead of
        # silently truncating against a stale compiled buffer)
        band_cap = DENSITY_BAND_CAP
        fns = self._density_grid_fns(width, height, mode, band_cap)
        boxes = pad_boxes(
            [
                (g.envelope.xmin, g.envelope.ymin, g.envelope.xmax, g.envelope.ymax)
                for g in gv.values
            ],
            dtype=np.float32,
        )
        env = np.asarray(spec["envelope"], dtype=np.float32)
        b = replicate(self.mesh, boxes)
        e = replicate(self.mesh, env)
        w = (
            replicate(self.mesh, pad_windows(windows))
            if windows is not None
            else None
        )
        # dual grids: the device counts rows it can certify in f32;
        # band candidates come back as packed-array indices for the
        # host to decide from its f64 columns (exact host parity —
        # the density analog of the banded-polygon ring)
        total: Optional[np.ndarray] = None
        band: List[Tuple[object, np.ndarray]] = []
        band_layout = "indices"
        # every D2H read below is a device.fetch (span, deadline, fault
        # point, receipt bytes); ``outcome`` says what became of the
        # device's work: the grid, or a host answer after an f32 overflow
        with trace.span("density", segments=len(dev.segments)) as sp:
            for seg in dev.segments:
                if seg.kind == "z3":
                    args = (seg.xf, seg.yf, seg.bins, seg.t_ms, seg.valid, b, w, e)
                    grid, gidx, cnt = fns[0](*args)
                else:
                    args = (seg.xf, seg.yf, seg.valid, b, e)
                    grid, gidx, cnt = fns[1](*args)
                cnts = _np_local(cnt)
                n_band = int(np.max(cnts))
                g = np.asarray(_np_local(grid), dtype=np.float64)
                if float(g.max()) >= 2.0 ** 24:
                    # the device grid accumulates in f32, which is exact
                    # for integer counts only below 2^24 per cell; counts
                    # only grow during accumulation, so any loss leaves
                    # the final cell >= 2^24 and this check catches it —
                    # the host path answers exactly instead
                    sp.set_attr("outcome", "f32_overflow")
                    return None
                total = g if total is None else total + g
                if n_band > band_cap:
                    # a shard's band outgrew its index buffer (dense data
                    # near the cell edges): the grid stands, exact outside
                    # the band, and the band comes back whole as a bitmap
                    devstats_metrics().inc("density.band.overflow")
                    sp.add_event("density.band_overflow", cnt=n_band, cap=band_cap)
                    band_layout = "bitmap"
                    idx = self._density_band_bitmap(
                        seg, args, width, height, int(np.sum(cnts))
                    )
                else:
                    idx = _np_local(gidx)
                    idx = idx[idx >= 0]
                if idx.size:
                    band.append((seg, idx))
            rows = sum(int(idx.size) for _, idx in band)
            sp.set_attr("band", band_layout)
            sp.set_attr("band_rows", rows)
            if band:
                with trace.span("density.certify", rows=rows):
                    total += self._certify_density_band(
                        table, plan, spec, band, width, height
                    )
            sp.set_attr("outcome", "grid")
            return total

    def _density_grid_fns(self, width: int, height: int, mode: str,
                          band_cap: int):
        key = (width, height, mode, band_cap)
        fns = self._density_fns.get(key)
        if fns is None:
            from geomesa_tpu.ops.aggregations import make_sharded_density_dual

            fns = make_sharded_density_dual(
                self.mesh, width, height, mode, band_cap=band_cap
            )
            self._density_fns[key] = fns
        return fns

    def _density_band_bitmap(self, seg, args, width: int, height: int,
                             n_band: int) -> np.ndarray:
        """One segment's band candidates, fetched whole as a packed bitmap
        (ops/aggregations.make_sharded_band_bitmap over the dual pass's
        inputs) and decoded to packed-array row indices. The device copy
        is dropped as soon as the host holds the bytes."""
        from geomesa_tpu.ops.aggregations import (
            make_sharded_band_bitmap,
            row_tile_rows,
        )

        key = ("bitmap", width, height)
        fns = self._density_fns.get(key)
        if fns is None:
            fns = self._density_fns[key] = make_sharded_band_bitmap(
                self.mesh, width, height
            )
        with trace.span("density.band_bitmap", rows=n_band):
            bits = fns[0 if seg.kind == "z3" else 1](*args)
            packed = _np_local(bits)
            del bits
            pos = _decode_bitmap_rows(packed, 0, n_band)
        if pos.size != n_band:
            raise ValueError(
                f"density band bitmap holds {pos.size} rows, the pass counted {n_band}"
            )
        return row_tile_rows(pos, seg.shard_n())

    def _certify_density_band(
        self, table: IndexTable, plan: QueryPlan, spec,
        band: List[Tuple[object, np.ndarray]], width: int, height: int,
    ) -> np.ndarray:
        """Host-exact decisions for the density band: evaluate the plan's
        post filter on the f64 block columns of the band candidates and
        bin the passing rows with the f64 GridSnap (density_grid_numpy) —
        the same arithmetic the host reducer path uses, so the combined
        grid matches it exactly."""
        from geomesa_tpu.filter.evaluate import evaluate
        from geomesa_tpu.index.aggregators import density_grid_numpy
        from geomesa_tpu.store.datastore import _INTERNAL_SUFFIXES, LazyColumns

        ft = table.ft
        geom = ft.default_geometry.name
        add = np.zeros((height, width), dtype=np.float64)
        for seg, idx in band:
            idx = np.unique(idx[idx < seg.n])  # drop tail padding rows
            if idx.size == 0:
                continue
            parts = seg.to_block_rows(idx)
            # same observable-key rule as datastore._columns_from_parts:
            # a key must exist in every part's record layout (__null
            # absence means "no nulls" and materializes as zeros)
            keysets = [
                set(b.record.columns) if getattr(b, "record", None) is not None
                else set(b.columns)
                for b, _ in parts
            ]
            common = set.intersection(*keysets)
            keys = {"__fid__"} | {
                k for k in set.union(*keysets)
                if k != "__vis__"
                and not k.endswith(_INTERNAL_SUFFIXES)
                and (k in common or k.endswith("__null"))
            }
            cols = LazyColumns(parts, keys)
            pf = plan.post_filter
            m = (
                evaluate(pf, ft, cols) if pf is not None
                else np.ones(cols.num_rows, dtype=bool)
            )
            if not m.any():
                continue
            add += density_grid_numpy(
                np.asarray(cols[geom + "__x"], dtype=np.float64)[m],
                np.asarray(cols[geom + "__y"], dtype=np.float64)[m],
                None,
                tuple(spec["envelope"]),
                width,
                height,
            )
        return add
