"""Device-side spatial joins: point-in-polygon and distance joins.

The enrichment query class ("which events fall inside which geofences")
the predicate-scan pipeline cannot answer. The shape follows the
adaptive-join literature (PAPERS.md: "Adaptive Geospatial Joins for
Modern Hardware", "3DPipe"): a grid/Z-bucketed BUILD side resident on
device, a streamed PROBE side, and adaptive repartitioning when skew
blows a bucket past the pad budget.

Layout
------
The build side (geofence polygons for ``contains``, points for
``dwithin``) buckets into a low-resolution z2 grid
(``geomesa.join.bucket.bits`` per dimension). Each geometry lands in
every cell its radius-and-epsilon expanded envelope overlaps; any
bucket holding more than ``geomesa.join.skew.threshold`` geometries
quad-splits into finer cells (up to ``geomesa.join.split.depth``
levels) — the devstats pad gauges are fed per upload, and the split
keeps every kernel dispatch inside one shared pow2 candidate bucket
instead of letting one hot cluster pad every probe chunk to its size.
Geometry edge lists (``[G, E_pad, 4]``), build coordinates, and the
bucket candidate matrix (``[B, C_pad]``, -1 padded) upload ONCE per
schema generation through the mesh dispatch path and stay HBM-resident
in a TTL'd per-store cache (``geomesa.join.cache.ttl``).

Kernels and exactness
---------------------
Probe points stream through the segment-upload path
(``parallel/executor.join_upload``) ``geomesa.join.probe.chunk`` rows
at a time, NaN-padded to pow2 groups per bucket. The f32 kernels
(``join_pip`` even-odd ray cast, ``join_dwithin`` haversine) return a
DUAL mask per (probe, candidate) pair: ``accept`` (decidably matching,
safely away from any boundary) and ``check`` (within the boundary band
— the GridSnap/normalization epsilon of ops/geometry plus the f32
slack). Accepted pairs are final; band pairs get the exact f64 host
predicate. The host reference join routes probes through the SAME
bucket structure and applies the same exact predicates, so the device
path and the host degradation path return identical pairs by
construction — the repo's parity-under-faults invariant extends to the
join query class.

Failure envelope
----------------
``join.build`` (bucketing + device upload) and ``join.probe`` (per
chunk) are named fault points paired with spans and deadline checks.
Any device failure degrades the WHOLE join to the host reference path
(identical pairs) and trips the session flag via
``GEOMESA_JOIN_DEVICE`` semantics (auto | 0=host | 1=always retry
device), mirroring the density/stats push-down autos.
"""

from __future__ import annotations

import threading
import time
import weakref
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from geomesa_tpu.geom.base import Geometry, MultiPolygon, Polygon
from geomesa_tpu.ops.geometry import (
    polygon_edges,
    snap_epsilon_deg,
    snap_epsilon_m,
)
from geomesa_tpu.utils import audit as audit_mod
from geomesa_tpu.utils import deadline, faults, trace
from geomesa_tpu.utils import plans as plans_mod
from geomesa_tpu.utils.devstats import devstats_metrics, instrumented_jit

# the point-in-polygon boundary band, degrees. Pairs whose probe point
# sits within this distance of ANY build edge are host-verified in f64;
# the f32 ray cast is trusted only beyond it. 1e-3 deg (~110 m) safely
# dominates worst-case f32 coordinate arithmetic error at world scale
# (ulp(360) ~ 3e-5 deg, amplified a few x by the edge-intersection
# division) while keeping the exact-check band a sliver of any real
# geofence. The curve layer's snap epsilon folds in for index-derived
# coordinates.
PIP_BAND_DEG = 1e-3

# pow2 floor for probe-group padding: small groups bucket to one shape
GROUP_FLOOR = 64
# build-cache entries kept per store (LRU beyond this)
CACHE_CAP = 8

_KERNELS: Dict[str, Any] = {}
_KERNELS_LOCK = threading.Lock()
# live per-store build caches (entry counting for /debug/device) and the
# most recent build's bucket-occupancy summary (the skew histogram an
# operator reads when a join slows down)
_CACHES: "weakref.WeakSet" = weakref.WeakSet()
# guards _CACHES add vs. join_debug's sum — a WeakSet mutated during
# iteration raises, and a first join on a fresh store must not blank the
# /debug/device join block (GC removals are iteration-safe already)
_CACHES_LOCK = threading.Lock()
_LAST_BUILD: Dict[str, Any] = {}
_LAST_BUILD_LOCK = threading.Lock()

# conservative meters-per-degree FLOOR for bucket-envelope expansion:
# deliberately BELOW the true spherical scale (~111195 m/deg; contrast
# geometry.METERS_PER_DEGREE = 111320, which rounds the other way for
# epsilon widening), so dlat/dlon only ever OVER-cover. Never raise it
# past the true scale — an under-covered envelope drops boundary dwithin
# pairs on device AND host alike, since both share the bucket routing.
M_PER_DEG_FLOOR = 111000.0

# dwithin radii past this (10,000 km) decline the device kernel: near the
# antipodal distance (~20,015 km) the haversine's asin amplifies a few
# ulps of f32 error past any fixed epsilon band (asin'(s) = 1/sqrt(1-s²)
# blows up as s -> 1), so the f32 mask stops being a guaranteed superset
# of the f64 predicate. The host path answers such joins exactly; at
# these radii the bucket cover is the whole world anyway, so the kernel
# has no pruning advantage to give up.
DWITHIN_DEVICE_MAX_R_M = 1.0e7


class JoinError(ValueError):
    """Bad join request (unknown predicate, missing radius, non-point
    probe side)."""


@dataclass(frozen=True)
class JoinSpec:
    """Parsed join predicate: ``contains`` (probe point in build
    polygon, boundary inclusive — JTS intersects semantics, matching
    ``geom.predicates.points_in_geometry``) or ``dwithin`` (haversine
    meters between probe and build points)."""

    kind: str
    radius_m: float = 0.0

    @classmethod
    def parse(cls, predicate, radius_m: Optional[float] = None) -> "JoinSpec":
        if isinstance(predicate, JoinSpec):
            return predicate
        p = str(predicate).strip().lower()
        if p == "dwithin" or p.startswith("dwithin("):
            inner = p[len("dwithin"):].strip()
            if inner:
                # anything after "dwithin" must be a complete (...) —
                # a typo like "dwithin500" must fail crisply, not run
                # with the separately-supplied radius
                if not inner.endswith(")"):
                    raise JoinError(
                        f"malformed dwithin predicate: {predicate!r}"
                    )
                radius_m = inner[1:-1]
            if radius_m is None:
                raise JoinError("dwithin join needs a radius: dwithin(<meters>)")
            try:
                radius_m = float(radius_m)
            except (TypeError, ValueError):
                raise JoinError(
                    f"dwithin radius must be a number, got {radius_m!r}"
                ) from None
            if radius_m < 0:
                raise JoinError("dwithin radius must be >= 0")
            return cls("dwithin", radius_m)
        if p == "contains":
            return cls("contains")
        raise JoinError(
            f"unknown join predicate {predicate!r} (contains | dwithin(r))"
        )


def _pow2_at_least(n: int, floor: int) -> int:
    """The executor's pad-bucket rule, single-sourced: build-side
    candidate caps and probe-group pads must bucket exactly like every
    other segment shape or the jit shape model drifts."""
    from geomesa_tpu.parallel.executor import _pow2_at_least as impl

    return impl(n, floor)


def _knobs() -> Tuple[int, int, int, float, int]:
    from geomesa_tpu.utils.config import (
        JOIN_BUCKET_BITS,
        JOIN_CACHE_TTL,
        JOIN_PROBE_CHUNK,
        JOIN_SKEW_THRESHOLD,
        JOIN_SPLIT_DEPTH,
    )

    # None-checked, not falsy-or'd: an explicit 0 is a legitimate
    # setting (split.depth=0 disables adaptive splits) and must be
    # honored — the PR 6 shard-knob rule
    def val(prop, default):
        got = prop.to_int()
        return default if got is None else got

    bits = max(1, val(JOIN_BUCKET_BITS, 3))
    threshold = max(1, val(JOIN_SKEW_THRESHOLD, 128))
    depth = max(0, val(JOIN_SPLIT_DEPTH, 6))
    ttl = JOIN_CACHE_TTL.to_duration_s(600.0)
    chunk = max(1, val(JOIN_PROBE_CHUNK, 2048))
    return bits, threshold, depth, ttl, chunk


# -- grid cells ---------------------------------------------------------------


def _cell_of(x: float, y: float, bits: int) -> Tuple[int, int]:
    n = 1 << bits
    cx = min(n - 1, max(0, int((x + 180.0) / 360.0 * n)))
    cy = min(n - 1, max(0, int((y + 90.0) / 180.0 * n)))
    return cx, cy

def _cell_bounds(bits: int, cx: int, cy: int) -> Tuple[float, float, float, float]:
    w = 360.0 / (1 << bits)
    h = 180.0 / (1 << bits)
    return (-180.0 + cx * w, -90.0 + cy * h, -180.0 + (cx + 1) * w, -90.0 + (cy + 1) * h)


def _cover_cells(bits: int, env: np.ndarray) -> List[Tuple[int, int]]:
    """All (cx, cy) cells at ``bits`` overlapped by one [4] envelope.

    A radius-expanded envelope may cross the antimeridian (lon outside
    [-180, 180]); the overflow WRAPS to the far columns instead of
    clamping — a geofence at lon 179.9 must be routable from a probe at
    -179.9 or dwithin pairs straddling the date line silently vanish.
    Latitude only clamps (no wrap over the poles; near-pole radii widen
    dlon toward the whole-world cover in ``_expand_envs``)."""
    n = 1 << bits
    cy0 = min(n - 1, max(0, int((env[1] + 90.0) / 180.0 * n)))
    cy1 = min(n - 1, max(0, int((env[3] + 90.0) / 180.0 * n)))
    xmin, xmax = float(env[0]), float(env[2])
    if xmax - xmin >= 360.0:
        segs = [(-180.0, 180.0)]
    elif xmin < -180.0:
        segs = [(-180.0, xmax), (xmin + 360.0, 180.0)]
    elif xmax > 180.0:
        segs = [(xmin, 180.0), (-180.0, xmax - 360.0)]
    else:
        segs = [(xmin, xmax)]
    cols = set()
    for sx0, sx1 in segs:
        cx0 = min(n - 1, max(0, int((sx0 + 180.0) / 360.0 * n)))
        cx1 = min(n - 1, max(0, int((sx1 + 180.0) / 360.0 * n)))
        cols.update(range(cx0, cx1 + 1))
    return [(cx, cy) for cx in sorted(cols) for cy in range(cy0, cy1 + 1)]


def _lon_overlaps(exmin: float, exmax: float, cxmin: float, cxmax: float) -> bool:
    """Longitude-interval overlap with antimeridian wrap: an expanded
    envelope running past +-180 overlaps the far-side columns too."""
    if exmax - exmin >= 360.0:
        return True
    if exmin < -180.0:
        segs = ((-180.0, exmax), (exmin + 360.0, 180.0))
    elif exmax > 180.0:
        segs = ((exmin, 180.0), (-180.0, exmax - 360.0))
    else:
        segs = ((exmin, exmax),)
    return any(s0 <= cxmax and s1 >= cxmin for s0, s1 in segs)


def _expand_envs(envs: np.ndarray, spec: JoinSpec) -> np.ndarray:
    """Build geometries' bucket-insertion envelopes, vectorized over an
    [N, 4] array (one numpy pass — a 100k-row dwithin build must not
    pay 100k Python-level calls per cache miss): each envelope widened
    by the predicate radius (latitude-aware for longitude — a 500 m
    radius spans far more lon degrees near the poles than the planner's
    equator-scale conversion suggests) plus the boundary band and the
    curve layer's snap epsilon, so a probe point that matches ALWAYS
    routes to a bucket holding the geometry."""
    envs = np.asarray(envs, dtype=np.float64)
    band = max(snap_epsilon_deg(), PIP_BAND_DEG)
    if spec.kind == "dwithin":
        r_m = spec.radius_m + snap_epsilon_m(spec.radius_m)
        dlat = r_m / M_PER_DEG_FLOOR + band
        lat_reach = np.minimum(
            90.0, np.maximum(np.abs(envs[:, 1]), np.abs(envs[:, 3])) + dlat
        )
        # a radius cap that wraps a pole makes every cos-scaled dlon
        # unsound: two points at the same high latitude but opposite
        # longitudes can sit within r OVER the pole — cover every column
        safe = lat_reach < 90.0 - 1e-9
        dlon = np.where(
            safe,
            r_m
            / (M_PER_DEG_FLOOR
               * np.cos(np.radians(np.where(safe, lat_reach, 0.0))))
            + band,
            360.0,
        )
    else:
        dlat = band
        dlon = band
    out = np.empty_like(envs)
    out[:, 0] = envs[:, 0] - dlon
    out[:, 1] = envs[:, 1] - dlat
    out[:, 2] = envs[:, 2] + dlon
    out[:, 3] = envs[:, 3] + dlat
    return out


# -- build side ---------------------------------------------------------------


def _geometry_edges(g: Geometry) -> Optional[np.ndarray]:
    """[E, 4] f32 edge list for the even-odd ray cast, or None when the
    geometry cannot ride the kernel (device-ineligible build member).

    Multi-member MultiPolygons decline: the even-odd parity of the
    concatenated rings equals the UNION only when members are disjoint,
    and nothing at ingest validates that — a point inside an overlap of
    two members crosses an even total and the kernel would drop a pair
    the host's member-OR semantics keeps. The host path answers those
    builds exactly (single-member MultiPolygons unwrap and ride)."""
    if isinstance(g, Polygon):
        return polygon_edges(g)
    if isinstance(g, MultiPolygon) and len(g.geoms) == 1:
        return polygon_edges(g.geoms[0])
    return None


class JoinBuild:
    """One build side, bucketed and (lazily) HBM-resident.

    Host state: exact f64 geometries/coordinates (the final word on
    boundary pairs and the degradation path), the bucket map, and the
    materialized build columns the join result re-exposes. Device
    state: edge/coordinate arrays plus the candidate matrix, uploaded
    once via ``ensure_device`` and reused across queries until the
    schema generation moves or the TTL expires."""

    def __init__(self, spec: JoinSpec, ft, columns: Dict[str, np.ndarray],
                 fids: np.ndarray, geoms: Optional[List[Optional[Geometry]]],
                 bx: Optional[np.ndarray], by: Optional[np.ndarray]):
        bits, threshold, depth, _ttl, _chunk = _knobs()
        self.spec = spec
        self.ft = ft
        self.columns = columns
        self.fids = fids
        self.geoms = geoms  # contains: Geometry|None per row
        self.bx = bx        # dwithin: f64 coords per row (NaN = null geom)
        self.by = by
        self.base_bits = bits
        self.built_at = time.time()
        # refreshed by every cache hit: the TTL evicts IDLE builds, not
        # hot ones (staleness is impossible — the cache key carries the
        # schema generation, so a write re-keys instead of aging out)
        self.last_used = self.built_at
        self.device_eligible = True
        self.stats: Dict[str, Any] = {}
        self._dev = None  # (edges/bxy, ecnt, cand) device arrays
        self._dev_lock = threading.Lock()

        n = len(fids)
        envs = np.zeros((n, 4), dtype=np.float64)
        self.active = np.zeros(n, dtype=bool)
        if spec.kind == "contains":
            self.edge_lists: List[Optional[np.ndarray]] = []
            for i, g in enumerate(geoms):
                if g is None:
                    self.edge_lists.append(None)
                    continue
                e = _geometry_edges(g)
                self.edge_lists.append(e)
                if e is None:
                    # non-polygonal member: the kernel cannot evaluate it;
                    # the whole join takes the host path (no silent drop)
                    self.device_eligible = False
                envs[i] = g.envelope.as_tuple()
                self.active[i] = True
        else:
            ok = ~(np.isnan(bx) | np.isnan(by))
            self.active = ok
            envs[:, 0] = np.where(ok, bx, 0.0)
            envs[:, 1] = np.where(ok, by, 0.0)
            envs[:, 2] = envs[:, 0]
            envs[:, 3] = envs[:, 1]
        self.envs = _expand_envs(envs, spec) if n else np.zeros((0, 4))

        # -- bucket + adaptive skew split -------------------------------
        buckets: Dict[Tuple[int, int, int], List[int]] = {}
        splits: set = set()
        n_splits = 0
        for i in np.flatnonzero(self.active):
            for cx, cy in _cover_cells(bits, self.envs[i]):
                buckets.setdefault((bits, cx, cy), []).append(int(i))
        work = [c for c, v in buckets.items() if len(v) > threshold]
        while work:
            cell = work.pop()
            b, cx, cy = cell
            if b - bits >= depth or cell not in buckets:
                continue
            entries = buckets.pop(cell)
            if len(entries) <= threshold:
                buckets[cell] = entries
                continue
            splits.add(cell)
            n_splits += 1
            for ccx in (cx * 2, cx * 2 + 1):
                for ccy in (cy * 2, cy * 2 + 1):
                    cb = _cell_bounds(b + 1, ccx, ccy)
                    child = [
                        i for i in entries
                        if _lon_overlaps(self.envs[i][0], self.envs[i][2],
                                         cb[0], cb[2])
                        and self.envs[i][1] <= cb[3] and self.envs[i][3] >= cb[1]
                    ]
                    if child:
                        key = (b + 1, ccx, ccy)
                        buckets[key] = child
                        if len(child) > threshold and (b + 1 - bits) < depth:
                            work.append(key)
        self.buckets = {c: np.asarray(v, dtype=np.int32)
                        for c, v in buckets.items()}
        self.splits = splits
        sizes = [len(v) for v in buckets.values()]
        self.cand_cap = _pow2_at_least(max(sizes, default=1), 8)
        self.n_splits = n_splits
        reg = devstats_metrics()
        if n_splits:
            reg.inc("join.bucket.splits", n_splits)
        reg.set_gauge("join.buckets", len(buckets))
        reg.set_gauge("join.bucket.max_entries", max(sizes, default=0))
        reg.set_gauge(
            "join.bucket.mean_entries",
            float(np.mean(sizes)) if sizes else 0.0,
        )
        hist: Dict[str, int] = {}
        for s in sizes:
            p = 1
            while p < s:
                p *= 2
            hist[f"<={p}"] = hist.get(f"<={p}", 0) + 1
        self.stats = {
            "geometries": int(self.active.sum()),
            "buckets": len(buckets),
            "splits": n_splits,
            "max_bucket": max(sizes, default=0),
            "candidate_cap": self.cand_cap,
            "histogram": dict(sorted(hist.items(), key=lambda kv: int(kv[0][2:]))),
        }
        with _LAST_BUILD_LOCK:
            _LAST_BUILD.clear()
            _LAST_BUILD.update(self.stats)
        # candidate matrix: bucket row -> padded geometry indices
        self.bucket_rows = {c: r for r, c in enumerate(sorted(self.buckets))}
        cand = np.full((max(len(self.buckets), 1), self.cand_cap), -1,
                       dtype=np.int32)
        for c, idxs in self.buckets.items():
            cand[self.bucket_rows[c], : len(idxs)] = idxs
        self.cand = cand

    def leaf_cell(self, x: float, y: float) -> Tuple[int, int, int]:
        b = self.base_bits
        while True:
            cx, cy = _cell_of(x, y, b)
            if (b, cx, cy) in self.splits:
                b += 1
                continue
            return (b, cx, cy)

    def route(self, x: np.ndarray, y: np.ndarray) -> Dict[Tuple[int, int, int], np.ndarray]:
        """Group probe rows by leaf bucket; rows landing in empty cells
        (no candidates) or carrying NaN coordinates drop out — both by
        construction match nothing. Base-cell routing is vectorized;
        only rows whose base cell was skew-split take the per-point
        descent (the split set is small by construction)."""
        idx = np.flatnonzero(~(np.isnan(x) | np.isnan(y)))
        if not len(idx):
            return {}
        n = 1 << self.base_bits
        cx = np.clip(((x[idx] + 180.0) / 360.0 * n).astype(np.int64), 0, n - 1)
        cy = np.clip(((y[idx] + 90.0) / 180.0 * n).astype(np.int64), 0, n - 1)
        key = cx * n + cy
        order = np.argsort(key, kind="stable")
        sidx = idx[order]
        skey = key[order]
        groups: Dict[Tuple[int, int, int], np.ndarray] = {}
        refined: Dict[Tuple[int, int, int], List[int]] = {}
        skx = cx[order]
        sky = cy[order]
        bounds = np.flatnonzero(np.diff(skey)) + 1
        for grp, g0 in zip(np.split(sidx, bounds),
                           np.concatenate([[0], bounds])):
            cell = (self.base_bits, int(skx[g0]), int(sky[g0]))
            if cell in self.splits:
                for i in grp:
                    leaf = self.leaf_cell(float(x[i]), float(y[i]))
                    if leaf in self.buckets:
                        refined.setdefault(leaf, []).append(int(i))
            elif cell in self.buckets:
                groups[cell] = grp.astype(np.int64)
        for c, v in refined.items():
            # never collides with groups: a refined leaf always carries
            # b > base_bits (its base cell is in splits, so the descent
            # takes at least one step), while every groups key is at
            # base_bits exactly
            groups[c] = np.asarray(v, dtype=np.int64)
        return groups

    # -- device residency -------------------------------------------------

    def ensure_device(self, mesh):
        """Upload the build arrays once (edge lists / coordinates and the
        candidate matrix) through the mesh dispatch path; subsequent
        queries reuse the HBM-resident copies. Raises on dispatch faults
        — the caller's degradation path answers from the host state."""
        with self._dev_lock:
            if self._dev is not None:
                return self._dev
            from geomesa_tpu.parallel import mesh as mesh_mod

            if self.spec.kind == "contains":
                g = len(self.edge_lists)
                e_max = max(
                    (len(e) for e in self.edge_lists if e is not None),
                    default=1,
                )
                e_pad = _pow2_at_least(max(e_max, 1), 8)
                edges = np.zeros((max(g, 1), e_pad, 4), dtype=np.float32)
                ecnt = np.zeros(max(g, 1), dtype=np.int32)
                for i, e in enumerate(self.edge_lists):
                    if e is None or not len(e):
                        continue
                    edges[i, : len(e)] = e
                    ecnt[i] = len(e)
                dev = (
                    mesh_mod.replicate(mesh, edges),
                    mesh_mod.replicate(mesh, ecnt),
                    mesh_mod.replicate(mesh, self.cand),
                )
            else:
                bx = np.where(self.active, self.bx, np.nan).astype(np.float32)
                by = np.where(self.active, self.by, np.nan).astype(np.float32)
                dev = (
                    mesh_mod.replicate(mesh, bx),
                    mesh_mod.replicate(mesh, by),
                    mesh_mod.replicate(mesh, self.cand),
                )
            self._dev = dev
            return dev

    def evict_device(self) -> None:
        with self._dev_lock:
            self._dev = None


# -- kernels ------------------------------------------------------------------


def _pip_fn():
    with _KERNELS_LOCK:
        fn = _KERNELS.get("pip")
        if fn is not None:
            return fn

    def run(px, py, cand, edges, ecnt, eps_deg):
        import jax.numpy as jnp

        idx = jnp.maximum(cand, 0)
        e = edges[idx]                      # [C, E, 4]
        cnt = ecnt[idx]                     # [C]
        emask = jnp.arange(e.shape[1])[None, :] < cnt[:, None]  # [C, E]
        x0, y0, x1, y1 = e[..., 0], e[..., 1], e[..., 2], e[..., 3]
        pxb = px[:, None, None]
        pyb = py[:, None, None]
        straddles = ((y0[None] > pyb) != (y1[None] > pyb)) & emask[None]
        denom = jnp.where(y1 - y0 == 0, 1.0, y1 - y0)[None]
        xint = x0[None] + (pyb - y0[None]) * (x1 - x0)[None] / denom
        crossings = jnp.sum(
            (straddles & (xint > pxb)).astype(jnp.int32), axis=2
        )
        inside = (crossings % 2) == 1       # [N, C]
        # min squared point->edge distance (degree space): the boundary
        # band the f32 parity cannot be trusted inside
        abx = (x1 - x0)[None]
        aby = (y1 - y0)[None]
        den = abx * abx + aby * aby
        den = jnp.where(den == 0, 1.0, den)
        t = jnp.clip(
            ((pxb - x0[None]) * abx + (pyb - y0[None]) * aby) / den, 0.0, 1.0
        )
        dx = pxb - (x0[None] + t * abx)
        dy = pyb - (y0[None] + t * aby)
        d2 = jnp.where(emask[None], dx * dx + dy * dy, jnp.inf)
        near = jnp.min(d2, axis=2) <= eps_deg * eps_deg  # [N, C]
        valid = (cand >= 0)[None]
        return (inside & ~near & valid), (near & valid)

    with _KERNELS_LOCK:
        fn = _KERNELS.setdefault("pip", instrumented_jit("join_pip", run))
    return fn


def _dwithin_fn():
    with _KERNELS_LOCK:
        fn = _KERNELS.get("dwithin")
        if fn is not None:
            return fn

    def run(px, py, cand, bx, by, r_m, eps_m):
        import jax.numpy as jnp

        from geomesa_tpu.ops.geometry import haversine_m_f32

        idx = jnp.maximum(cand, 0)
        d = haversine_m_f32(px[:, None], py[:, None], bx[idx][None], by[idx][None])
        valid = (cand >= 0)[None] & ~jnp.isnan(d)
        accept = (d <= r_m - eps_m) & valid
        check = (d > r_m - eps_m) & (d <= r_m + eps_m) & valid
        return accept, check

    with _KERNELS_LOCK:
        fn = _KERNELS.setdefault(
            "dwithin", instrumented_jit("join_dwithin", run)
        )
    return fn


# -- exact host predicates ----------------------------------------------------


def _exact_pairs(build: JoinBuild, gi: int, px: np.ndarray, py: np.ndarray,
                 rows: np.ndarray) -> np.ndarray:
    """Row subset of ``rows`` exactly matching build geometry ``gi``
    (f64; the final word on every boundary pair and the whole host
    path)."""
    if not len(rows):
        return rows
    x = px[rows]
    y = py[rows]
    if build.spec.kind == "contains":
        from geomesa_tpu.geom.predicates import points_in_geometry

        m = points_in_geometry(x, y, build.geoms[gi])
    else:
        from geomesa_tpu.process.geodesy import haversine_m

        m = haversine_m(x, y, build.bx[gi], build.by[gi]) <= build.spec.radius_m
    return rows[m]


def host_join(build: JoinBuild, px: np.ndarray, py: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray]:
    """The exact host reference join: same bucket routing as the device
    path, exact f64 predicate per (bucket, candidate). Returns
    (build_idx, probe_idx) sorted (build-major) — identical to the
    device path's canonical pair order."""
    out_b: List[np.ndarray] = []
    out_p: List[np.ndarray] = []
    for cell, rows in build.route(px, py).items():
        deadline.check("join.probe")
        for gi in build.buckets[cell]:
            hit = _exact_pairs(build, int(gi), px, py, rows)
            if len(hit):
                out_b.append(np.full(len(hit), int(gi), dtype=np.int64))
                out_p.append(hit)
    return _canonical_pairs(out_b, out_p)


def _canonical_pairs(out_b: List[np.ndarray], out_p: List[np.ndarray]
                     ) -> Tuple[np.ndarray, np.ndarray]:
    if not out_b:
        e = np.empty(0, dtype=np.int64)
        return e, e.copy()
    bi = np.concatenate(out_b)
    pi = np.concatenate(out_p)
    order = np.lexsort((pi, bi))
    return bi[order], pi[order]


# -- device probe -------------------------------------------------------------


def device_join(build: JoinBuild, mesh, px: np.ndarray, py: np.ndarray,
                stats: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    """Stream the probe side through the segment-upload path and
    evaluate the join kernels bucket by bucket. Accepted pairs are
    final; boundary-band pairs take the exact f64 host predicate, so
    the result is identical to ``host_join``."""
    from geomesa_tpu.parallel.executor import join_fetch, join_upload

    _bits, _thr, _depth, _ttl, chunk = _knobs()
    dev = build.ensure_device(mesh)
    extra = (dev[0], dev[1])
    if build.spec.kind == "contains":
        eps = np.float32(max(snap_epsilon_deg(), PIP_BAND_DEG))
        kern = _pip_fn()
    else:
        eps = np.float32(snap_epsilon_m(build.spec.radius_m))
        kern = _dwithin_fn()
    cand_dev = dev[2]
    out_b: List[np.ndarray] = []
    out_p: List[np.ndarray] = []
    verified = 0
    chunks = 0
    for start in range(0, len(px), chunk):
        # per-chunk boundary: injectable, span-wrapped, deadline-paired
        with trace.span("join.probe", chunk=chunks, rows=min(chunk, len(px) - start)):
            deadline.check("join.probe")
            faults.fault_point("join.probe")
            cx = px[start : start + chunk]
            cy = py[start : start + chunk]
            for cell, rows in build.route(cx, cy).items():
                deadline.check("join.probe")
                gx, gy = join_upload(
                    mesh, cx[rows], cy[rows], floor=GROUP_FLOOR
                )
                crow = cand_dev[build.bucket_rows[cell]]
                if build.spec.kind == "contains":
                    accept, check = kern(gx, gy, crow, *extra, eps)
                else:
                    accept, check = kern(
                        gx, gy, crow, *extra,
                        np.float32(build.spec.radius_m), eps,
                    )
                accept = join_fetch(accept)[: len(rows)]
                check = join_fetch(check)[: len(rows)]
                cands = build.buckets[cell]
                for j in range(len(cands)):
                    gi = int(cands[j])
                    hit = rows[accept[:, j]]
                    band = rows[check[:, j]]
                    if len(band):
                        verified += len(band)
                        band = _exact_pairs(build, gi, cx, cy, band)
                    if len(hit) or len(band):
                        both = np.concatenate([hit, band])
                        out_b.append(np.full(len(both), gi, dtype=np.int64))
                        out_p.append(both + start)
        chunks += 1
    stats["chunks"] = chunks
    stats["band_verified"] = verified
    devstats_metrics().inc("join.probe.chunks", chunks)
    return _canonical_pairs(out_b, out_p)


# -- build cache --------------------------------------------------------------


class JoinBuildCache:
    """Per-store TTL'd LRU of JoinBuild structures, keyed by (type name,
    filter, schema generation = index table versions, spec, knobs). A
    generation move (any write/compact) changes the key, so a stale
    build can never answer; the TTL bounds HBM residency of idle
    builds."""

    def __init__(self):
        self._entries: Dict[tuple, JoinBuild] = {}
        self._lock = threading.Lock()
        with _CACHES_LOCK:
            _CACHES.add(self)

    def get(self, key: tuple, ttl_s: float) -> Optional[JoinBuild]:
        reg = devstats_metrics()
        with self._lock:
            self._sweep(ttl_s)
            b = self._entries.pop(key, None)
            if b is not None:
                self._entries[key] = b  # LRU refresh
                b.last_used = time.time()
                reg.inc("join.build.hits")
                return b
        reg.inc("join.build.misses")
        return None

    def _sweep(self, ttl_s: float) -> None:
        """Drop EVERY expired entry, not just a same-key hit: idle
        builds must release their HBM arrays at TTL, or a handful of
        abandoned geofence sets stays device-resident until capacity
        eviction (the 'TTL bounds HBM residency' contract). Called
        under the lock."""
        now = time.time()
        for k in [k for k, b in self._entries.items()
                  if now - b.last_used > ttl_s]:
            self._entries.pop(k).evict_device()

    def put(self, key: tuple, build: JoinBuild) -> None:
        with self._lock:
            # two concurrent misses on one key both build: the displaced
            # loser releases its device arrays like every other removal
            # path, instead of pinning HBM until GC
            old = self._entries.pop(key, None)
            if old is not None and old is not build:
                old.evict_device()
            self._entries[key] = build
            while len(self._entries) > CACHE_CAP:
                self._entries.pop(next(iter(self._entries))).evict_device()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# -- planner ------------------------------------------------------------------


class JoinResult:
    """Joined pairs + both sides' materialized state.

    ``build_idx``/``probe_idx`` are parallel row-index arrays in
    canonical (build-major) order; ``pairs()`` exposes fid tuples and
    ``raw_columns()`` the spatial_join-shaped joined column dict
    (matched probe rows + build columns, suffixed on collision)."""

    def __init__(self, probe, build: JoinBuild, build_idx: np.ndarray,
                 probe_idx: np.ndarray, stats: Dict[str, Any], plan=None):
        self.probe = probe
        self.build = build
        self.build_idx = build_idx
        self.probe_idx = probe_idx
        self.stats = stats
        self.plan = plan

    def __len__(self) -> int:
        return len(self.build_idx)

    @property
    def build_fids(self) -> np.ndarray:
        return self.build.fids[self.build_idx]

    @property
    def probe_fids(self) -> np.ndarray:
        fids = self.probe.columns["__fid__"]
        return np.asarray(fids, dtype=object)[self.probe_idx]

    def pairs(self, limit: Optional[int] = None) -> List[Tuple[str, str]]:
        """Fid pairs in canonical order; ``limit`` slices the index
        arrays BEFORE any fid materialization (an explicit client cap
        must not pay for the pairs it asked to skip)."""
        bi = self.build_idx[:limit] if limit is not None else self.build_idx
        pi = self.probe_idx[:limit] if limit is not None else self.probe_idx
        pfids = np.asarray(self.probe.columns["__fid__"], dtype=object)
        return [
            (str(b), str(p))
            for b, p in zip(self.build.fids[bi], pfids[pi])
        ]

    def raw_columns(self, suffix: str = "_r") -> Dict[str, np.ndarray]:
        pcols = self.probe.columns
        if hasattr(pcols, "materialize"):
            pcols = pcols.materialize()
        cols = {k: v[self.probe_idx] for k, v in pcols.items()}
        for k, v in self.build.columns.items():
            key = (k + suffix) if k in pcols else k
            cols[key] = v[self.build_idx]
        return cols


class JoinPlanner:
    """Build-once / probe-streamed join execution over a datastore.

    The build side queries once per schema generation (the per-store
    ``JoinBuildCache`` keyed by index-table versions — any write or
    compaction moves the key) and stays HBM-resident; the probe side is
    an ordinary store query whose surviving coordinates stream through
    the device kernels, with the host reference join as the degradation
    target for ANY device failure."""

    def __init__(self, store):
        self.store = store

    def join(self, build_name: str, build_query, probe_name: str,
             probe_query, spec: JoinSpec) -> JoinResult:
        import os

        from geomesa_tpu.filter.parser import to_cql
        from geomesa_tpu.parallel import mesh as mesh_mod

        store = self.store
        bits, threshold, depth, ttl, _chunk = _knobs()
        cache = getattr(store, "_join_cache", None)
        if cache is None:
            # dict.setdefault is atomic under the GIL: two concurrent
            # first joins agree on ONE cache (a plain assignment would
            # let the loser's build put() vanish into an orphaned cache
            # that pins its device arrays until GC)
            cache = store.__dict__.setdefault("_join_cache", JoinBuildCache())
        def cache_key() -> tuple:
            # schema_generation covers BOTH local index-table versions
            # (lazy replay moves them) and the store's write counter —
            # the latter is the only signal on coordinators whose rows
            # live on shard workers (ShardedDataStore). The FULL build
            # query identity keys too: a limit/projection/sort/hint
            # changes which rows and columns the build read, and two
            # builds sharing only a filter must never collide
            return (
                build_name, to_cql(build_query.filter),
                build_query.max_features,
                tuple(build_query.properties)
                if build_query.properties is not None else None,
                tuple(build_query.sort_by)
                if build_query.sort_by else None,
                repr(sorted(build_query.hints.items(), key=repr))
                if build_query.hints else None,
                store.schema_generation(build_name), spec.kind,
                round(spec.radius_m, 3), bits, threshold, depth,
            )

        # settle a lazy store's partition replay BEFORE the key is
        # computed (store.query re-runs the hook as a no-op), then
        # capture the key ONCE: a concurrent write landing mid-build
        # moves the generation PAST this key, so a build that read
        # pre-write rows can never answer a post-write join. Re-keying
        # after the query would file that stale build under the
        # post-write generation and serve it for a TTL.
        store._prepare_query(build_name, build_query)
        key = cache_key()
        build = cache.get(key, ttl)
        rebuilt = build is None
        if rebuilt:
            res_b = store.query(build_name, build_query)
            build = self._make_build(res_b, spec)
            cache.put(key, build)

        probe_res = store.query(probe_name, probe_query)
        gname = (
            probe_res.ft.default_geometry.name
            if probe_res.ft.default_geometry is not None else None
        )
        pcols = probe_res.columns
        if gname is None or (gname + "__x") not in pcols:
            raise JoinError(
                f"probe side {probe_name!r} must be a point schema"
            )
        px = np.asarray(pcols[gname + "__x"], dtype=np.float64)
        py = np.asarray(pcols[gname + "__y"], dtype=np.float64)

        stats: Dict[str, Any] = {"build": "rebuild" if rebuilt else "hit"}
        stats.update(build.stats)
        # cache-engagement tally on the join's plan fingerprint
        # (utils/plans.py; one contextvar read when plan telemetry is off)
        plans_mod.note("join.build", "rebuild" if rebuilt else "hit")
        mesh = getattr(store.executor, "mesh", None)
        env = os.environ.get("GEOMESA_JOIN_DEVICE", "auto")
        # kernel eligibility, decomposed so every decline is reason-coded
        # (utils/audit.decision): WHY a join ran host-side is part of its
        # plan-quality record, not something to re-derive from the inputs
        use_device = mesh is not None
        if use_device and not build.device_eligible:
            # e.g. a multi-member MultiPolygon build: concatenated
            # even-odd parity != member union (see _geometry_edges)
            audit_mod.decision(
                "join.kernel", "build_ineligible", build=build_name
            )
            use_device = False
        if use_device and (
            spec.kind == "dwithin" and spec.radius_m > DWITHIN_DEVICE_MAX_R_M
        ):
            audit_mod.decision(
                "join.kernel", "antipodal_radius",
                radius_m=float(spec.radius_m),
            )
            use_device = False
        if use_device and env == "0":
            audit_mod.decision("join.kernel", "env_disabled")
            use_device = False
        if use_device and mesh_mod.device_tripped(
            store.executor, "GEOMESA_JOIN_DEVICE"
        ):
            audit_mod.decision("join.kernel", "device_tripped")
            use_device = False
        if use_device:
            # brownout speculation gate (utils/brownout.py): at the
            # hedge-off ladder level, fresh device build/compile work is
            # capacity the queue needs more — the host reference join
            # answers with identical pairs
            bo = getattr(store, "_brownout", None)
            if bo is not None and not bo.speculation_allowed():
                from geomesa_tpu.utils import brownout as brownout_mod

                if brownout_mod.enabled():
                    audit_mod.decision(
                        "join.kernel", "brownout", level=bo.level
                    )
                    use_device = False
        bi = pi = None
        path = "host-join"
        if use_device:
            try:
                # the device boundary of the build side: upload (or reuse)
                # the HBM-resident structure. Injectable + span-wrapped +
                # deadline-paired; a failure here or in any probe chunk
                # degrades the whole join to the host reference path.
                with trace.span("join.build", type=build_name,
                                cached=not rebuilt):
                    deadline.check("join.build")
                    faults.fault_point("join.build")
                    build.ensure_device(mesh)
                bi, pi = device_join(build, mesh, px, py, stats)
                path = "device-join"
            except Exception as e:  # noqa: BLE001 - device failure
                from geomesa_tpu.utils.audit import (
                    QueryTimeout,
                    robustness_metrics,
                )

                if isinstance(e, QueryTimeout):
                    raise  # the query's budget died, not the device
                robustness_metrics().inc("degrade.join_to_host")
                trace.event(
                    "degrade.join_to_host",
                    reason=f"{type(e).__name__}: {e}",
                )
                audit_mod.decision(
                    "degrade", "join_to_host", error=type(e).__name__
                )
                mesh_mod.trip_device(
                    store.executor, "GEOMESA_JOIN_DEVICE", "join", e
                )
                build.evict_device()
                path = "host-join-degraded"
        if bi is None:
            bi, pi = host_join(build, px, py)
        stats["path"] = path
        stats["pairs"] = int(len(bi))
        stats["probed"] = int(len(px))
        devstats_metrics().inc("join.pairs", int(len(bi)))
        return JoinResult(probe_res, build, bi, pi, stats, probe_res.plan)

    @staticmethod
    def _make_build(res_b, spec: JoinSpec) -> JoinBuild:
        cols = res_b.columns
        if hasattr(cols, "materialize"):
            cols = cols.materialize()
        ft = res_b.ft
        geom = ft.default_geometry
        if geom is None:
            raise JoinError(f"build side {ft.name!r} has no geometry")
        fids = np.asarray(cols.get("__fid__", np.empty(0, object)), object)
        if spec.kind == "contains":
            if geom.name not in cols:
                raise JoinError(
                    "contains join needs a polygonal build side "
                    f"({ft.name!r} stores points)"
                )
            geoms = list(cols[geom.name])
            return JoinBuild(spec, ft, cols, fids, geoms, None, None)
        if (geom.name + "__x") not in cols:
            raise JoinError(
                f"dwithin join needs a point build side ({ft.name!r})"
            )
        bx = np.asarray(cols[geom.name + "__x"], dtype=np.float64)
        by = np.asarray(cols[geom.name + "__y"], dtype=np.float64)
        return JoinBuild(spec, ft, cols, fids, None, bx, by)


def _cache_entries_total() -> int:
    with _CACHES_LOCK:
        return sum(len(c) for c in _CACHES)


def join_debug() -> Dict[str, Any]:
    """The ``join`` block of GET /debug/device: build-cache occupancy +
    hit/miss counters, the latest build's bucket skew histogram, and
    the split/pair counters."""
    reg = devstats_metrics()
    counters, gauges, _t, _tt = reg.snapshot()
    with _LAST_BUILD_LOCK:
        last = dict(_LAST_BUILD)
    return {
        "build_cache": {
            "entries": _cache_entries_total(),
            "hits": counters.get("join.build.hits", 0),
            "misses": counters.get("join.build.misses", 0),
        },
        "buckets": {
            "count": gauges.get("join.buckets", 0),
            "max_entries": gauges.get("join.bucket.max_entries", 0),
            "mean_entries": gauges.get("join.bucket.mean_entries", 0.0),
            "splits_total": counters.get("join.bucket.splits", 0),
            "histogram": last.get("histogram", {}),
        },
        "probe": {
            "chunks": counters.get("join.probe.chunks", 0),
            "pairs": counters.get("join.pairs", 0),
        },
    }
