"""Benchmark suite: BASELINE.md configs #2-#5 (the headline #1 lives in
bench.py, which the driver runs). Each config prints one JSON line with
parity-checked throughput vs the in-process numpy full-scan baseline.

  #2 z2: bbox-only point query (OSM-GPS-trace shape)
  #3 xz2: ST_Intersects over polygons/lines (OSM-ways shape)
  #4 z3 + attribute secondary filter (GDELT actor1='USA' AND bbox)
  #5 kNN process over the z3 index
  #6 density-grid aggregation push-down (device grid vs host reducer)

Usage: python bench_suite.py            (on the chip, like bench.py; exits
                                        non-zero without a TPU or when a
                                        config fails)
       GEOMESA_BENCH_N=... GEOMESA_BENCH_REPS=... to resize
"""

import json
import os
import sys
import time
from contextlib import contextmanager

import numpy as np


def log(msg):
    sys.stderr.write(f"[suite] {msg}\n")
    sys.stderr.flush()


def emit(payload):
    sys.stdout.write(json.dumps(payload) + "\n")
    sys.stdout.flush()


def _store():
    from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
    from geomesa_tpu.store.datastore import TpuDataStore

    return TpuDataStore(executor=TpuScanExecutor(default_mesh()))


@contextmanager
def _env_override(name, value):
    """Set one env var for the block, restoring the prior state (unset
    vars are re-unset) — the one home of the save/set/restore dance the
    forced-path measurements need."""
    saved = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = saved


def _grid_parity(grid, host_grid, hits):
    """(ok, l1): density parity tolerant of f32 cell-boundary flips.

    The device kernel snaps in float32 (executor.py density_scan doc:
    mirrors the reference's loose-bbox semantics); the host reducer is
    f64, so points within one f32 ulp of a cell or box edge may land one
    cell over (L1 contribution 2) or flip box membership (contribution
    1). Bound the allowed L1 by the statistically expected flip count;
    an actual kernel bug (wrong row set, shifted grid) blows far past
    it."""
    if grid.shape != host_grid.shape:
        return False, -1
    l1 = int(np.abs(np.asarray(grid, np.int64) - np.asarray(host_grid, np.int64)).sum())
    return l1 <= max(8, int(hits) // 10_000 * 2), l1


def _timeit(fn, reps):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    return (time.perf_counter() - t0) / reps, out


def _device_stream_fields(ds, name, cqls, wants, n, base_s):
    """Device-forced jittered query stream:
    GEOMESA_SEEK=0 routes the stream through the batched exact device
    scans (one execution per chunk); parity-checked per query. Reported
    as device_path_* next to the cost-chosen headline metric."""
    from geomesa_tpu.index.planner import Query as _Q

    with _env_override("GEOMESA_SEEK", "0"):
        queries = [_Q.cql(c, properties=[]) for c in cqls]
        prev = None
        for _ in range(3):  # warm until adaptive run capacities settle
            ds.query_many(name, queries)
            caps = {
                id(s): (s._rcap, s._span_cap)
                for d in getattr(ds.executor, "_cache", {}).values()
                for s in d[1].segments
            }
            if caps == prev:
                break
            prev = caps
        t0 = time.perf_counter()
        res = ds.query_many(name, queries)
        dt = (time.perf_counter() - t0) / len(queries)
    ok = all(
        set(map(str, r.fids)) == w for r, w in zip(res, wants)
    )
    return {
        "device_path_fps": round(n / dt, 1),
        "device_path_vs_baseline": round(base_s / dt, 3),
        "device_query_ms_pipelined": round(dt * 1000, 3),
        "device_parity": bool(ok),
    }


def bench_z2(n, reps):
    from geomesa_tpu.schema.featuretype import parse_spec

    rng = np.random.default_rng(5)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-85, 85, n)
    ds = _store()
    ft = parse_spec("gps", "*geom:Point:srid=4326")
    ds.create_schema(ft)
    fids = np.char.add("f", np.arange(n).astype(f"<U{len(str(n - 1))}"))
    ds._insert_columns(ft, {"__fid__": fids, "geom__x": x, "geom__y": y})
    box = (-10.0, -5.0, 15.0, 12.0)
    want = np.flatnonzero((x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3]))
    cql = f"bbox(geom, {box[0]}, {box[1]}, {box[2]}, {box[3]})"

    base_s, _ = _timeit(
        lambda: np.flatnonzero(
            (x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])
        ),
        max(3, reps // 4),
    )
    dev_s, res = _timeit(lambda: ds.query("gps", cql), reps)
    parity = set(res.fids) == {f"f{i}" for i in want}
    # jittered stream for the device-forced measurement
    jit_rng = np.random.default_rng(55)
    cqls, wants = [], []
    for _ in range(max(24, reps)):
        dx, dy = jit_rng.uniform(-8, 8, 2)
        b = (box[0] + dx, box[1] + dy, box[2] + dx, box[3] + dy)
        cqls.append(f"bbox(geom, {b[0]}, {b[1]}, {b[2]}, {b[3]})")
        wants.append({
            f"f{i}" for i in np.flatnonzero(
                (x >= b[0]) & (x <= b[2]) & (y >= b[1]) & (y <= b[3])
            )
        })
    return {
        "metric": "z2_bbox_throughput", "value": round(n / dev_s, 1),
        "unit": "features/sec", "vs_baseline": round(base_s / dev_s, 3),
        "n": n, "hits": int(len(want)), "parity": bool(parity),
        "query_ms": round(dev_s * 1000, 3),
        **_device_stream_fields(ds, "gps", cqls, wants, n, base_s),
    }


def bench_xz2(n, reps):
    from geomesa_tpu.geom.base import Polygon
    from geomesa_tpu.schema.featuretype import parse_spec

    rng = np.random.default_rng(6)
    cx = rng.uniform(-170, 170, n)
    cy = rng.uniform(-80, 80, n)
    w = rng.uniform(0.01, 0.5, n)
    ds = _store()
    ft = parse_spec("ways", "*geom:Polygon:srid=4326")
    ds.create_schema(ft)
    geoms = np.empty(n, dtype=object)
    for i in range(n):  # geometry OBJECTS are per-row; ingest is columnar
        x0, y0, ww = cx[i], cy[i], w[i]
        geoms[i] = Polygon(
            [[x0, y0], [x0 + ww, y0], [x0 + ww, y0 + ww], [x0, y0 + ww], [x0, y0]]
        )
    fids = np.char.add("w", np.arange(n).astype(f"<U{len(str(n - 1))}"))
    # envelope + isrect companions precomputed columnar (what the converter
    # emits at ingest) — skips the per-object Python walk
    ds._insert_columns(ft, {
        "__fid__": fids, "geom": geoms,
        "geom__bxmin": cx, "geom__bymin": cy,
        "geom__bxmax": cx + w, "geom__bymax": cy + w,
        "geom__isrect": np.ones(n, dtype=np.uint8),
    })
    box = (0.0, 0.0, 20.0, 15.0)
    hit = (cx + w >= box[0]) & (cx <= box[2]) & (cy + w >= box[1]) & (cy <= box[3])
    cql = f"bbox(geom, {box[0]}, {box[1]}, {box[2]}, {box[3]})"

    base_s, _ = _timeit(
        lambda: np.flatnonzero(
            (cx + w >= box[0]) & (cx <= box[2]) & (cy + w >= box[1]) & (cy <= box[3])
        ),
        max(3, reps // 4),
    )
    dev_s, res = _timeit(lambda: ds.query("ways", cql), reps)
    parity = set(res.fids) == {f"w{i}" for i in np.flatnonzero(hit)}
    jit_rng = np.random.default_rng(66)
    cqls, wants = [], []
    for _ in range(max(24, reps)):
        dx, dy = jit_rng.uniform(-10, 10, 2)
        b = (box[0] + dx, box[1] + dy, box[2] + dx, box[3] + dy)
        cqls.append(f"bbox(geom, {b[0]}, {b[1]}, {b[2]}, {b[3]})")
        wants.append({
            f"w{i}" for i in np.flatnonzero(
                (cx + w >= b[0]) & (cx <= b[2]) & (cy + w >= b[1]) & (cy <= b[3])
            )
        })
    # COUNT(*) pushdown over the extent table (round-5): |device-decided|
    # + host-certified ring, no row extraction for the decided bulk.
    # FORCED device edition (like the other device_* fields — the
    # cost-chosen count over a slow link may pick the host path, which
    # would make an unforced timing indistinguishable from the pushdown)
    with _env_override("GEOMESA_COUNT_DEVICE", "1"):
        cnt_s, cnt = _timeit(lambda: ds.count("ways", cql), max(3, reps // 4))
    return {
        "metric": "xz2_intersects_throughput", "value": round(n / dev_s, 1),
        "unit": "features/sec", "vs_baseline": round(base_s / dev_s, 3),
        "n": n, "hits": int(hit.sum()), "parity": bool(parity),
        "query_ms": round(dev_s * 1000, 3),
        "count_device_ms": round(cnt_s * 1000, 3),
        "count_parity": bool(cnt == int(hit.sum())),
        **_device_stream_fields(ds, "ways", cqls, wants, n, base_s),
    }


def bench_attr_bbox(n, reps):
    from geomesa_tpu.schema.featuretype import parse_spec

    rng = np.random.default_rng(7)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-85, 85, n)
    base_ms = np.datetime64("2026-01-01T00:00:00", "ms").astype(np.int64)
    t = base_ms + rng.integers(0, 30 * 86400_000, n)
    actors = np.array(["USA", "CHN", "RUS", "FRA", "BRA"], dtype=object)[
        rng.integers(0, 5, n)
    ]
    gold = np.round(rng.uniform(-10, 10, n), 1)  # goldsteinscale shape
    ds = _store()
    ft = parse_spec(
        "gdelt",
        "actor1:String:index=true,goldstein:Double,dtg:Date,*geom:Point:srid=4326",
    )
    ds.create_schema(ft)
    fids = np.char.add("f", np.arange(n).astype(f"<U{len(str(n - 1))}"))
    ds._insert_columns(
        ft, {"__fid__": fids, "actor1": actors, "goldstein": gold,
             "geom__x": x, "geom__y": y, "dtg": t}
    )
    box = (-30.0, 0.0, 10.0, 30.0)
    want_mask = (
        (actors == "USA") & (x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])
    )
    cql = f"actor1 = 'USA' AND bbox(geom, {box[0]}, {box[1]}, {box[2]}, {box[3]})"

    base_s, _ = _timeit(
        lambda: np.flatnonzero(
            (actors == "USA") & (x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])
        ),
        max(3, reps // 4),
    )
    dev_s, res = _timeit(lambda: ds.query("gdelt", cql), reps)
    parity = set(res.fids) == set(fids[want_mask])
    # jittered attr+bbox stream: with GEOMESA_SEEK=0 these route through
    # the attr device batches — equality via the membership edition
    # (VERDICT r3 #9's silicon number) AND numeric ranges via the
    # [lo, hi] code-interval edition (round 4's plane), interleaved so
    # one pipelined stream measures both kernel families
    cqls, wants = [], []
    for k in range(max(24, reps)):  # both families need >= 2 batch members
        dx = round(float(rng.uniform(-5, 5)), 3)
        b = (box[0] + dx, box[1], box[2] + dx, box[3])
        bq = f"bbox(geom, {b[0]!r}, {b[1]!r}, {b[2]!r}, {b[3]!r})"
        in_box = (x >= b[0]) & (x <= b[2]) & (y >= b[1]) & (y <= b[3])
        if k % 2 == 0:
            actor = ["USA", "CHN", "RUS"][(k // 2) % 3]
            cqls.append(f"actor1 = '{actor}' AND {bq}")
            wants.append(set(fids[(actors == actor) & in_box]))
        else:
            lo = round(float(rng.uniform(-8, 0)), 1)
            hi = round(lo + float(rng.uniform(2, 10)), 1)
            cqls.append(f"goldstein > {lo} AND goldstein <= {hi} AND {bq}")
            wants.append(set(fids[(gold > lo) & (gold <= hi) & in_box]))
    # device stats push-down (per-code histograms -> exact sketches, no
    # row extraction): parity checked against direct numpy aggregation.
    # FORCED like the other device_path_* fields — auto declines over a
    # high-latency link (the cost gate), which auto_stats_path records;
    # the forced run measures the device edition itself
    from geomesa_tpu.index.planner import Query as _Q

    bq0 = f"bbox(geom, {box[0]}, {box[1]}, {box[2]}, {box[3]})"
    sq = _Q.cql(bq0, hints={"stats": "Count();MinMax(goldstein);TopK(actor1)"})
    auto_path = ds.query("gdelt", sq).plan.scan_path
    with _env_override("GEOMESA_STATS_DEVICE", "1"):
        ds.query("gdelt", sq)  # warm (jit per u_pad bucket)
        st_s, st_res = _timeit(lambda: ds.query("gdelt", sq), max(3, reps // 4))
    in_box = (x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])
    seq = st_res.aggregate["stats"].stats
    uniq, cnt = np.unique(actors[in_box], return_counts=True)
    stats_parity = (
        seq[0].count == int(in_box.sum())
        and float(seq[1].min) == float(gold[in_box].min())
        and float(seq[1].max) == float(gold[in_box].max())
        and dict(seq[2].topk(5)) == dict(zip(uniq.tolist(), cnt.astype(int).tolist()))
    )
    stats_fields = {
        "device_stats_ms": round(st_s * 1000, 3),
        "device_stats_path": st_res.plan.scan_path,
        "device_stats_parity": bool(stats_parity),
        "auto_stats_path": auto_path,
    }
    return {
        "metric": "attr_plus_bbox_throughput", "value": round(n / dev_s, 1),
        "unit": "features/sec", "vs_baseline": round(base_s / dev_s, 3),
        "n": n, "hits": int(want_mask.sum()), "parity": bool(parity),
        "query_ms": round(dev_s * 1000, 3),
        **stats_fields,
        **_device_stream_fields(ds, "gdelt", cqls, wants, n, base_s),
    }


def bench_poly(n, reps):
    """Non-rect INTERSECTS(polygon) over a point store vs a vectorized f64
    numpy ray-cast full scan. The headline times the cost-chosen path
    (like every suite config); the device_path_* fields time the banded
    device ray-cast (executor._poly_mask_body) on the jittered stream."""
    from geomesa_tpu.schema.featuretype import parse_spec

    rng = np.random.default_rng(9)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-85, 85, n)
    ds = _store()
    ft = parse_spec("pts", "*geom:Point:srid=4326")
    ds.create_schema(ft)
    fids = np.char.add("f", np.arange(n).astype(f"<U{len(str(n - 1))}"))
    ds._insert_columns(ft, {"__fid__": fids, "geom__x": x, "geom__y": y})

    def star(cx, cy, r):
        ang = np.linspace(0, 2 * np.pi, 13)[:-1]
        rad = np.where(np.arange(12) % 2 == 0, r, 0.45 * r)
        pts = np.stack([cx + rad * np.cos(ang), cy + rad * np.sin(ang)], axis=1)
        return np.vstack([pts, pts[:1]])

    def pip(poly, px, py):
        inside = np.zeros(len(px), bool)
        for (x1, y1), (x2, y2) in zip(poly[:-1], poly[1:]):
            cond = (y1 > py) != (y2 > py)
            if y1 != y2:
                xint = x1 + (py - y1) * (x2 - x1) / (y2 - y1)
                inside ^= cond & (px < xint)
        return inside

    def wkt(poly):
        return "POLYGON ((" + ", ".join(f"{a:.6f} {b:.6f}" for a, b in poly) + "))"

    poly = star(2.0, 10.0, 14.0)
    cql = f"intersects(geom, {wkt(poly)})"
    base_s, want_mask = _timeit(lambda: pip(poly, x, y), max(3, reps // 4))
    dev_s, res = _timeit(lambda: ds.query("pts", cql), reps)
    parity = set(res.fids) == set(fids[want_mask])
    jit_rng = np.random.default_rng(99)
    cqls, wants = [], []
    for _ in range(max(24, reps)):
        dx, dy = jit_rng.uniform(-6, 6, 2)
        p = star(2.0 + dx, 10.0 + dy, 14.0)
        cqls.append(f"intersects(geom, {wkt(p)})")
        wants.append(set(fids[pip(p, x, y)]))
    return {
        "metric": "polygon_intersects_throughput", "value": round(n / dev_s, 1),
        "unit": "features/sec", "vs_baseline": round(base_s / dev_s, 3),
        "n": n, "hits": int(want_mask.sum()), "parity": bool(parity),
        "query_ms": round(dev_s * 1000, 3),
        **_device_stream_fields(ds, "pts", cqls, wants, n, base_s),
    }


def bench_density(n, reps):
    """Density aggregation push-down (#6): the fused device kernel
    returns a [H, W] grid (KBs over the link) instead of hit rows — the
    server-side-compute-at-the-data win (DensityScan.scala:30-59 role,
    here an MXU one-hot-matmul / XLA bincount kernel over resident
    columns). Baseline: numpy mask + bincount over the raw arrays (the
    strongest host equivalent of the reducer's core loop). Parity: the
    cost-chosen grid vs the f64 host reducer's grid under the bounded-L1
    tolerance of _grid_parity (f32 cell-boundary flips), plus a total-
    count cross-check against the brute grid."""
    from geomesa_tpu.index.planner import Query as _Q
    from geomesa_tpu.schema.featuretype import parse_spec

    rng = np.random.default_rng(12)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-85, 85, n)
    ds = _store()
    ft = parse_spec("dens", "*geom:Point:srid=4326")
    ds.create_schema(ft)
    fids = np.char.add("f", np.arange(n).astype(f"<U{len(str(n - 1))}"))
    ds._insert_columns(ft, {"__fid__": fids, "geom__x": x, "geom__y": y})
    box = (-60.0, -30.0, 60.0, 40.0)
    wdt, hgt = 256, 128
    cql = f"bbox(geom, {box[0]}, {box[1]}, {box[2]}, {box[3]})"
    spec = {"envelope": box, "width": wdt, "height": hgt}

    def _bin(xs, ys):
        """One grid-snap + bincount — shared by BOTH baselines so their
        ratio can never drift on a snapping change."""
        gx = np.clip(
            ((xs - box[0]) / (box[2] - box[0]) * wdt).astype(np.int64),
            0, wdt - 1,
        )
        gy = np.clip(
            ((ys - box[1]) / (box[3] - box[1]) * hgt).astype(np.int64),
            0, hgt - 1,
        )
        return np.bincount(gy * wdt + gx, minlength=wdt * hgt)

    def brute():
        m = (x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])
        return _bin(x[m], y[m])

    base_s, base_grid = _timeit(brute, max(3, reps // 4))
    q = _Q.cql(cql, hints={"density": dict(spec)})
    dev_s, res = _timeit(lambda: ds.query("dens", q), reps)
    grid = np.asarray(res.aggregate["density"])
    # parity oracle: the HOST reducer on the same store (GridSnap
    # semantics, f64) — tolerance for f32 cell-boundary flips, see
    # _grid_parity; the brute bincount cross-checks the total count
    with _env_override("GEOMESA_DENSITY_DEVICE", "0"):
        host_s, host_res = _timeit(lambda: ds.query("dens", q), max(3, reps // 4))
        host_grid = np.asarray(host_res.aggregate["density"])
    parity, l1 = _grid_parity(grid, host_grid, base_grid.sum())
    count_ok = abs(int(grid.sum()) - int(base_grid.sum())) <= max(
        4, int(base_grid.sum()) // 20_000
    )
    # the push-down's REFERENCE-FAITHFUL comparison: DensityScan exists
    # so rows never leave the server (KryoLazyDensityIterator vs a plain
    # scan + client-side binning). Time the extract-then-bin alternative
    # — materialize the hit rows through the store, then bincount — and
    # report the ratio next to the raw numpy full-scan baseline (which
    # no deployed client can actually run: it presumes the raw arrays).
    def extract_then_bin():
        r = ds.query("dens", cql)
        return _bin(
            np.asarray(r.columns["geom__x"]), np.asarray(r.columns["geom__y"])
        )

    extract_s, _ = _timeit(extract_then_bin, max(3, reps // 4))
    out = {
        "metric": "density_grid_throughput", "value": round(n / dev_s, 1),
        "unit": "features/sec", "vs_baseline": round(base_s / dev_s, 3),
        "vs_extract_baseline": round(extract_s / dev_s, 3),
        "extract_then_bin_ms": round(extract_s * 1000, 3),
        "n": n, "grid": [hgt, wdt], "hits": int(base_grid.sum()),
        "parity": bool(parity and count_ok), "grid_l1_diff": l1,
        "query_ms": round(dev_s * 1000, 3),
        "host_reducer_ms": round(host_s * 1000, 3),
    }
    # forced device kernel (the cost gate may already choose it —
    # this field isolates the fused-kernel time either way). The seek
    # scan must ALSO be disabled: with it on, the plan routes
    # host-seek before the density push-down is consulted, and the
    # forced run times the host reducer under a device label (the
    # r5 capture's "kernel declined (scan_path='host-seek')")
    with _env_override("GEOMESA_DENSITY_DEVICE", "1"), \
            _env_override("GEOMESA_SEEK", "0"):
        dvc_s, dvc_res = _timeit(lambda: ds.query("dens", q), reps)
    if getattr(dvc_res.plan, "scan_path", "") != "device-density":
        # the fused kernel declined (unsupported shape / failure
        # fallback): the timing above is the HOST reducer — do
        # not report it as a device number
        out["device_error"] = (
            f"kernel declined (scan_path="
            f"{getattr(dvc_res.plan, 'scan_path', '')!r})"
        )
    else:
        dgrid = np.asarray(dvc_res.aggregate["density"])
        dparity, dl1 = _grid_parity(dgrid, host_grid, base_grid.sum())
        out.update({
            "device_path_fps": round(n / dvc_s, 1),
            "device_path_vs_baseline": round(base_s / dvc_s, 3),
            "device_query_ms_pipelined": round(dvc_s * 1000, 3),
            "device_parity": bool(dparity),
            "device_grid_l1_diff": dl1,
        })
    return out


def bench_knn(n, reps):
    from geomesa_tpu.process.geodesy import haversine_m
    from geomesa_tpu.process.knn import knn_search
    from geomesa_tpu.schema.featuretype import parse_spec

    rng = np.random.default_rng(8)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-85, 85, n)
    base_ms = np.datetime64("2026-01-01T00:00:00", "ms").astype(np.int64)
    t = base_ms + rng.integers(0, 30 * 86400_000, n)
    ds = _store()
    ft = parse_spec("pts", "dtg:Date,*geom:Point:srid=4326")
    ds.create_schema(ft)
    fids = np.char.add("f", np.arange(n).astype(f"<U{len(str(n - 1))}"))
    ds._insert_columns(ft, {"__fid__": fids, "geom__x": x, "geom__y": y, "dtg": t})
    qx, qy, k = 2.35, 48.85, 10

    def brute():
        d = haversine_m(x, y, qx, qy)
        return [f"f{i}" for i in np.argsort(d, kind="stable")[:k]]

    from geomesa_tpu.process.knn import last_knn_path

    base_s, want = _timeit(brute, max(3, reps // 4))
    paths = []

    def timed_knn():
        r = knn_search(ds, "pts", qx, qy, k=k)
        paths.append(last_knn_path())  # per CALL: a mid-loop fallback
        return r  # must not be mislabeled by the final rep's path

    dev_s, got = _timeit(timed_knn, reps)
    parity = [f for f, _ in got] == want
    out = {
        "metric": "knn_throughput", "value": round(n / dev_s, 1),
        "unit": "features/sec", "vs_baseline": round(base_s / dev_s, 3),
        "n": n, "k": k, "parity": bool(parity),
        "query_ms": round(dev_s * 1000, 3),
        "cost_chosen_path": (
            paths[-1] if len(set(paths)) == 1 else f"mixed:{sorted(set(paths))}"
        ),
    }
    if set(paths) == {"device-topk"}:
        # the cost gate already chose the device for every rep — the
        # headline numbers ARE the device numbers; no second loop
        out.update({
            "device_path_fps": out["value"],
            "device_path_vs_baseline": out["vs_baseline"],
            "device_query_ms_pipelined": out["query_ms"],
            "device_parity": bool(parity),
        })
        return out
    # forced device top-k: EVERY rep must have answered on device or
    # the averaged time includes fallback latencies (mislabeling)
    paths.clear()
    with _env_override("GEOMESA_KNN_DEVICE", "1"):
        dvc_s, got_d = _timeit(timed_knn, reps)
    if set(paths) != {"device-topk"}:
        out["device_error"] = (
            f"device top-k declined or fell back ({sorted(set(paths))})"
        )
    else:
        out.update({
            "device_path_fps": round(n / dvc_s, 1),
            "device_path_vs_baseline": round(base_s / dvc_s, 3),
            "device_query_ms_pipelined": round(dvc_s * 1000, 3),
            "device_parity": [f for f, _ in got_d] == want,
        })
    return out


def main():
    import bench
    from geomesa_tpu.parallel.mesh import enable_compile_cache

    dev = bench.device_fields()
    if dev["platform"] != "tpu":
        log(f"no TPU (jax gives {dev['platform']}); nothing to measure")
        return 1
    enable_compile_cache()
    smoke = os.environ.get("GEOMESA_BENCH_SMOKE", "") not in ("", "0")
    # 8M (was 2M): at 2M the per-execution device floor drowned the z2/xz2
    # device paths (0.30-0.34x vs 1.5x at the headline's 20M) — the suite
    # should measure kernels above the floor, like the reference's bulk
    # scans do (tablet-server scans amortize per-RPC cost the same way)
    n = int(os.environ.get("GEOMESA_BENCH_N", 0)) or (200_000 if smoke else 8_000_000)
    reps = int(os.environ.get("GEOMESA_BENCH_REPS", 3 if smoke else 10))
    failed = 0
    for name, fn in [
        ("z2", bench_z2),
        ("xz2", bench_xz2),
        ("attr_bbox", bench_attr_bbox),
        ("poly", bench_poly),
        ("density", bench_density),
        ("knn", bench_knn),
    ]:
        log(f"running {name} (n={n})")
        try:
            payload = fn(n, reps)
        except Exception as e:  # noqa: BLE001 - report it, run the next config
            payload = {"metric": name, "error": f"{type(e).__name__}: {e}"}
        payload.update(dev)
        failed += bool(payload.get("error") or payload.get("device_error"))
        emit(payload)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
