"""Headline benchmark: GDELT-like Z3 bbox+time filter throughput.

Mirrors BASELINE.json config #1: N synthetic GDELT-style point features, a
bbox + date-range CQL query, result-set parity enforced between the device
path and a brute-force host reference. The CPU reference is a vectorized
NumPy full-scan predicate — a stand-in for (and strictly stronger than) the
reference's in-memory CQEngine datastore (geomesa-memory GeoCQEngine.scala:34),
which walks a quadtree + per-attribute indices on the JVM.

Runs in ONE process on whatever ``jax.devices()`` gives, and exits
non-zero without a result when that is not a TPU: a number measured on
the CPU is never printed under a device metric. The last stdout line is
the result: {"metric", "value", "unit", "vs_baseline", "platform",
"device_kind", "device_count", ...}. A parity failure or a query the
device did not answer (``degrade.device_to_host`` > 0) exits non-zero.

Env knobs:
  GEOMESA_BENCH_N        rows (default 20_000_000)
  GEOMESA_BENCH_REPS     timed repetitions (default 20)
  GEOMESA_BENCH_SMOKE=1  small fast mode (N=200_000, reps=3)
  GEOMESA_BENCH_REAL=1   57-column GDELT TSV through the converter ingest
"""

import json
import os
import sys
import time

import numpy as np


def synthesize(n: int, seed: int = 13):
    """GDELT-ish: world-wide points clustered around hot spots + 40 days."""
    rng = np.random.default_rng(seed)
    k = n // 4
    # uniform background + three dense clusters (cities)
    x = np.concatenate(
        [
            rng.uniform(-180, 180, n - 3 * k),
            rng.normal(-77.0, 3.0, k),
            rng.normal(2.35, 3.0, k),
            rng.normal(116.4, 3.0, k),
        ]
    )
    y = np.concatenate(
        [
            rng.uniform(-90, 90, n - 3 * k),
            rng.normal(38.9, 2.0, k),
            rng.normal(48.85, 2.0, k),
            rng.normal(39.9, 2.0, k),
        ]
    )
    x = np.clip(x, -180.0, 180.0)
    y = np.clip(y, -90.0, 90.0)
    base = np.datetime64("2026-01-01T00:00:00", "ms").astype(np.int64)
    t = base + rng.integers(0, 40 * 86400_000, n)
    order = rng.permutation(n)
    return x[order], y[order], t[order]


BOX = (-80.0, 36.0, -70.0, 41.0)
T_LO = np.datetime64("2026-01-05T00:00:00", "ms").astype(np.int64)
T_HI = np.datetime64("2026-01-19T00:00:00", "ms").astype(np.int64)
DURING = "dtg DURING 2026-01-05T00:00:00Z/2026-01-19T00:00:00Z"


def make_queries(reps: int):
    """The base query plus jittered variants (a realistic query stream —
    identical repeats would be answered from the plan/dispatch cache)."""
    rng = np.random.default_rng(7)
    boxes = [BOX]
    for _ in range(reps - 1):
        # jitter rounded so the CQL text is an exact f64 round trip
        dx = round(rng.uniform(-2.0, 2.0), 3)
        dy = round(rng.uniform(-1.0, 1.0), 3)
        boxes.append(
            (round(BOX[0] + dx, 3), round(BOX[1] + dy, 3),
             round(BOX[2] + dx, 3), round(BOX[3] + dy, 3))
        )
    cqls = [
        f"bbox(geom, {b[0]!r}, {b[1]!r}, {b[2]!r}, {b[3]!r}) AND {DURING}"
        for b in boxes
    ]
    return boxes, cqls


QUERY = make_queries(1)[1][0]


def brute_force(x, y, t, box=BOX):
    """The CPU reference: vectorized full-scan predicate (CQEngine stand-in)."""
    return np.flatnonzero(
        (x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3]) & (t > T_LO) & (t < T_HI)
    )


def emit(payload: dict) -> None:
    sys.stdout.write(json.dumps(payload) + "\n")
    sys.stdout.flush()


def log(msg: str) -> None:
    sys.stderr.write(f"[bench] {msg}\n")
    sys.stderr.flush()


def device_fields() -> dict:
    """The device every result names (jax's own report of it)."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def synth_gdelt_tsv(path: str, n: int, seed: int, id_offset: int = 0):
    """Real-format synthesis: the 57-column tab-delimited GDELT event
    layout (vectorized row assembly). Returns (x, y, t_ms) for parity."""
    rng = np.random.default_rng(seed)
    x, y, t = synthesize(n, seed)
    day_ms = 86400_000
    day = (t // day_ms * day_ms).astype("datetime64[ms]").astype("datetime64[D]")
    ymd = np.char.replace(day.astype(str), "-", "")
    lat = np.round(y, 4)
    lon = np.round(x, 4)
    actor1 = np.array(["UNITED STATES", "CHINA", "RUSSIA", "FRANCE", "BRAZIL"])[
        rng.integers(0, 5, n)
    ]
    ids = np.arange(id_offset, id_offset + n).astype("U10")
    mid = "\t" * 18  # cols 7-24
    nums = "\t1\t010\t01\t01\t1\t1.5\t3\t1\t2\t-1.2"  # cols 25-34
    a = np.char.add(ids, "\t")
    a = np.char.add(a, ymd)
    a = np.char.add(a, "\t\t\t\tUSA\t")
    a = np.char.add(a, actor1)
    a = np.char.add(a, mid + nums + "\t\t\t\t\t")
    a = np.char.add(a, lat.astype("U12"))
    a = np.char.add(a, "\t")
    a = np.char.add(a, lon.astype("U12"))
    a = np.char.add(a, "\t" * 16)
    with open(path, "w") as f:
        f.write("\n".join(a))
        f.write("\n")
    # the converter parses rounded coords and day-resolution dates: the
    # parity oracle must see exactly what was written
    return lon, lat, day.astype("datetime64[ms]").astype(np.int64)


def run_real(n: int, reps: int) -> dict:
    """GEOMESA_BENCH_REAL=1: the headline protocol over the PUBLIC ingest
    path — 57-column GDELT TSV through the premade converter + bulk
    ingest (VERDICT #6: no _insert_columns shortcut), same jittered query
    stream, same parity contract."""
    import tempfile

    from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
    from geomesa_tpu.schema.featuretype import parse_spec
    from geomesa_tpu.store.datastore import TpuDataStore
    from geomesa_tpu.tools.ingest import bulk_ingest
    from geomesa_tpu.tools.premade import GDELT_CONVERTER, GDELT_SFT

    per_file = 1_000_000
    files = []
    xs, ys, ts = [], [], []
    tmpdir = tempfile.mkdtemp(prefix="gdelt_bench_")
    t0 = time.perf_counter()
    for i in range(max(1, n // per_file)):
        path = os.path.join(tmpdir, f"part{i:03d}.tsv")
        lon, lat, tms = synth_gdelt_tsv(
            path, min(per_file, n), seed=100 + i, id_offset=i * per_file
        )
        files.append(path)
        xs.append(lon)
        ys.append(lat)
        ts.append(tms)
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    t = np.concatenate(ts)
    n = len(x)
    log(f"synthesized {len(files)} TSV files ({n:,} rows) in {time.perf_counter()-t0:.0f}s")

    boxes, cqls = make_queries(reps)
    brute_force(x[:1000], y[:1000], t[:1000])
    t0 = time.perf_counter()
    wants = [brute_force(x, y, t, b) for b in boxes]
    cpu_fps = n / ((time.perf_counter() - t0) / reps)
    log(f"cpu baseline: {cpu_fps:,.0f} features/sec ({len(wants[0])} hits)")

    store = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
    store.create_schema(parse_spec("gdelt", GDELT_SFT))
    t0 = time.perf_counter()
    ec = bulk_ingest(store, "gdelt", files, GDELT_CONVERTER)
    ingest_s = time.perf_counter() - t0
    log(f"converter ingest: {ec.success:,} ok / {ec.failure} bad, "
        f"{ec.success / ingest_s:,.0f} rec/sec")
    for f in files:
        os.remove(f)

    from geomesa_tpu.index.planner import Query as _Q

    store.query("gdelt", QUERY)  # warm
    # project the source event id (converter fids are md5 hashes): the
    # parity quantity stays a one-column identity set, gathered lazily
    # after the timed region like the headline's fid set
    queries = [_Q.cql(c, properties=["globalEventId"]) for c in cqls]
    t0 = time.perf_counter()
    results = store.query_many("gdelt", queries)
    pipe_s = (time.perf_counter() - t0) / reps
    dev_fps = n / pipe_s
    for i, (res, want) in enumerate(zip(results, wants)):
        got = set(res.columns["globalEventId"])
        if got != {str(j) for j in want}:
            return {
                "metric": "gdelt_real_format_throughput",
                "value": 0.0,
                "unit": "features/sec",
                "vs_baseline": 0.0,
                "error": f"parity_failure_query_{i}",
                "n": n,
            }
    return {
        "metric": "gdelt_real_format_throughput",
        "value": round(dev_fps, 1),
        "unit": "features/sec",
        "vs_baseline": round(dev_fps / cpu_fps, 3),
        "ingest_path": "57-column GDELT TSV -> premade converter -> bulk_ingest",
        "n": n,
        "reps": reps,
        "hits": int(len(wants[0])),
        "cpu_baseline_fps": round(cpu_fps, 1),
        "ingest_rec_per_sec": round(ec.success / ingest_s, 1),
        "query_ms_pipelined": round(pipe_s * 1000, 3),
    }


def run(n: int, reps: int) -> dict:
    x, y, t = synthesize(n)
    boxes, cqls = make_queries(reps)

    # --- CPU baseline (CQEngine stand-in) --------------------------------
    # Times the SAME jittered query stream the device path answers below.
    brute_force(x[:1000], y[:1000], t[:1000])  # warm
    wants = []
    t0 = time.perf_counter()
    for b in boxes:
        wants.append(brute_force(x, y, t, b))
    cpu_s = (time.perf_counter() - t0) / reps
    cpu_fps = n / cpu_s
    log(f"cpu baseline: {cpu_fps:,.0f} features/sec ({len(wants[0])} hits)")

    # --- device store path -----------------------------------------------
    from geomesa_tpu.geom.base import Point  # noqa: F401  (schema dep)
    from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
    from geomesa_tpu.schema.featuretype import parse_spec
    from geomesa_tpu.store.datastore import TpuDataStore

    store = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
    ft = parse_spec("gdelt", "dtg:Date,*geom:Point:srid=4326")
    store.create_schema(ft)
    # vectorized fixed-width fids: skips the object->unicode intern pass
    fids = np.char.add("f", np.arange(n).astype(f"<U{len(str(n - 1))}"))
    t0 = time.perf_counter()
    store.writer("gdelt").write_columns(
        {"__fid__": fids, "geom__x": x, "geom__y": y, "dtg": t}
    )
    ingest_s = time.perf_counter() - t0
    log(f"ingest: {n / ingest_s:,.0f} rec/sec")

    t0 = time.perf_counter()
    res = store.query("gdelt", QUERY)  # warm: device pack + compile
    warm_s = time.perf_counter() - t0
    log(f"warm query (pack+compile): {warm_s:.1f}s, {len(res.fids)} hits")
    if set(res.fids) != {f"f{i}" for i in wants[0]}:
        return {
            "metric": "gdelt_z3_bbox_time_filter_throughput",
            "value": 0.0,
            "unit": "features/sec",
            "vs_baseline": 0.0,
            "error": "parity_failure",
            "n": n,
        }

    # single-query (sync) latency: one device round trip per query
    t0 = time.perf_counter()
    lat_reps = min(3, reps)
    for _ in range(lat_reps):
        store.query("gdelt", QUERY)
    lat_s = (time.perf_counter() - t0) / lat_reps

    # pipelined query stream (BatchScanner analog): every query's device
    # work is dispatched before the first result is decoded, so the link
    # round trip amortizes across the stream. Queries project to fids only
    # (the parity quantity; the CPU baseline also produces just the index
    # set) — attribute columns are gathered on demand via projections.
    from geomesa_tpu.index.planner import Query as _Q

    queries = [_Q.cql(c, properties=[]) for c in cqls]
    t0 = time.perf_counter()
    results = store.query_many("gdelt", queries)
    pipe_s = (time.perf_counter() - t0) / reps
    dev_fps = n / pipe_s
    for i, (res, want) in enumerate(zip(results, wants)):
        if set(res.fids) != {f"f{j}" for j in want}:
            return {
                "metric": "gdelt_z3_bbox_time_filter_throughput",
                "value": 0.0,
                "unit": "features/sec",
                "vs_baseline": 0.0,
                "error": f"parity_failure_query_{i}",
                "n": n,
            }

    core = {
        "metric": "gdelt_z3_bbox_time_filter_throughput",
        "value": round(dev_fps, 1),
        "unit": "features/sec",
        "vs_baseline": round(dev_fps / cpu_fps, 3),
        "baseline": "numpy-fullscan (CQEngine stand-in, stronger than GeoCQEngine)",
        "n": n,
        "reps": reps,
        "hits": int(len(wants[0])),
        "cpu_baseline_fps": round(cpu_fps, 1),
        "ingest_rec_per_sec": round(n / ingest_s, 1),
        "query_ms": round(lat_s * 1000, 3),
        "query_ms_pipelined": round(pipe_s * 1000, 3),
    }
    if os.environ.get("GEOMESA_SEEK") == "0":
        return core  # the headline stream already was the device scan
    return {**device_stream(store, queries, wants, n, reps, cpu_fps), **core}


def device_stream(store, queries, wants, n: int, reps: int, cpu_fps: float) -> dict:
    """The SAME query stream answered by the device scan (GEOMESA_SEEK=0):
    the batched exact path fuses all queries into one device execution
    per segment. Recorded next to the cost-chosen headline, which may be
    the host seek. A failure here fails the run."""
    saved = os.environ.get("GEOMESA_SEEK")
    os.environ["GEOMESA_SEEK"] = "0"
    try:
        # warm until the adaptive run capacities stop changing: rcap
        # learning happens at resolve time, and a changed rcap keys a
        # fresh jit compile — which must land here, not in the timing
        t0 = time.perf_counter()
        prev_rcaps = None
        for _ in range(4):
            store.query_many("gdelt", queries)
            rcaps = {
                id(s): (s._rcap, s._span_cap)
                for d in store.executor._cache.values()
                for s in d[1].segments
            }
            if rcaps == prev_rcaps:
                break
            prev_rcaps = rcaps
        dwarm_s = time.perf_counter() - t0
        log(f"device stream warm (pack+compile): {dwarm_s:.1f}s")
        t0 = time.perf_counter()
        dres = store.query_many("gdelt", queries)
        dpipe_s = (time.perf_counter() - t0) / reps
    finally:
        if saved is None:
            os.environ.pop("GEOMESA_SEEK", None)
        else:
            os.environ["GEOMESA_SEEK"] = saved
    dok = all(
        set(r.fids) == {f"f{j}" for j in w} for r, w in zip(dres, wants)
    )
    out = {
        "device_path_fps": round(n / dpipe_s, 1),
        "device_path_vs_baseline": round(n / dpipe_s / cpu_fps, 3),
        "device_query_ms_pipelined": round(dpipe_s * 1000, 3),
        "device_parity": bool(dok),
        "device_warm_s": round(dwarm_s, 1),
    }
    if not dok:
        out["error"] = "device_parity_failure"
    log(
        f"device stream: {n / dpipe_s:,.0f} features/sec "
        f"({dpipe_s * 1000:.1f} ms/query, parity={dok})"
    )
    return out


def main():
    from geomesa_tpu.parallel.mesh import enable_compile_cache
    from geomesa_tpu.utils.audit import robustness_metrics
    from geomesa_tpu.utils.malloc import retain_freed_memory

    dev = device_fields()
    if dev["platform"] != "tpu":
        log(f"no TPU (jax gives {dev['platform']}); nothing to measure")
        return 1
    enable_compile_cache()
    retain_freed_memory()  # page re-faulting throttles large-N ingest otherwise
    smoke = os.environ.get("GEOMESA_BENCH_SMOKE", "") not in ("", "0")
    n = int(os.environ.get("GEOMESA_BENCH_N", 0)) or (
        200_000 if smoke else 20_000_000
    )
    reps = int(os.environ.get("GEOMESA_BENCH_REPS", 3 if smoke else 20))
    real = os.environ.get("GEOMESA_BENCH_REAL", "") not in ("", "0")
    payload = run_real(n, reps) if real else run(n, reps)
    payload.update(dev)
    degraded = robustness_metrics().counter("degrade.device_to_host")
    payload["degrade_device_to_host"] = degraded
    if degraded and "error" not in payload:
        payload["error"] = "device_to_host_degradation"
    emit(payload)
    return 1 if payload.get("error") else 0


if __name__ == "__main__":
    raise SystemExit(main())
