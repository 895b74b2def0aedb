"""One run of one cell: set-up, the measured window, the check."""

from __future__ import annotations

import gc
import importlib
import math
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark.lib import loop, reference, spec as spec_mod, tracing, traffic

# the sample of finished requests the check compares is drawn with this
# stream of the seed (kept apart from the traffic's permutation)
_SAMPLE_STREAM = 0x5EED


class _JaxEvents:
    """Counts JAX's lowerings and persistent-cache misses (a lowering in
    the window is a compile, or a cache load, that set-up missed)."""

    def __init__(self):
        import jax

        self.lowered = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.lowered += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


_EVENTS: Optional[_JaxEvents] = None


def _jax_events() -> _JaxEvents:
    global _EVENTS
    if _EVENTS is None:
        _EVENTS = _JaxEvents()
    return _EVENTS


def _degraded() -> int:
    """Queries the host answered after a device failure, so far."""
    from geomesa_tpu.utils.audit import robustness_metrics

    counters = robustness_metrics().snapshot()[0]
    return int(sum(v for k, v in counters.items() if k.startswith("degrade.")))


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def _sample(pool_len: int, seed: int, share: float) -> set:
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, _SAMPLE_STREAM])
    return set(np.flatnonzero(rng.random(pool_len) < share).tolist())


def _percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def build_store(cell, ds, devices):
    from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
    from geomesa_tpu.schema.featuretype import parse_spec
    from geomesa_tpu.store.datastore import TpuDataStore

    cfg = cell.config
    store = TpuDataStore(executor=TpuScanExecutor(default_mesh(devices)))
    store.create_schema(parse_spec(cfg["type_name"], cfg["spec"]))
    store.writer(cfg["type_name"]).write_columns(ds.columns)
    return store


def check(recs, kind, ref: "reference.Reference") -> Dict:
    """The compared number of the kept outputs against the reference."""
    off = 0
    checked = 0
    for r in recs:
        if r.output is None:
            continue
        checked += 1
        off += kind.off(r.output, kind.answer(ref, r.req), r.req, ref)
    return {"off": off, "checked": checked}


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, control: bool = False, rows: Optional[int] = None,
             log: Callable[[str], None] = lambda m: sys.stderr.write(m + "\n"),
             store_hook: Optional[Callable] = None) -> Dict:
    """Set up, measure, check; returns the result object.

    ``rows`` overrides the configuration's row count (the CPU tests), and
    ``store_hook(store)`` may wrap the store before the window (the fault
    tests break the timed path there). ``control`` puts the float32
    reference in the program's place: no store is built, the window's
    requests are answered by the reference at float32, and the same check
    judges them."""
    import jax

    from geomesa_tpu.parallel.mesh import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program goes to the persistent cache, however fast it compiled:
    # a second run of the cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    events = _jax_events()
    cfg, tr = cell.config, cell.traffic
    kind = traffic.kind(tr)
    name = cfg["type_name"]
    n_rows = int(rows or cfg["rows"])
    clients = int(tr["clients"])
    phases: Dict[str, float] = {}

    t = time.perf_counter()
    gen = importlib.import_module(f"benchmark.datasets.{cfg['generator']}")
    ds = gen.make(cfg, n_rows, seed)
    phases["generate_s"] = time.perf_counter() - t

    store = None
    if control:
        ref32 = reference.Reference(ds.ref, precision="f32")
        serve = lambda req: kind.served(ref32, req)  # noqa: E731
    else:
        t = time.perf_counter()
        store = build_store(cell, ds, devices)
        phases["ingest_s"] = time.perf_counter() - t
        serve = lambda req: store.query(name, kind.query(req))  # noqa: E731

        warm = traffic.pool(cfg, tr, warm=True)
        t = time.perf_counter()
        serve(warm[0])  # mirror upload + first compile
        phases["upload_s"] = time.perf_counter() - t
        t = time.perf_counter()
        per_pass = clients * int(tr["warmup_per_client"])
        for i in range(len(warm) // per_pass):
            before = events.lowered
            batch = warm[i * per_pass:(i + 1) * per_pass]
            loop.run_clients(serve, kind.read, [batch[c::clients] for c in range(clients)],
                             math.inf, lambda r: False, finite=True)
            if events.lowered == before:
                break
        phases["warm_s"] = time.perf_counter() - t
        if store_hook is not None:
            store = store_hook(store)
    ds.columns = None  # the store holds what it needs; ref keeps the rest

    pool = traffic.pool(cfg, tr)
    streams = traffic.streams(pool, cfg, tr, seed)
    sampled = _sample(len(pool), seed, float(tr["check_share"]))
    keep = lambda req: req.pool_index in sampled  # noqa: E731

    lowered0, misses0, degraded0 = events.lowered, events.cache_misses, _degraded()
    exporter = logdir = None
    if trace:
        from geomesa_tpu.utils import trace as trace_mod

        exporter = trace_mod.InMemoryTraceExporter(capacity=1 << 20)
        trace_mod.install(exporter)
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(logdir)
    setup_s = time.perf_counter() - t_start
    anchor_wall_ns = time.time_ns()
    with jax.profiler.TraceAnnotation(tracing.ANCHOR):
        t_open = time.perf_counter()
        # the control may answer faster than the pool lasts: it then stops
        per_client = loop.run_clients(serve, kind.read, streams, t_open + seconds, keep,
                                      finite=control)
        t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
        trace_mod.uninstall(exporter)
    t_close = t_open + seconds
    recs = [r for c in per_client for r in c]
    done = [r for r in recs if r.t1 <= t_close]
    ok = [r for r in done if r.error is None]
    errors = [r for r in recs if r.error is not None]
    degraded = _degraded() - degraded0
    breaker = getattr(getattr(store, "executor", None), "breaker", None)
    breaker_open = breaker is not None and getattr(breaker, "state", "closed") != "closed"
    failed = len(errors) + degraded + (1 if breaker_open else 0)
    peak = _peak_bytes(devices)
    lowered = events.lowered - lowered0
    misses = events.cache_misses - misses0
    paths: Dict[str, int] = {}
    for r in recs:
        paths[r.scan_path or "error"] = paths.get(r.scan_path or "error", 0) + 1
    log(f"setup_s {setup_s:.3f} = " + ", ".join(f"{k} {v:.3f}" for k, v in phases.items())
        + f"; compile cache {cache_dir}")
    log(f"window {seconds} s: {len(recs)} sent, {len(done)} done in window, "
        f"{len(errors)} errors, {degraded} degraded, breaker open {breaker_open}; "
        f"lowerings in window {lowered}, cache misses in window {misses}")
    log(f"scan paths {paths}")
    for r in errors[:3]:
        log(f"error: {r.error}")

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    lat = [(r.t1 - r.t0) * 1000.0 for r in ok]
    if lat:
        log(f"latency ms p50 {_percentile(lat, 50)} p95 {_percentile(lat, 95)}")
    e2e = {
        "queries_per_s": len(ok) / seconds,
        "hbm_bytes_per_row": peak / n_rows,
        "setup_s": setup_s,
    }
    metrics: Dict[str, Dict] = {}
    breakdown = None
    ctx = None
    if not trace:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        roots = list(exporter.traces)
        dev = tracing.reduce_trace(tracing.find_xspace(logdir), anchor_wall_ns)
        shutil.rmtree(logdir, ignore_errors=True)
        if dev is not None:
            device["busy_s"] = dev.busy_s
            device["window_s"] = dev.window_s
            ops = sorted(dev.op_seconds.items(), key=lambda kv: -kv[1])[:10]
            # the contract's breakdown: the top device ops, and the longest
            # idle gaps named by what the host was doing
            breakdown = {"device_ops": [[k, v] for k, v in ops],
                         "idle_gaps": tracing.label_gaps(dev.gaps, roots)}
            mods = sorted(dev.module_seconds.items(), key=lambda kv: -kv[1])[:10]
            log(f"device programs {mods}")
        else:
            device["busy_s"] = 0.0
            device["window_s"] = t_end - t_open
        ctx = SimpleNamespace(records=[r for r in recs if r.error is None], spans=roots,
                              device=dev, window_s=t_end - t_open, reference=None,
                              peaks=spec_mod.peaks(devices[0].device_kind)
                              if devices[0].platform == "tpu" else None,
                              self_ms=tracing.self_ms_by_name(roots))
        log("span self ms: " + ", ".join(
            f"{k} {v:.1f}" for k, v in sorted(ctx.self_ms.items(), key=lambda kv: -kv[1])[:12]))

    # the program's state goes before the reference runs
    del store, serve, per_client
    gc.collect()
    t = time.perf_counter()
    ref = reference.Reference(ds.ref)
    got = check(recs, kind, ref)
    checks = {kind.CHECK: {"value": got["off"], "limit": 0}}
    log(f"checked {got['checked']} requests against the reference "
        f"in {time.perf_counter() - t:.3f} s")
    if trace:
        ctx.reference = ref
        for m in cell.per_layer:
            v = spec_mod.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = got["checked"] > 0 and all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": bool(correct), "attempted": len(recs), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
