"""Device & compiler telemetry: recompile accounting, transfer byte
counters, padding efficiency, and best-effort HBM gauges.

PR 2 made every query a span tree and every boundary a metric, but the
device layer underneath stayed a black box: ~30 ``jax.jit`` sites behind
shape-bucketed caches, where a silent recompile or a padding blow-up
costs more than anything the host-side spans can see. This module is the
measurement substrate underneath those spans:

* ``instrumented_jit(name, fn, **jit_kw)`` — the ONLY sanctioned way to
  jit in ``geomesa_tpu/`` (enforced by scripts/lint_observability.sh).
  It models the jit cache with the argument signature (shapes + dtypes +
  static values) and, on each first-seen signature, wraps the triggering
  call in an ``xla.compile`` span so the compile attributes to the QUERY
  that paid for it, bumps ``xla.compile.<name>`` / ``xla.compile.total``
  counters, and feeds the ``xla.compile`` wall-time timer. A per-kernel
  cache-entry gauge (``xla.cache.<name>.entries``) tracks bucket growth.
* monotone ``device.h2d.bytes`` / ``device.d2h.bytes`` counters, fed by
  the dispatch/fetch boundaries (parallel/mesh.py shard_array/replicate,
  parallel/executor._np_local) that already carry per-trace byte attrs.
* padding-efficiency gauges (``device.pad.*``): rows used vs. the pow2
  capacity bucket of the latest segment upload, plus monotone row
  totals so a fleet-wide pad regression shows up in rate() form.
* best-effort HBM gauges: ``device.hbm.live_bytes`` from
  ``jax.live_arrays()`` and ``device.hbm.bytes_in_use`` /
  ``device.hbm.peak_bytes_in_use`` from ``Device.memory_stats()`` when
  the backend provides it (TPU/GPU do; CPU reads 0).

Everything lands in one process-wide ``MetricsRegistry``
(``devstats_metrics()``, the ``robustness_metrics()`` posture) so the
existing reporters/exposition carry it for free; web.py merges it into
``GET /metrics`` and serves a structured ``GET /debug/device``.

Per-query attribution rides the "cost receipt": ``receipt_snapshot()``
before execution, ``receipt_since()`` after — the delta (recompiles
triggered, bytes moved each way, current pad ratio) attaches to the
query's root span, the QueryEvent audit row, and therefore the
slow-query log. Counters are process-wide, so under concurrent query
streams a receipt is an upper bound on what THIS query caused — exact
on the single-stream bench/CI paths the perf gate
(scripts/bench_gate.py) runs.
"""

from __future__ import annotations

import contextvars
import threading
import time
import weakref
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

from geomesa_tpu.utils import trace
from geomesa_tpu.utils.audit import MetricsRegistry

_DEVSTATS: Optional[MetricsRegistry] = None
_DEVSTATS_LOCK = threading.Lock()

# kernel name -> _KernelStats; shared by every instrumented_jit wrapper
# carrying that name (the executor builds one wrapper per cache key, but
# accounting is per KERNEL — that is the unit an operator reasons about)
_KERNELS: Dict[str, "_KernelStats"] = {}
_KERNELS_LOCK = threading.Lock()


def devstats_metrics() -> MetricsRegistry:
    """Process-wide device/compiler telemetry registry:

        xla.compile.<name>        compiles per kernel name (counter)
        xla.compile.total         compiles across every kernel (counter)
        xla.compile               compile wall time (timer percentiles)
        xla.cache.<name>.entries  live cache signatures per kernel (gauge)
        xla.cache.entries         sum across kernels (gauge)
        device.h2d.bytes          host->device bytes, monotone (counter)
        device.d2h.bytes          device->host bytes, monotone (counter)
        device.pad.rows_used      latest segment upload's real rows (gauge)
        device.pad.rows_capacity  its pow2 capacity bucket (gauge)
        device.pad.ratio          used / capacity of that upload (gauge)
        device.pad.rows_used_total / rows_padded_total   monotone totals
        device.hbm.live_bytes     sum of jax.live_arrays() nbytes (gauge)
        device.hbm.bytes_in_use / peak_bytes_in_use      backend stats
        density.band.overflow     density bands fetched as a bitmap (counter)

    One shared registry rather than per-store for the same reason as
    robustness_metrics(): the jit caches and the mesh dispatch helpers
    live below the store facade and are shared across stores."""
    global _DEVSTATS
    with _DEVSTATS_LOCK:
        if _DEVSTATS is None:
            reg = MetricsRegistry()
            reg.gauge_fn("xla.cache.entries", _total_cache_entries)
            reg.gauge_fn("device.hbm.live_bytes", _live_array_bytes)
            reg.gauge_fn("device.hbm.bytes_in_use",
                         lambda: _memory_stat("bytes_in_use"))
            reg.gauge_fn("device.hbm.peak_bytes_in_use",
                         lambda: _memory_stat("peak_bytes_in_use"))
            _DEVSTATS = reg
        return _DEVSTATS


def _total_cache_entries() -> int:
    with _KERNELS_LOCK:
        stats = list(_KERNELS.values())
    return sum(s.cache_entries() for s in stats)


def _live_array_bytes() -> int:
    """Best-effort HBM residency: bytes held by live jax arrays. On CPU
    this is host memory, but the shape of the number (mirror growth,
    leak detection) is what the gauge is for."""
    import jax

    total = 0
    for a in jax.live_arrays():
        try:
            total += int(a.nbytes)
        except Exception:  # noqa: BLE001 - a deleted/donated array mid-walk
            pass
    return total


def _memory_stat(key: str) -> int:
    """Sum one Device.memory_stats() field across devices; backends
    without stats (CPU) read 0 rather than failing the snapshot."""
    import jax

    total = 0
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 - backend without the API
            stats = None
        if stats:
            total += int(stats.get(key, 0))
    return total


class _SigSet(set):
    """One wrapper's seen-signature set. A plain ``set`` cannot be
    weakly referenced; this subclass can, so the kernel aggregate holds
    them via a WeakSet and a dropped wrapper's buckets leave the
    cache-entry gauge. Identity hashing (sets are unhashable by value)
    is exactly right: each wrapper's set is a distinct member."""

    __hash__ = object.__hash__


class _KernelStats:
    """Per-kernel-NAME aggregation over per-WRAPPER signature sets.

    jit's compilation cache is per wrapper, and the executor deliberately
    builds many wrappers per kernel (one per capacity bucket / mode /
    mesh), so the signature model must be per wrapper too: a new rcap
    bucket's first call is a REAL multi-second compile even though the
    input shapes were seen by a sibling — counting it at the name level
    only would hide exactly the silent recompiles this module exists to
    expose. Counters and the cache-entry gauge aggregate across the
    name's live wrappers (the operator's unit of reasoning)."""

    __slots__ = ("name", "compiles", "lock", "wrappers")

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.lock = threading.Lock()
        self.wrappers: "weakref.WeakSet[_SigSet]" = weakref.WeakSet()

    def cache_entries(self) -> int:
        return sum(len(s) for s in self.wrappers)


def _kernel_stats(name: str) -> _KernelStats:
    with _KERNELS_LOCK:
        st = _KERNELS.get(name)
        if st is None:
            st = _KernelStats(name)
            _KERNELS[name] = st
            devstats_metrics().gauge_fn(
                f"xla.cache.{name}.entries",
                lambda s=st: s.cache_entries(),
            )
        return st


def _signature(args: tuple, kwargs: dict) -> tuple:
    """Hashable stand-in for jit's cache key: shape+dtype per array-like
    argument, the value itself for hashable statics, the type name
    otherwise. Mirrors shape-bucketed specialization exactly for the
    all-array call sites this repo has; weak-type/layout re-traces would
    undercount, never overcount."""
    sig = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
        else:
            try:
                hash(a)
                sig.append(("v", a))
            except TypeError:
                sig.append(("t", type(a).__name__))
    for k in sorted(kwargs):
        sig.append((k, _signature((kwargs[k],), {})))
    return tuple(sig)


def instrumented_jit(name: str, fn, **jit_kw):
    """``jax.jit`` with compile accounting — the sanctioned jit wrapper.

    Returns a callable with the jitted function's behavior; each call
    whose argument signature THIS WRAPPER has not seen is treated as a
    compile (the model mirrors jit's per-wrapper cache — a sibling
    wrapper of the same kernel name, e.g. a new capacity bucket, pays
    its own real compiles and is counted for them): it runs inside an
    ``xla.compile`` span (attributing the stall to the query that
    triggered it), bumps the per-kernel and total compile counters, and
    records the call's wall time in the ``xla.compile`` timer (compile
    dominates first-call latency; the timer is an attribution aid, not
    a precise compiler clock). Warm calls pay one set lookup.
    """
    import jax

    jitted = jax.jit(fn, **jit_kw)
    stats = _kernel_stats(name)
    reg = devstats_metrics()
    seen = _SigSet()
    with stats.lock:
        stats.wrappers.add(seen)

    def call(*args, **kwargs):
        sig = _signature(args, kwargs)
        with stats.lock:
            fresh = sig not in seen
            if fresh:
                seen.add(sig)
                stats.compiles += 1
        if not fresh:
            return jitted(*args, **kwargs)
        reg.inc(f"xla.compile.{name}")
        reg.inc("xla.compile.total")
        _collect("recompiles", 1)
        t0 = time.perf_counter()
        with trace.span("xla.compile", kernel=name):
            out = jitted(*args, **kwargs)
        reg.update_timer("xla.compile", time.perf_counter() - t0)
        return out

    call.__name__ = f"instrumented_jit[{name}]"
    call._jitted = jitted  # escape hatch for lower()/cache introspection
    call._devstats = stats
    return call


# context-local receipt collectors: the stack of dicts that transfers
# and compiles counted from THIS thread/context also accumulate into.
# Unlike the process-wide receipt window (receipt_since), a collector is
# EXACT under concurrency — the shard coordinator (parallel/shards.py)
# wraps each per-shard scan in one so a hedged loser's bytes can never
# land in the winner's receipt.
_COLLECTORS: contextvars.ContextVar[Tuple[Dict[str, int], ...]] = (
    contextvars.ContextVar("geomesa_tpu_receipt_collectors", default=())
)
# trace.wrap copies the caller's context into worker threads, so an
# OUTER collector can legitimately be fed from several threads at once
# (e.g. collecting() around a sharded query) — the fold must not lose
# increments to interleaved read-modify-writes
_COLLECT_LOCK = threading.Lock()


@contextmanager
def collecting(out: Optional[Dict[str, int]] = None):
    """Collect this context's device costs into ``out`` (keys
    ``h2d_bytes`` / ``d2h_bytes`` / ``recompiles``), in ADDITION to the
    process-wide counters. Nests; each active collector sees every
    event. Yields the dict."""
    out = {} if out is None else out
    token = _COLLECTORS.set(_COLLECTORS.get() + (out,))
    try:
        yield out
    finally:
        _COLLECTORS.reset(token)


def _collect(key: str, n: int) -> None:
    outs = _COLLECTORS.get()
    if not outs:
        return
    with _COLLECT_LOCK:
        for out in outs:
            out[key] = out.get(key, 0) + n


def count_h2d(nbytes: int) -> None:
    """Fold one host->device transfer into the monotone byte counter
    (called from the device.dispatch boundary, parallel/mesh.py)."""
    if nbytes:
        devstats_metrics().inc("device.h2d.bytes", int(nbytes))
        _collect("h2d_bytes", int(nbytes))


def count_d2h(nbytes: int) -> None:
    """Fold one device->host transfer into the monotone byte counter
    (called from the device.fetch boundary, parallel/executor.py)."""
    if nbytes:
        devstats_metrics().inc("device.d2h.bytes", int(nbytes))
        _collect("d2h_bytes", int(nbytes))


def record_pad(rows_used: int, rows_capacity: int, kind: str = "") -> None:
    """Padding efficiency of one segment upload: real rows vs. the pow2
    capacity bucket actually dispatched. Gauges show the latest upload
    (the "is THIS mirror bloated" question); the monotone totals let a
    dashboard rate() the fleet-wide pad overhead."""
    reg = devstats_metrics()
    reg.set_gauge("device.pad.rows_used", rows_used)
    reg.set_gauge("device.pad.rows_capacity", rows_capacity)
    if rows_capacity > 0:
        reg.set_gauge("device.pad.ratio", rows_used / rows_capacity)
    # monotone upload-event count: receipts use its delta to tell "this
    # query uploaded a segment" from "the gauge is another query's"
    reg.inc("device.pad.events")
    reg.inc("device.pad.rows_used_total", int(rows_used))
    reg.inc("device.pad.rows_padded_total",
            max(0, int(rows_capacity) - int(rows_used)))
    if kind:
        trace.event("device.pad", kind=kind, used=int(rows_used),
                    capacity=int(rows_capacity))


# -- per-query cost receipt ---------------------------------------------------


_RECEIPT_COUNTERS = (
    ("recompiles", "xla.compile.total"),
    ("h2d_bytes", "device.h2d.bytes"),
    ("d2h_bytes", "device.d2h.bytes"),
    ("pad_events", "device.pad.events"),
)


def receipt_snapshot() -> Dict[str, int]:
    """Cheap point-in-time read of the receipt counters (three dict
    lookups under the registry lock — safe on the per-query hot path)."""
    reg = devstats_metrics()
    return {k: reg.counter(c) for k, c in _RECEIPT_COUNTERS}


def receipt_since(before: Dict[str, int]) -> Dict[str, Any]:
    """The per-query cost receipt: counter deltas since ``before``.
    ``pad_ratio`` reports the pad gauge only when THIS window uploaded a
    segment (the pad-event counter moved) — a warm query must not
    inherit another query's mirror efficiency — and 0.0 otherwise.
    Process-wide counters make the deltas an upper bound under
    concurrent streams, exact single-stream."""
    now = receipt_snapshot()
    out: Dict[str, Any] = {
        k: now[k] - before.get(k, 0) for k, _ in _RECEIPT_COUNTERS
    }
    uploaded = out.pop("pad_events") > 0
    out["pad_ratio"] = (
        round(devstats_metrics().gauge("device.pad.ratio"), 4)
        if uploaded else 0.0
    )
    return out


def device_debug() -> Dict[str, Any]:
    """The GET /debug/device payload: backend identity, per-kernel
    compile/cache accounting, transfer + padding counters, HBM gauges."""
    import jax

    reg = devstats_metrics()
    counters, gauges, _timers, totals = reg.snapshot()
    with _KERNELS_LOCK:
        stats = list(_KERNELS.items())
    kernels = {
        name: {
            "cache_entries": st.cache_entries(),
            "compiles": st.compiles,
        }
        for name, st in sorted(stats)
    }
    compile_count, compile_sum_s = totals.get("xla.compile", (0, 0.0))
    try:
        # lazy: the join subsystem may never have loaded in this process
        from geomesa_tpu.ops.join import join_debug

        join_block = join_debug()
    except Exception:  # noqa: BLE001 - debug page must render regardless
        join_block = {}
    try:
        # lazy: same rule for the aggregate pyramid cache
        from geomesa_tpu.ops.pyramid import agg_debug

        agg_block = agg_debug()
    except Exception:  # noqa: BLE001 - debug page must render regardless
        agg_block = {}
    try:
        backend = jax.default_backend()
        n_devices = len(jax.devices())
    except Exception as e:  # noqa: BLE001 - backend init failure is still a page
        backend = f"unavailable: {e}"
        n_devices = 0
    return {
        "backend": backend,
        "device_count": n_devices,
        "kernels": kernels,
        "compile": {
            "total": counters.get("xla.compile.total", 0),
            "wall_s": round(compile_sum_s, 4),
            "count": compile_count,
        },
        "transfer": {
            "h2d_bytes": counters.get("device.h2d.bytes", 0),
            "d2h_bytes": counters.get("device.d2h.bytes", 0),
        },
        "pad": {
            "rows_used": gauges.get("device.pad.rows_used", 0),
            "rows_capacity": gauges.get("device.pad.rows_capacity", 0),
            "ratio": gauges.get("device.pad.ratio", 0.0),
            "rows_used_total": counters.get("device.pad.rows_used_total", 0),
            "rows_padded_total": counters.get(
                "device.pad.rows_padded_total", 0
            ),
        },
        "hbm": {
            "live_bytes": gauges.get("device.hbm.live_bytes", 0),
            "bytes_in_use": gauges.get("device.hbm.bytes_in_use", 0),
            "peak_bytes_in_use": gauges.get(
                "device.hbm.peak_bytes_in_use", 0
            ),
        },
        # spatial-join telemetry (ops/join.py): build-cache occupancy +
        # hit/miss counters, bucket skew histogram, split/pair counters
        "join": join_block,
        # aggregate pyramid cache (ops/pyramid.py): entries/bytes,
        # hit/miss/build/eviction counters, latest pyramid shape
        "agg": agg_block,
        # density push-down (parallel/executor.density_scan): grids whose
        # band overflowed its per-shard buffer and came back as a bitmap
        "density": {
            "band_overflow": counters.get("density.band.overflow", 0),
        },
        # cross-query coalescer reach (parallel/batch.py admission
        # groups + parallel/executor.dispatch_coalesced routing): how
        # many groups formed, the pow2 group-size histogram (all-1s
        # means the window never fills), how many member plans rode a
        # stacked-mask sweep vs fell to the dispatch_many batch paths,
        # and the mesh size the sweeps compiled for — the timeline/SLO
        # layer's "is the coalescer earning its window" signal
        "coalesce": {
            "groups": counters.get("batch.coalesce.groups", 0),
            "members": counters.get("batch.coalesce.members", 0),
            "stacked_plans": counters.get("batch.coalesce.plans.stacked", 0),
            "rest_plans": counters.get("batch.coalesce.plans.rest", 0),
            "devices": gauges.get("batch.coalesce.devices", 0),
            # NUMERIC bucket order: lexical sort would interleave 16/32
            # between 1 and 2, scrambling exactly the large-group tail
            # the histogram exists to show
            "group_pow2": {
                k.rsplit(".", 1)[1]: counters[k]
                for k in sorted(
                    (
                        k for k in counters
                        if k.startswith("batch.coalesce.group.pow2.")
                    ),
                    key=lambda k: int(k.rsplit(".", 1)[1]),
                )
            },
        },
    }
