"""End-to-end query tracing: a lightweight span-tree tracer.

The reference attributes per-query latency through Dropwizard timers and
MethodProfiling (utils/stats/MethodProfiling.scala:1-222), which answers
"how slow is planning on average" but not "where did THIS query spend its
time". GPU/TPU engines need the per-stage split (kernel vs transfer vs
host post-filter — arxiv 2203.14362 §5) to attribute anything, so this
module provides what process-wide counters cannot: one tree of timed
spans per query, from plan through range decomposition, block scans,
device dispatch/fetch (or the degradation event) to the post-filter.

Design constraints, in order:

1. **Free when off.** With no exporter installed and no active trace,
   ``span()`` returns a shared no-op singleton — two reads and no
   allocation — so the hooks can sit on per-block and per-RPC paths
   (the fault_point posture, utils/faults.py:44-47).
2. **Context propagation.** The active span lives in a ``contextvars``
   ContextVar, so nesting needs no plumbing and ``wrap()`` carries a
   trace across the executor's / server's worker threads.
3. **Whole trees, not span streams.** Exporters receive the ROOT span
   once it closes, children attached — consumers (the slow-query log,
   /debug/traces, tests) always see a complete tree and never splice.

Usage::

    from geomesa_tpu.utils import trace

    with trace.exporting(trace.InMemoryTraceExporter()) as ring:
        with trace.span("query", type="gdelt") as root:
            with trace.span("plan"):
                ...
            trace.event("degrade.device_to_host", error="device lost")
    ring.traces[-1].render()

Cross-process correlation: ``current_trace_id()`` rides in the netlog
message envelope, and the broker opens its server-side spans with that
``trace_id`` — one id joins client and broker work (stream/netlog.py).
"""

from __future__ import annotations

import contextvars
import json
import logging
import sys
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


class Span:
    """One timed operation: name, [start, end), attributes, point-in-time
    events, and child spans. Times are perf_counter-based; ``start_ms``
    is the epoch wall clock for log correlation."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "start_ms",
        "duration_ms", "attributes", "events", "children", "_t0",
    )

    def __init__(self, name: str, trace_id: str, parent_id: Optional[str]):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.start_ms = time.time() * 1000.0
        self.duration_ms: float = 0.0
        self.attributes: Dict[str, Any] = {}
        self.events: List[Dict[str, Any]] = []
        self.children: List["Span"] = []
        self._t0 = time.perf_counter()

    # real spans record; the no-op singleton overrides this to False so
    # callers can skip computing expensive attribute values
    recording = True

    def set_attr(self, key: str, value: Any) -> "Span":
        self.attributes[key] = value
        return self

    def add_event(self, name: str, **attrs: Any) -> "Span":
        ev: Dict[str, Any] = {
            "name": name,
            "t_ms": (time.perf_counter() - self._t0) * 1000.0,
        }
        if attrs:
            ev.update(attrs)
        self.events.append(ev)
        return self

    def finish(self) -> None:
        self.duration_ms = (time.perf_counter() - self._t0) * 1000.0

    @property
    def self_time_ms(self) -> float:
        """Duration minus DIRECT children's durations (time attributable
        to this span's own work)."""
        return max(
            0.0, self.duration_ms - sum(c.duration_ms for c in self.children)
        )

    def walk(self):
        """Yield this span and every descendant, depth-first."""
        yield self
        for c in self.children:
            yield from c.walk()

    def find(self, name: str) -> List["Span"]:
        return [s for s in self.walk() if s.name == name]

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "start_ms": self.start_ms,
            "duration_ms": self.duration_ms,
        }
        if self.parent_id:
            out["parent_id"] = self.parent_id
        if self.attributes:
            out["attributes"] = dict(self.attributes)
        if self.events:
            out["events"] = list(self.events)
        if self.children:
            out["children"] = [c.to_dict() for c in self.children]
        return out

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Span":
        """Inverse of ``to_dict`` — rebuilds a span tree from its wire
        form (the fleet trace-stitching trailer, parallel/fleet.py).
        Ids and timings are kept verbatim; the caller re-anchors wall
        times via ``graft`` (a remote clock is never trusted as-is)."""
        sp = cls.__new__(cls)
        sp.name = str(d.get("name", ""))
        sp.trace_id = str(d.get("trace_id", ""))
        sp.span_id = str(d.get("span_id") or _new_id())
        sp.parent_id = d.get("parent_id")
        sp.start_ms = float(d.get("start_ms", 0.0))
        sp.duration_ms = float(d.get("duration_ms", 0.0))
        sp.attributes = dict(d.get("attributes") or {})
        sp.events = list(d.get("events") or [])
        sp.children = [cls.from_dict(c) for c in d.get("children") or ()]
        sp._t0 = 0.0  # deserialized spans are closed; never re-timed
        return sp

    def render(self, indent: int = 0) -> str:
        """Human-readable indented tree (the Explainer's indentation
        idiom, index/planner.py Explainer)."""
        pad = "  " * indent
        attrs = ""
        if self.attributes:
            short = {
                k: v for k, v in self.attributes.items()
                if not isinstance(v, str) or len(v) <= 64
            }
            if short:
                attrs = " " + json.dumps(short, default=str, sort_keys=True)
        lines = [f"{pad}{self.name} {self.duration_ms:.2f}ms{attrs}"]
        for ev in self.events:
            extra = {k: v for k, v in ev.items() if k not in ("name", "t_ms")}
            tail = f" {json.dumps(extra, default=str)}" if extra else ""
            lines.append(f"{pad}  ! {ev['name']} @{ev['t_ms']:.2f}ms{tail}")
        for c in self.children:
            lines.append(c.render(indent + 1))
        return "\n".join(lines)


class _NoopSpan:
    """Shared do-nothing span/context-manager: what ``span()`` hands out
    when nothing is listening. Every method is a cheap no-op so call
    sites never branch."""

    __slots__ = ()
    recording = False
    name = ""
    trace_id = ""
    span_id = ""
    duration_ms = 0.0
    self_time_ms = 0.0
    attributes: Dict[str, Any] = {}
    events: List[Dict[str, Any]] = []
    children: List[Span] = []

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_attr(self, key, value) -> "_NoopSpan":
        return self

    def add_event(self, name, **attrs) -> "_NoopSpan":
        return self

    def walk(self):
        return iter(())

    def find(self, name):
        return []

    def render(self, indent: int = 0) -> str:
        return ""

    def to_dict(self):
        return {}


NOOP = _NoopSpan()

_CURRENT: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "geomesa_tpu_trace_span", default=None
)
_EXPORTERS: List["TraceExporter"] = []
_EXPORTERS_LOCK = threading.Lock()
_log = logging.getLogger("geomesa_tpu.trace")


# jax.profiler.TraceAnnotation once resolved; None until jax is loaded,
# False where it cannot be imported
_ANNOTATION: Any = None


def _annotation():
    """The profiler's host annotation class, or None. Resolved once jax is
    loaded: a process that never imported jax has no device trace to
    share a clock with, and a span must not be what imports it."""
    global _ANNOTATION
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION or None


class _SpanContext:
    """The live edition of ``span()``: enters a new Span as the current
    context, exports the tree from the root's __exit__.

    It also enters a ``jax.profiler.TraceAnnotation`` of the span's name
    for the span's lifetime, so a ``jax.profiler.trace`` shows every live
    span on its host thread, on the same clock as the device's XLA ops
    (no cost beyond the annotation's own when no profiler runs)."""

    __slots__ = ("span", "_token", "_ann")

    def __init__(self, name: str, parent: Optional[Span],
                 trace_id: Optional[str], attrs: Dict[str, Any]):
        if parent is not None:
            tid = parent.trace_id
            pid = parent.span_id
        else:
            tid = trace_id or _new_id()
            pid = None
        sp = Span(name, tid, pid)
        if attrs:
            sp.attributes.update(attrs)
        if parent is not None:
            parent.children.append(sp)
        self.span = sp
        self._token = None
        self._ann = None

    def __enter__(self) -> Span:
        self._token = _CURRENT.set(self.span)
        ann = _annotation()
        if ann is not None:
            self._ann = ann(self.span.name)
            self._ann.__enter__()
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        sp = self.span
        sp.finish()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        if exc is not None:
            sp.add_event("error", type=type(exc).__name__, message=str(exc))
        if self._token is not None:
            _CURRENT.reset(self._token)
        if sp.parent_id is None and _CURRENT.get() is None:
            _export(sp)
        return False


def span(name: str, trace_id: Optional[str] = None, force: bool = False,
         **attrs: Any):
    """Context manager for one span.

    Activates when a trace is already open (nesting), an exporter is
    installed, or ``force=True`` (the slow-query log needs the tree even
    with no exporter). Otherwise returns the free NOOP singleton — an
    explicit ``trace_id`` (joining a remote trace) only takes effect
    when something is listening."""
    parent = _CURRENT.get()
    if parent is None and not (_EXPORTERS or force):
        return NOOP
    return _SpanContext(name, parent, trace_id, attrs)


def current_span() -> Optional[Span]:
    return _CURRENT.get()


def current_trace_id() -> Optional[str]:
    sp = _CURRENT.get()
    return sp.trace_id if sp is not None else None


def event(name: str, **attrs: Any) -> None:
    """Attach a point-in-time event to the current span (no-op outside a
    trace) — how one-shot facts (a fired fault, a degradation) land on
    the query that suffered them."""
    sp = _CURRENT.get()
    if sp is not None:
        sp.add_event(name, **attrs)


def set_attr(key: str, value: Any) -> None:
    sp = _CURRENT.get()
    if sp is not None:
        sp.set_attr(key, value)


def graft(parent: Span, sub: Span, offset_ms: float = 0.0) -> Span:
    """Attach a deserialized remote subtree under ``parent`` — the
    coordinator half of fleet trace stitching (parallel/fleet.py).

    Every span in the subtree is re-keyed onto the parent's trace id
    (the remote side opened its root with the envelope's id, but a
    dropped/foreign id must not fracture the tree) and its wall-clock
    ``start_ms`` is shifted by ``offset_ms`` — the caller computes the
    offset from its OWN clock observations (RPC span start + elapsed)
    plus the remote span's monotonic-derived durations, so a skewed
    remote wall clock can never place the subtree outside the RPC that
    carried it. Span-relative event times need no shift."""
    sub.parent_id = parent.span_id
    for s in sub.walk():
        s.trace_id = parent.trace_id
        s.start_ms += offset_ms
    parent.children.append(sub)
    return sub


def active() -> bool:
    """True when spans would record (exporter installed or trace open)."""
    return bool(_EXPORTERS) or _CURRENT.get() is not None


def wrap(fn: Callable) -> Callable:
    """Bind ``fn`` to the CALLER's context so the active span survives a
    hop onto another thread (executor pools, server handler threads)."""
    ctx = contextvars.copy_context()
    return lambda *a, **k: ctx.run(fn, *a, **k)


# -- exporters ----------------------------------------------------------------


class TraceExporter:
    """Receives each completed ROOT span (children attached)."""

    def export(self, root: Span) -> None:
        raise NotImplementedError


class InMemoryTraceExporter(TraceExporter):
    """Bounded ring of recent trace trees (the InMemoryAuditWriter
    posture) — feeds tests and the /debug/traces endpoint.

    ``root_names`` restricts the ring to trees whose root has one of the
    given names: the debug ring keeps only query trees, so background
    roots (stream polls, ingest block writes) can never evict the traces
    an operator came to read."""

    def __init__(self, capacity: int = 256, root_names=None):
        self.capacity = capacity
        self.root_names = frozenset(root_names) if root_names else None
        self.traces: List[Span] = []
        self._lock = threading.Lock()

    def export(self, root: Span) -> None:
        if self.root_names is not None and root.name not in self.root_names:
            return
        with self._lock:
            self.traces.append(root)
            if len(self.traces) > self.capacity:
                del self.traces[: len(self.traces) - self.capacity]

    def recent(self, n: int = 20) -> List[Span]:
        if n <= 0:  # traces[-0:] would be the WHOLE ring
            return []
        with self._lock:
            return list(self.traces[-n:])


class JsonLinesTraceExporter(TraceExporter):
    """One JSON object per trace tree, appended to a file — offline
    analysis / replay (the DelimitedFileReporter posture)."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()

    def export(self, root: Span) -> None:
        line = json.dumps(root.to_dict(), default=str)
        with self._lock:
            with open(self.path, "a") as fh:
                fh.write(line + "\n")


class LoggingTraceExporter(TraceExporter):
    """Rendered trace trees through the logging module."""

    def __init__(self, logger: Optional[logging.Logger] = None):
        self.logger = logger or logging.getLogger("geomesa_tpu.trace")

    def export(self, root: Span) -> None:
        self.logger.info("trace %s\n%s", root.trace_id, root.render())


def install(exporter: TraceExporter) -> TraceExporter:
    with _EXPORTERS_LOCK:
        if exporter not in _EXPORTERS:
            _EXPORTERS.append(exporter)
    return exporter


def uninstall(exporter: TraceExporter) -> None:
    with _EXPORTERS_LOCK:
        try:
            _EXPORTERS.remove(exporter)
        except ValueError:
            pass


class exporting:
    """Scoped install for tests: ``with trace.exporting(ring): ...``"""

    def __init__(self, exporter: TraceExporter):
        self.exporter = exporter

    def __enter__(self) -> TraceExporter:
        return install(self.exporter)

    def __exit__(self, *exc) -> None:
        uninstall(self.exporter)


def _export(root: Span) -> None:
    # telemetry must never take the traced path down with it
    # (the GraphiteReporter drop-the-snapshot posture)
    with _EXPORTERS_LOCK:
        sinks = list(_EXPORTERS)
    for e in sinks:
        try:
            e.export(root)
        except Exception:  # noqa: BLE001 - exporter failure is not query failure
            _log.exception("trace exporter %r failed", type(e).__name__)


_DEBUG_RING: Optional[InMemoryTraceExporter] = None
_DEBUG_RING_REFS = 0
_DEBUG_RING_LOCK = threading.Lock()


def ensure_ring(capacity: int = 256) -> InMemoryTraceExporter:
    """Install (once) the process debug ring behind /debug/traces —
    query trees only, so serving traffic cannot flood the ring with
    poll/ingest roots. Refcounted against ``release_ring()``: each
    server holds one reference, and the last release restores the
    free-when-off no-op path."""
    global _DEBUG_RING, _DEBUG_RING_REFS
    with _DEBUG_RING_LOCK:
        if _DEBUG_RING is None:
            _DEBUG_RING = install(
                InMemoryTraceExporter(
                    capacity,
                    root_names=(
                        # every query-class root (utils/slo.py CLASSES):
                        # exemplar trace ids from any class must resolve
                        # here, and background roots (polls, ingest)
                        # still can never evict them
                        "query", "query.batch", "query.join",
                        "query.aggregate", "query.stream",
                    ),
                )
            )
        _DEBUG_RING_REFS += 1
        return _DEBUG_RING


def release_ring() -> None:
    """Drop one ensure_ring reference; the last one uninstalls the debug
    ring (a short-lived server must not leave the tracer — and up to 256
    retained span trees — active for the rest of the process)."""
    global _DEBUG_RING, _DEBUG_RING_REFS
    with _DEBUG_RING_LOCK:
        if _DEBUG_RING is None:
            return
        _DEBUG_RING_REFS -= 1
        if _DEBUG_RING_REFS > 0:
            return
        ring, _DEBUG_RING, _DEBUG_RING_REFS = _DEBUG_RING, None, 0
    uninstall(ring)


def find_trace(trace_id: str) -> Optional[Span]:
    """Resolve one retained trace tree by id — how the incident report
    (web.py GET /debug/report) turns an exemplar's trace_id into the
    actual span tree. Searches the debug ring (or a test's in-memory
    exporter); None once the ring has rotated past it."""
    if not trace_id:
        return None
    for root in recent_traces(10**9):
        if root.trace_id == trace_id:
            return root
    return None


def blackbox_traces(n: int = 20) -> List[Span]:
    """The crash black box's trace dump (utils/history.py): the last
    ``n`` retained trace trees, [] when no ring is installed. Identical
    to ``recent_traces`` today, but named for its shutdown-path caller —
    the dump must stay a pure read that can run during interpreter
    teardown (no ring installation, no lock beyond the snapshot)."""
    return recent_traces(n)


def recent_traces(n: int = 20) -> List[Span]:
    """Last ``n`` trace trees for /debug/traces: the debug ring when one
    is installed (query-filtered — an application's own unfiltered ring
    must not hijack the endpoint), else the first in-memory exporter
    (a test's ring); [] when none is."""
    with _DEBUG_RING_LOCK:
        ring = _DEBUG_RING
    if ring is not None:
        return ring.recent(n)
    with _EXPORTERS_LOCK:
        sinks = list(_EXPORTERS)
    for e in sinks:
        if isinstance(e, InMemoryTraceExporter):
            return e.recent(n)
    return []
