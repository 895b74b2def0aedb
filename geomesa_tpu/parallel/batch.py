"""Cross-query coalescing at the admission point.

``query_many`` already pipelines ONE caller's batch; production traffic
is many callers. The PR 4 admission queue is the natural batching point:
queries that were admitted concurrently are, by definition, in flight at
the same instant — so instead of each paying a full segment sweep over
the same HBM-resident columns, a ``QueryCoalescer`` gathers them per
feature type for a tiny window (``geomesa.batch.window.ms``, cap
``geomesa.batch.max.queries``), stacks their compiled predicate
parameters into ONE batched kernel call (``instrumented_jit``-accounted:
one sweep evaluates N predicate rows, producing an [N, rows] packed
mask — executor.dispatch_coalesced / _exact_mask_batch_fn), and demuxes
per query. Plain box(+window), attribute-plane, extent (xz), and banded
polygon shapes all stack (the dual-plane editions resolve through the
ring-certify contract); on an SPMD mesh the sweep compiles per chip
inside shard_map with no collective anywhere (the stacked-mask SPMD
kernel — multi-chip groups are rendezvous-safe by construction), so
coalescing reaches every mesh size.

Contract (the standing envelope):

* **Strictly after admit.** Every member holds its own admission slot
  before it ever reaches the coalescer, so ``ShedLoad``/queue semantics
  are untouched; the window only opens when another query is already in
  flight (or a group is already gathering), so an unsaturated store pays
  zero added latency.
* **Per-member deadlines.** Each member keeps its own ambient
  ``Deadline``. A member whose budget dies mid-window ejects crisply
  with ``QueryTimeout`` (never stalls the group — the leader just skips
  it); the leader resolves each member's scan under an ``attach`` of
  that member's own deadline.
* **Member isolation.** One member's failure (device fault, timeout)
  lands on THAT member only. A failure of the coalesce seam itself — the
  ``batch.coalesce`` fault point wrapping the shared plan+dispatch
  phase — degrades the WHOLE group to per-query solo execution with
  identical results (``degrade.coalesce_to_solo``).
* **Receipts split, not double-counted.** The shared sweep's device
  costs are captured in the leader's context-local collector
  (``devstats.collecting``) — including the batched buffer fetch, which
  the leader prefetches inside the shared phase — and apportioned across
  the members that rode it (integer remainder spread so member receipts
  SUM to the shared cost exactly); each member's own resolve costs are
  collected per member. Per-member QueryEvent rows audit as usual in the
  member's own thread.

``geomesa.batch.enabled=0`` is the escape hatch: every query takes the
pre-existing solo path with identical answers.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from geomesa_tpu.utils import audit, deadline
from geomesa_tpu.utils import devstats, faults, trace
from geomesa_tpu.utils.audit import QueryTimeout, robustness_metrics

# sentinel outcome: "run this query yourself on the solo path" (coalesce
# seam degraded, or the leader died before reaching this member)
SOLO = object()

# sentinel outcome: the member abandoned the group (its own budget died
# mid-window); the leader discards any late result for it
_ABANDONED = object()


def batch_knobs() -> tuple:
    """(enabled, window_s, max_queries) from the geomesa.batch.* tier."""
    from geomesa_tpu.utils.config import (
        BATCH_ENABLED,
        BATCH_MAX_QUERIES,
        BATCH_WINDOW_MS,
    )

    enabled = BATCH_ENABLED.to_bool()
    window_ms = BATCH_WINDOW_MS.to_float()
    max_q = BATCH_MAX_QUERIES.to_int() or 32
    return (
        bool(enabled) and (window_ms or 0) > 0 and max_q > 1,
        (window_ms or 0) / 1000.0,
        max_q,
    )


class MemberOutcome:
    """One coalesced member's finished execution, handed back to the
    member's thread: the result, its plan, the split cost receipt, and
    the timing the member's audit row needs."""

    __slots__ = ("result", "plan", "receipt", "plan_s", "group_n")

    def __init__(self, result, plan, receipt, plan_s: float, group_n: int):
        self.result = result
        self.plan = plan
        self.receipt = receipt
        self.plan_s = plan_s
        self.group_n = group_n


class _Member:
    __slots__ = ("query", "dl", "event", "outcome", "plan", "plan_s",
                 "_lock", "done")

    def __init__(self, query, dl):
        self.query = query
        self.dl = dl  # the member's OWN ambient deadline (may be None)
        self.event = threading.Event()
        self.outcome: Any = None
        self.plan = None
        self.plan_s = 0.0
        self._lock = threading.Lock()
        self.done = False

    def finish(self, outcome) -> bool:
        """Atomically claim this member with ``outcome``; False when the
        other side (leader vs. ejecting member) already claimed it."""
        with self._lock:
            if self.done:
                return False
            self.done = True
            self.outcome = outcome
        self.event.set()
        return True


class _Group:
    __slots__ = ("members", "closed")

    def __init__(self, leader: _Member):
        self.members = [leader]
        self.closed = False


class QueryCoalescer:
    """Per-store coalescing point. One instance per TpuDataStore,
    created lazily by the store (``_coalescer_obj``)."""

    def __init__(self, store):
        self.store = store
        self._cond = threading.Condition()
        self._open: Dict[str, _Group] = {}

    def gathering(self, name: str) -> bool:
        """True while a group for ``name`` is collecting members (a
        lock-free heuristic read — the store's concurrency gate)."""
        g = self._open.get(name)
        return g is not None and not g.closed

    # -- membership ----------------------------------------------------------

    def submit(self, name: str, ft, query) -> Optional[MemberOutcome]:
        """Coalesce one admitted query. Returns the member's finished
        outcome, or None when the caller should run the solo path
        (seam degraded / leader died before reaching this member).
        Raises the member's own failure (QueryTimeout on ejection)."""
        _enabled, window_s, max_q = batch_knobs()
        m = _Member(query, deadline.ambient())
        with self._cond:
            g = self._open.get(name)
            if g is not None and not g.closed:
                g.members.append(m)
                if len(g.members) >= max_q:
                    g.closed = True
                    if self._open.get(name) is g:
                        del self._open[name]
                    self._cond.notify_all()  # wake the leader early
                leader = False
            else:
                g = _Group(m)
                self._open[name] = g
                leader = True
        if leader:
            self._lead(name, ft, g, window_s)
        else:
            self._wait(m)
        out = m.outcome
        if out is SOLO:
            return None
        if isinstance(out, BaseException):
            raise out
        return out

    def _wait(self, m: _Member) -> None:
        """Member side: block for the leader's demux, bounded by the
        member's OWN deadline — a budget that dies mid-window ejects
        crisply with QueryTimeout and never stalls the group. A deadline
        cancellation (hedge loser) wakes the wait immediately via the
        on_cancel hook instead of a poll tick."""
        dl = m.dl
        unregister = dl.on_cancel(m.event.set) if dl is not None else None
        try:
            # the queue time a member pays: the leader's window, the
            # group's shared phase and the members executed ahead of it
            with trace.span("batch.wait"):
                while not m.done:
                    if dl is not None and (
                        dl.is_cancelled or dl.remaining() <= 0.0
                    ):
                        if m.finish(_ABANDONED):
                            # counts/attributes via the deadline's own
                            # raise paths (deadline.cancelled vs .exceeded)
                            dl.check("batch.coalesce.wait")
                        break  # leader won the race: outcome is set
                    m.event.wait(None if dl is None else dl.remaining())
                    m.event.clear()
        finally:
            if unregister is not None:
                unregister()

    # -- leadership ----------------------------------------------------------

    def _lead(self, name: str, ft, g: _Group, window_s: float) -> None:
        """Leader side: gather joiners for the window, then execute the
        group. The leader is itself members[0]."""
        end = time.monotonic() + window_s
        with trace.span("batch.gather") as sp, self._cond:
            while not g.closed:
                left = end - time.monotonic()
                if left <= 0.0:
                    break
                self._cond.wait(left)
            g.closed = True
            if self._open.get(name) is g:
                del self._open[name]
            members = list(g.members)
            sp.set_attr("n", len(members))
        try:
            self._execute_group(name, ft, members)
        finally:
            # ANY leader exit path — including a SimulatedCrash unwinding
            # through — must release every unfinished member to the solo
            # path; a waiting member may never stall on a dead leader
            for m in members:
                m.finish(SOLO)

    def _execute_group(self, name: str, ft, members: List[_Member]) -> None:
        store = self.store
        reg = devstats.devstats_metrics()
        reg.inc("batch.coalesce.groups")
        reg.inc("batch.coalesce.members", len(members))
        # pow2 group-size histogram for the /debug/device coalesce block
        # (the timeline/SLO layer's "is the coalescer actually batching"
        # signal — a histogram of all-1s means the window never fills)
        bucket = 1
        while bucket < len(members):
            bucket *= 2
        reg.inc(f"batch.coalesce.group.pow2.{bucket}")
        pad0 = reg.counter("device.pad.events")
        shared: Dict[str, int] = {}
        try:
            with trace.span("batch.coalesce", n=len(members)):
                # the coalesce seam: a failure of the SHARED phase (plan
                # + batched dispatch + prefetch) degrades the whole group
                # to solo with identical results — chaos-tested like
                # every other boundary
                deadline.check("batch.coalesce")
                faults.fault_point("batch.coalesce")
                with devstats.collecting(shared):
                    live = self._shared_phase(name, members)
        except Exception as e:
            if isinstance(e, QueryTimeout):
                # the LEADER's own budget died (its member outcome) —
                # no verdict on the seam; siblings run solo unharmed
                members[0].finish(e)
                return
            robustness_metrics().inc("degrade.coalesce_to_solo")
            trace.event(
                "degrade.coalesce_to_solo",
                reason=f"{type(e).__name__}: {e}",
                n=len(members),
            )
            audit.decision(
                "coalesce", "seam_degraded",
                error=type(e).__name__, n=len(members),
            )
            return  # _lead's finally hands every member to the solo path
        if not live:
            return
        pad_ratio = (
            round(reg.gauge("device.pad.ratio"), 4)
            if reg.counter("device.pad.events") > pad0
            else 0.0
        )
        shares = _apportion(shared, len(live))
        # a member that ejects or fails mid-resolve reports no receipt —
        # its share of the shared sweep carries forward to the next
        # SUCCESSFUL member, so surviving receipts still sum to the
        # sweep's cost (only a group whose tail all fails drops bytes,
        # and those members' failures are themselves audited)
        carry: Dict[str, int] = {}
        for i, (m, plan, pending) in enumerate(live):
            if m.done:
                # ejected while the shared phase ran
                _fold(carry, shares[i])
                continue
            own: Dict[str, int] = {}
            t0 = time.perf_counter()
            try:
                with deadline.attach(m.dl):
                    with devstats.collecting(own):
                        with trace.span("query.member", i=i):
                            result = store._execute(
                                name, ft, m.query, plan, t0, pending
                            )
            except Exception as e:
                # member isolation: THIS member fails; siblings proceed
                _fold(carry, shares[i])
                m.finish(e)
                continue
            _fold(carry, shares[i])
            receipt = {
                k: own.get(k, 0) + carry.get(k, 0)
                for k in ("recompiles", "h2d_bytes", "d2h_bytes")
            }
            carry = {}
            receipt["pad_ratio"] = pad_ratio
            m.finish(
                MemberOutcome(result, plan, receipt, m.plan_s, len(members))
            )

    def _shared_phase(self, name: str, members: List[_Member]):
        """Plan every live member and dispatch the stacked sweeps.
        Returns [(member, plan, pending)] for the per-member resolves.
        A failure anywhere in here propagates to the ``batch.coalesce``
        envelope in _execute_group, which degrades the WHOLE group to
        solo — per-member execution re-answers identically, so shared-
        phase failures cost latency, never correctness. A member whose
        OWN preparation fails (its budget died mid-plan, a bad filter)
        fails alone without touching the group."""
        store = self.store
        live = []
        for m in members:
            if m.done:
                continue
            if m.dl is not None and (
                m.dl.is_cancelled or m.dl.remaining() <= 0.0
            ):
                continue  # ejecting member claims itself in _wait
            t0 = time.perf_counter()
            try:
                with deadline.attach(m.dl):
                    store._prepare_query(name, m.query)
                    plan = store._plan_cached(name, m.query)
            except Exception as e:
                # a member whose own preparation fails (its budget died
                # mid-plan, a bad filter) fails ALONE
                m.finish(e)
                continue
            m.plan_s = time.perf_counter() - t0
            live.append((m, plan, None))
        if not live:
            return live
        dispatch = getattr(store.executor, "dispatch_coalesced", None)
        pending: Dict[int, object] = {}
        if dispatch is not None:
            items = []
            seen = set()
            for _m, plan, _p in live:
                if "density" in _m.query.hints:
                    continue  # fused density dispatches its own compute
                arms = plan.union if plan.union is not None else [plan]
                for arm in arms:
                    if arm.is_empty or id(arm) in seen:
                        continue
                    seen.add(id(arm))
                    items.append((store._tables[name][arm.index.name], arm))
            if items:
                pending = dispatch(items)
                # resolve the shared buffers NOW, inside the shared cost
                # collector: the sweep's D2H apportions across members
                # instead of landing in the first resolver's receipt
                for scan in {id(s): s for s in pending.values()}.values():
                    fn = getattr(scan, "prefetch", None)
                    if fn is not None:
                        fn()
        return [(m, plan, pending) for m, plan, _ in live]


def _fold(acc: Dict[str, int], extra: Dict[str, int]) -> None:
    for k, v in extra.items():
        acc[k] = acc.get(k, 0) + v


def _apportion(shared: Dict[str, int], n: int) -> List[Dict[str, int]]:
    """Split the shared sweep's cost counters across ``n`` members so
    the per-member shares SUM exactly to the shared total (quotient to
    everyone, remainder spread over the first members — the
    "± apportionment rounding" of the receipt-splitting invariant)."""
    out: List[Dict[str, int]] = [dict() for _ in range(n)]
    for key, total in shared.items():
        base, rem = divmod(int(total), n)
        for i in range(n):
            out[i][key] = base + (1 if i < rem else 0)
    return out
