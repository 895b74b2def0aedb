"""TpuDataStore: the GeoMesaDataStore analog.

Schema CRUD + writers + query execution over columnar index tables
(reference: geomesa-index-api .../geotools/MetadataBackedDataStore.scala:39,
GeoMesaDataStore.scala:39, GeoMesaFeatureWriter.scala:34-259,
QueryPlanner.runQuery planning/QueryPlanner.scala:74-99).

Execution pipeline per query: PLAN (_plan_cached) -> ROUTE (_route:
decompose into independently scannable units — union arms here, per-shard
partition scans in parallel/shards.py) -> SCAN (_scan_parts: ranges over
blocks -> candidate rows -> post-filter; host numpy by default, the TPU
executor in geomesa_tpu.parallel offloads point indices to device) ->
MERGE (_merge: dedupe -> sort -> projection/limits -> aggregation
reducers (density/stats/bin) when hinted).
"""

from __future__ import annotations

import math
import uuid
from collections.abc import Mapping
from typing import Any, Dict, Iterator, List, Optional, Sequence, Union

import numpy as np

from geomesa_tpu.filter import ast, evaluate
from geomesa_tpu.parallel import mesh as mesh_mod
from geomesa_tpu.index.aggregators import (
    AGGREGATION_HINTS,
    has_aggregation,
    run_aggregation,
)
from geomesa_tpu.index.keyspace import IndexKeySpace, default_indices
from geomesa_tpu.index.planner import Query, QueryPlan, QueryPlanner
from geomesa_tpu.schema.feature import Feature
from geomesa_tpu.schema.featuretype import AttributeType, FeatureType, parse_spec
from geomesa_tpu.store.blocks import (
    ColumnBuffer,
    Columns,
    IndexTable,
    take_rows,
)
from geomesa_tpu.store.metadata import InMemoryMetadata, Metadata
from geomesa_tpu.utils import admission as admission_mod
from geomesa_tpu.utils import audit as audit_mod
from geomesa_tpu.utils import deadline as deadline_mod
from geomesa_tpu.utils import devstats, trace
from geomesa_tpu.utils import plans as plans_mod
from geomesa_tpu.utils import tenants as tenants_mod
from geomesa_tpu.utils import workload as workload_mod

DEFAULT_FLUSH_SIZE = 100_000


class LazyColumns(Mapping):
    """Deferred column materialization over scanned (block, rows) pairs.

    The KryoBufferSimpleFeature analog (geomesa-feature-kryo
    .../KryoBufferSimpleFeature.scala:1-288 — zero-copy lazy attribute
    reads): a query result holds row indices into the immutable sealed
    blocks and gathers a column only when something actually reads it.
    A fid-only parity stream or a count never pays for attribute gathers;
    the CPU-reference comparison (index arrays) stays apples-to-apples.

    Parts hold INDEX-block rows; columns resolve own (key-sorted, near-
    sequential) block columns first and fall through to the shared record
    block via the block's rowid mapping, computed lazily ONCE per part
    (the join against the record table, AttributeIndex JoinPlan analog) —
    a count or a fid-free stream never pays it.

    Read-only Mapping; ``materialize()`` returns a plain dict for code
    paths that mutate or re-order columns (sort/limit/sampling/dedupe)."""

    __slots__ = ("_parts", "_keys", "_cache", "_rmap", "num_rows")

    def __init__(self, parts, keys):
        self._parts = parts  # [(FeatureBlock | RecordBlock, row-index array)]
        self._keys = frozenset(keys)
        self._cache: Dict[str, np.ndarray] = {}
        self._rmap: Dict[int, np.ndarray] = {}  # part idx -> record rows
        self.num_rows = int(sum(len(r) for _, r in parts))

    def _part_col(self, i: int, block, rows, k: str) -> np.ndarray:
        gather = getattr(block, "gather", None)
        if gather is None:  # RecordBlock part: plain column lookup
            got = block.columns.get(k)
            if got is not None:
                got = got[rows]
            elif k.endswith("__null"):
                return np.zeros(len(rows), dtype=bool)
            else:
                raise KeyError(f"Column {k} missing from a block")
        elif k not in block.columns and getattr(block, "record", None) is not None:
            # record-backed read: compute the join mapping once per part
            rr = self._rmap.get(i)
            if rr is None:
                rr = self._rmap[i] = block.rowid[rows]
            got = gather(k, rows, record_rows=rr)
        else:
            got = gather(k, rows)
        vocab = self._vocab_for(block, k)
        if vocab is not None:
            from geomesa_tpu.store.blocks import dict_decode

            got = dict_decode(got, vocab)  # results expose VALUES, not codes
        return got

    @staticmethod
    def _vocab_for(block, k: str):
        if k.startswith("__") or k.endswith("__null"):
            return None
        rec = getattr(block, "record", None)
        cols = rec.columns if rec is not None else block.columns
        return cols.get(k + "__vocab")

    def __getitem__(self, k: str) -> np.ndarray:
        if k not in self._keys:
            raise KeyError(k)
        got = self._cache.get(k)
        if got is None:
            pieces = [
                self._part_col(i, block, rows, k)
                for i, (block, rows) in enumerate(self._parts)
            ]
            got = np.concatenate(pieces) if pieces else np.empty(0, dtype=object)
            self._cache[k] = got
        return got

    def __iter__(self):
        return iter(self._keys)

    def __len__(self):
        return len(self._keys)

    def __contains__(self, k):
        return k in self._keys

    def materialize(self) -> Columns:
        return {k: self[k] for k in self._keys}


class QueryResult:
    """Columnar query result with row-feature accessors."""

    def __init__(
        self,
        ft: FeatureType,
        columns: Columns,
        plan: Optional[QueryPlan] = None,
        aggregate: Optional[Dict[str, Any]] = None,
    ):
        self.ft = ft
        self.columns = columns
        self.plan = plan
        # density grid / stats sketch / bin records when hints requested them
        self.aggregate = aggregate or {}

    def __len__(self):
        n = getattr(self.columns, "num_rows", None)
        if n is not None:
            return n
        for v in self.columns.values():
            return len(v)
        return 0

    @property
    def fids(self) -> np.ndarray:
        return self.columns.get("__fid__", np.empty(0, dtype=object))

    def to_features(self) -> List[Feature]:
        out = []
        n = len(self)
        for i in range(n):
            values = []
            for a in self.ft.attributes:
                if a.type == AttributeType.POINT:
                    x = self.columns[a.name + "__x"][i]
                    y = self.columns[a.name + "__y"][i]
                    if np.isnan(x):
                        values.append(None)
                    else:
                        from geomesa_tpu.geom.base import Point

                        values.append(Point(float(x), float(y)))
                elif a.name in self.columns:
                    v = self.columns[a.name][i]
                    nulls = self.columns.get(a.name + "__null")
                    if nulls is not None and nulls[i]:
                        values.append(None)
                    elif v is None:
                        values.append(None)
                    else:
                        values.append(v.item() if isinstance(v, np.generic) else v)
                else:
                    values.append(None)
            out.append(Feature(self.ft, str(self.fids[i]), values))
        return out


class FeatureWriter:
    """Buffered appender; flush seals one block per index
    (GeoMesaFeatureWriter analog -- fid generation mirrors Z3FeatureIdGenerator's
    uuid fallback)."""

    def __init__(self, store: "TpuDataStore", ft: FeatureType, flush_size: int):
        self.store = store
        self.ft = ft
        self.buffer = ColumnBuffer(ft)
        self.flush_size = flush_size

    def write(
        self,
        values: Sequence[Any],
        fid: Optional[str] = None,
        visibility: Optional[str] = None,
    ) -> str:
        fid = fid if fid is not None else str(uuid.uuid4())
        user_data = {"visibility": visibility} if visibility else None
        self.buffer.append(Feature(self.ft, fid, values, user_data))
        if len(self.buffer) >= self.flush_size:
            self.flush()
        return fid

    def write_feature(self, feature: Feature) -> str:
        if feature.fid is None:
            feature = Feature(
                self.ft, str(uuid.uuid4()), feature.values, feature.user_data
            )
        self.buffer.append(feature)
        if len(self.buffer) >= self.flush_size:
            self.flush()
        return feature.fid

    def write_columns(self, columns: Columns):
        """Bulk columnar ingest (the fast path: no row objects)."""
        self.flush()
        self.store._insert_columns(self.ft, columns)

    def flush(self):
        if len(self.buffer):
            self.store._insert_columns(self.ft, self.buffer.to_columns())
            self.buffer.clear()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        # flush buffered rows on normal exit and on plain failures (the
        # historical contract), but NOT while a crash-like BaseException
        # (faults.SimulatedCrash, KeyboardInterrupt) unwinds — a dying
        # process flushes nothing, and the crash harness depends on the
        # unwind leaving disk exactly as a SIGKILL would
        if exc is None or isinstance(exc, Exception):
            self.flush()
        return False


class TpuDataStore:
    """The datastore facade: create_schema / writer / query / delete."""

    # cross-query coalescing at the admission point (parallel/batch.py).
    # Subclasses whose _execute is NOT a local device scan opt out: the
    # sharded coordinator's fan-out is already concurrent across shards,
    # and serializing members behind one leader would cost parallelism
    # instead of sharing a sweep (its WORKER stores coalesce, where the
    # device sweeps actually run).
    COALESCE_QUERIES = True
    # whether query_stream may scan this store's LOCAL tables
    # incrementally. Subclasses whose rows live elsewhere (the sharded
    # coordinator's local tables are intentionally empty — data is
    # routed to shard workers) MUST opt out, or the streamable branch
    # would stream zero rows from the empty local tables; with the
    # opt-out they stream via the overridden _execute (materialize,
    # then chunk) with correct answers and no first-byte win.
    STREAMS_LOCAL_PARTS = True

    def __init__(
        self,
        metadata: Optional[Metadata] = None,
        executor: Optional["ScanExecutor"] = None,
        flush_size: int = DEFAULT_FLUSH_SIZE,
        stats: Optional[Any] = None,
        auths: Optional[Any] = None,
        audit_writer: Optional[Any] = None,
        metrics: Optional[Any] = None,
        query_timeout_s: Optional[float] = None,
        slow_query_s: Optional[float] = None,
        user: str = "unknown",
        max_inflight: Optional[int] = None,
        max_queue: Optional[int] = None,
    ):
        from geomesa_tpu.stats.service import MetadataBackedStats

        self.metadata = metadata or InMemoryMetadata()
        self.executor = executor or HostScanExecutor()
        self.flush_size = flush_size
        # AuthorizationsProvider, a plain list of auth strings, or None
        # (None = no auths: only visibility-free features are readable)
        self.auths = auths
        self.audit_writer = audit_writer
        self.metrics = metrics
        if query_timeout_s is None:
            # tiered knob (QueryProperties 'geomesa.query.timeout'):
            # GEOMESA_QUERY_TIMEOUT or utils.config.set_property
            from geomesa_tpu.utils.config import QUERY_TIMEOUT

            query_timeout_s = QUERY_TIMEOUT.to_duration_s()
        self.query_timeout_s = query_timeout_s
        if slow_query_s is None:
            # tiered knob: geomesa.query.slow.threshold — any query over
            # the budget logs its full span tree + explain
            from geomesa_tpu.utils.config import SLOW_QUERY_THRESHOLD

            slow_query_s = SLOW_QUERY_THRESHOLD.to_duration_s()
        self.slow_query_s = slow_query_s
        self.user = user
        # admission control (utils/admission.py): bounded in-flight
        # queries + a bounded wait queue; overflow sheds with ShedLoad
        # instead of queueing into collapse. Knobs:
        # geomesa.query.max.inflight / geomesa.query.queue.depth.
        from geomesa_tpu.utils.admission import AdmissionController
        from geomesa_tpu.utils.config import (
            QUERY_MAX_INFLIGHT,
            QUERY_QUEUE_DEPTH,
        )

        if max_inflight is None:
            max_inflight = QUERY_MAX_INFLIGHT.to_int() or 64
        if max_queue is None:
            mq = QUERY_QUEUE_DEPTH.to_int()
            max_queue = 256 if mq is None else mq
        self.admission = AdmissionController(max_inflight, max_queue)
        # closed-loop brownout (utils/brownout.py): the timeline tick
        # drives the ladder off queue depth, SLO burn, and breaker
        # state; the admission gate consults it per query. The
        # controller exists unconditionally (one attribute read when
        # idle); geomesa.brownout.enabled=0 bypasses every gate.
        from geomesa_tpu.utils.brownout import BrownoutController

        self._brownout = BrownoutController()
        self.admission.brownout = self._brownout
        # write-time maintained sketches feeding the cost-based decider
        # (accumulo/data/stats/StatsCombiner.scala:26 analog)
        self.stats = stats if stats is not None else MetadataBackedStats(self.metadata)
        self._schemas: Dict[str, FeatureType] = {}
        self._indices: Dict[str, List[IndexKeySpace]] = {}
        self._tables: Dict[str, Dict[str, IndexTable]] = {}
        self._plan_cache: Dict[Any, QueryPlan] = {}
        # per-type write generation: bumped on EVERY mutation path —
        # including subclass overrides whose writes never touch the
        # local tables (ShardedDataStore routes rows to shard workers)
        # — so schema-generation cache keys (ops/join.py) can never
        # serve state from before a write
        self._write_gen: Dict[str, int] = {}
        if self.metrics is not None and hasattr(self.metrics, "gauge_fn"):
            # sampled at snapshot time: cache pressure without
            # bookkeeping. One gauge per REGISTRY summing over a WeakSet
            # of live stores — several stores sharing the scrape registry
            # don't overwrite each other, and a registry outliving a
            # store never pins its tables and mirrors (dead stores just
            # drop out of the set).
            import weakref

            stores = getattr(self.metrics, "_plan_cache_stores", None)
            if stores is None:
                stores = weakref.WeakSet()
                self.metrics._plan_cache_stores = stores
                self.metrics.gauge_fn(
                    "plan_cache.size",
                    lambda: sum(len(s._plan_cache) for s in stores),
                )
            stores.add(self)
        # recover schemas from persistent metadata
        for name in self.metadata.scan_types():
            spec = self.metadata.read(name, "attributes")
            if spec:
                self._register(parse_spec(name, spec))

    @property
    def authorizations(self) -> List[str]:
        if self.auths is None:
            return []
        if hasattr(self.auths, "get_authorizations"):
            return list(self.auths.get_authorizations())
        return list(self.auths)

    # -- schema CRUD --------------------------------------------------------

    def create_schema(self, ft: FeatureType) -> None:
        if ft.name in self._schemas:
            existing = self._schemas[ft.name]
            if existing != ft:
                raise ValueError(f"Schema {ft.name} already exists with different spec")
            return
        if ft.default_geometry is None:
            raise ValueError("Schema requires a geometry attribute")
        self.metadata.insert(ft.name, "attributes", ft.spec())
        self._register(ft)

    def _register(self, ft: FeatureType) -> None:
        self._schemas[ft.name] = ft
        indices = default_indices(ft)
        if not indices:
            raise ValueError(f"No indices support schema {ft.name}")
        self._indices[ft.name] = indices
        self._tables[ft.name] = {i.name: IndexTable(i, ft) for i in indices}

    def get_schema(self, name: str) -> FeatureType:
        if name not in self._schemas:
            raise KeyError(f"Unknown feature type: {name}")
        return self._schemas[name]

    @property
    def type_names(self) -> List[str]:
        return sorted(self._schemas.keys())

    def delete_schema(self, name: str) -> None:
        self.get_schema(name)
        self.metadata.delete(name)
        del self._schemas[name], self._indices[name], self._tables[name]
        # the generation counter deliberately SURVIVES the schema (not
        # popped): a delete + recreate cycle must never reproduce an
        # old schema_generation, or the join build cache would serve
        # pairs from the deleted incarnation on stores whose local
        # table versions never move (ShardedDataStore coordinators)
        self._note_write(name)

    # -- writes -------------------------------------------------------------

    def writer(self, name: str, flush_size: Optional[int] = None) -> FeatureWriter:
        return FeatureWriter(self, self.get_schema(name), flush_size or self.flush_size)

    def _insert_columns(self, ft: FeatureType, columns: Columns, observe_stats: bool = True):
        from geomesa_tpu.store.blocks import (
            RecordBlock,
            intern_fids,
            intern_string_columns,
        )

        # once per batch, not per index table
        columns = intern_string_columns(ft, intern_fids(columns))
        # ONE shared record block per batch: index tables sort only their
        # key + scan-hot columns and reference the rest by rowid (the
        # record-table / join-index layout, AttributeIndex.scala:42,392)
        record = RecordBlock(columns)
        for table in self._tables[ft.name].values():
            table.insert_record(record)
        if observe_stats and self.stats is not None:
            # the z3 block just sealed already encoded every row's key: the
            # Z3 histogram reuses it (row order is irrelevant to counts).
            # Gate on NaN-free coords — observe_xyt drops NaN rows, while
            # the block's lenient encode would give them clipped keys.
            z3_keys = None
            zt = self._tables[ft.name].get("z3")
            geom = ft.default_geometry
            if zt is not None and zt.blocks and geom is not None:
                blk = zt.blocks[-1]
                x = columns.get(geom.name + "__x")
                y = columns.get(geom.name + "__y")
                if (
                    x is not None
                    and y is not None
                    and blk.n == len(x)
                    and blk.bins is not None
                    and not np.isnan(x).any()
                    and not np.isnan(y).any()
                ):
                    z3_keys = (blk.key, blk.bins)
            self.stats.observe_columns(ft, columns, z3_keys=z3_keys)
        # cold-column spill LAST: every index table and the stats observer
        # has read its columns; nothing refaults what fadvise just dropped
        record.spill()
        self._note_write(ft.name)

    def _note_write(self, name: str) -> None:
        """Advance the type's write generation (see _write_gen). Every
        mutation path — base or override — must call this. The aggregate
        cache (ops/pyramid.py) invalidates here too: the generation in
        its keys already re-keys stale entries, but dropping them NOW
        releases their device arrays instead of waiting out the TTL."""
        self._write_gen[name] = self._write_gen.get(name, 0) + 1
        cache = self.__dict__.get("_agg_cache")
        if cache is not None:
            cache.invalidate(name)

    def schema_generation(self, name: str) -> tuple:
        """An opaque value that changes whenever the type's stored rows
        may have changed: local index-table versions (a lazy store's
        replay moves them) plus the write counter (covers subclasses
        that keep no local rows). Cache keys derive from this."""
        return (
            tuple(t.version for t in self._tables[name].values()),
            self._write_gen.get(name, 0),
        )

    def delete_features(self, name: str, fids: Sequence[str]):
        for table in self._tables[name].values():
            table.delete(fids)
        self._note_write(name)

    def compact(self, name: str):
        tables = self._tables[name]
        first = next(iter(tables.values()))
        if len(first.blocks) <= 1 and not first.tombstones:
            return
        # merge record parts ONCE; every index table rebuilds against the
        # same shared record block (deletes are store-wide, so any table's
        # tombstone set covers them all — use the fullest view: a table
        # without a __valid__ row filter)
        full = next(
            (t for t in tables.values() if t.index.name in ("id", "z2", "z3", "xz2", "xz3")),
            first,
        )
        record = full.merged_record()
        for table in tables.values():
            table.compact(record)
        record.spill()  # after every table's rebuild read its columns
        self._note_write(name)

    def count(self, name: str, query: Union[str, "Query", None] = None, exact: bool = True) -> int:
        """Feature count; with a filter, ``exact=False`` answers from stats
        (the EXACT_COUNT hint / GeoMesaStats.getCount split)."""
        tables = self._tables[name]
        first = next(iter(tables.values()))
        # visibility-bearing tables must count through the auth-enforcing
        # query path — raw row counts (and write-time stats, which observed
        # every row) would leak the cardinality of unreadable features
        has_vis = any(b.has_col("__vis__") for b in first.blocks)
        if query is not None:
            q = self._as_query(query)
            if (
                not exact
                and self.stats is not None
                and not has_vis
                # expired rows were observed at write time: sketches would
                # count them, so age-off types must scan
                and self._age_off_cutoff(self.get_schema(name)) is None
            ):
                est = self.stats.get_count(self.get_schema(name), q.filter)
                if est is not None:
                    return int(est)
            if (
                exact
                and not has_vis
                and self._age_off_cutoff(self.get_schema(name)) is None
            ):
                # aggregate pyramid first (ops/pyramid.py): a hot region
                # answers from interior partial sums + the boundary ring
                # without sweeping candidate segments — cheaper than even
                # the device mask-sum, and available on host-only stores
                if q.max_features is None and not q.hints:
                    self._prepare_query(name, q)
                    plan = self._plan_cached(name, q)
                    got = self._count_pyramid(name, self.get_schema(name), q, plan)
                    if got is not None:
                        return got
                got = self._count_device(name, q)
                if got is not None:
                    return got
            return len(self.query(name, q))
        if has_vis or self._age_off_cutoff(self.get_schema(name)) is not None:
            # expired features must not be counted (age-off masks at scan)
            return len(self.query(name))
        n = first.num_rows
        if first.tombstones:
            n -= sum(1 for _ in first.tombstones)
        return n

    def _count_device(self, name: str, q: "Query") -> Optional[int]:
        """Device mask-sum count when the executor supports it and the
        query's semantics reduce to plain len() (no limit/hints). The
        failure fallback mirrors density: a lost device answers through
        the ordinary scan path and trips the session device flag."""
        count_scan = getattr(self.executor, "count_scan", None)
        if count_scan is None:
            return None
        if q.max_features is not None or q.hints:
            return None  # limits / sampling / aggregations change len()
        if mesh_mod.device_tripped(self.executor, "GEOMESA_COUNT_DEVICE"):
            return None
        plan = self._plan_cached(name, q)
        if plan.union:
            return None  # OR arms may overlap; the host path dedupes
        table = self._tables[name].get(plan.index.name)
        if table is None:
            return None
        try:
            return count_scan(table, plan)
        except Exception as e:  # noqa: BLE001 - device failure
            from geomesa_tpu.utils.audit import QueryTimeout

            if isinstance(e, QueryTimeout):
                raise  # the query's budget died, not the device
            mesh_mod.trip_device(
                self.executor, "GEOMESA_COUNT_DEVICE", "count", e
            )
            audit_mod.decision(
                "degrade", "count_to_host", error=type(e).__name__
            )
            return None

    # -- aggregate pyramid cache (ops/pyramid.py) ----------------------------

    def _agg_cache_obj(self):
        """The per-store aggregate cache, created lazily. GIL-atomic
        setdefault: two concurrent first aggregations agree on ONE cache
        (the ops/join.py rule — an orphaned loser would pin its device
        arrays until GC)."""
        cache = getattr(self, "_agg_cache", None)
        if cache is None:
            from geomesa_tpu.ops.pyramid import AggCache

            cache = self.__dict__.setdefault("_agg_cache", AggCache())
        return cache

    def _pyramid_for(self, name: str, ft) -> Optional[Any]:
        """The type's cached aggregate pyramid, built lazily under the
        ``agg.build`` fault envelope. None when ineligible (no z2 table)
        or when the build degraded — the caller answers from the
        uncached exact scan path with identical results (parity under
        faults covers aggregations-from-cache)."""
        from geomesa_tpu.ops.pyramid import agg_knobs, build_pyramid

        table = self._tables[name].get("z2")
        if table is None:
            return None
        bits, levels, ttl, _cap = agg_knobs()
        cache = self._agg_cache_obj()
        # the key carries the schema generation (local table versions +
        # the write counter): any write/compact/delete — including one
        # routed through a ShardedDataStore worker — moves it, so a
        # stale pyramid can never answer
        key = ("pyramid", name, self.schema_generation(name), bits, levels)
        pyr = cache.get(key, ttl)
        if pyr is not None:
            return pyr
        # brownout speculation gate: a COLD pyramid build is optional
        # work (the exact scan answers identically) — at hedge-off
        # levels the capacity it would burn belongs to queued queries.
        # A warm pyramid above keeps serving; only the build defers
        bo = getattr(self, "_brownout", None)
        if bo is not None and not bo.speculation_allowed():
            from geomesa_tpu.utils import brownout as brownout_mod
            from geomesa_tpu.utils.audit import robustness_metrics

            if brownout_mod.enabled():
                robustness_metrics().inc("agg.cache.declined")
                audit_mod.decision(
                    "pyramid", "brownout_deferred", level=bo.level
                )
                return None
        try:
            pyr = build_pyramid(table, ft, self.executor)
        except Exception as e:  # noqa: BLE001 - injected/device build failure
            from geomesa_tpu.utils.audit import QueryTimeout, robustness_metrics

            if isinstance(e, QueryTimeout):
                raise  # the query's budget died, not the build
            robustness_metrics().inc("degrade.agg_to_scan")
            trace.event(
                "degrade.agg_to_scan", reason=f"{type(e).__name__}: {e}"
            )
            audit_mod.decision(
                "pyramid", "build_degraded", error=type(e).__name__
            )
            return None
        cache.put(key, pyr)
        return pyr

    def _agg_eligible(self, name: str, ft) -> bool:
        """Store-state gates shared by every pyramid consumer: per-row
        visibilities need the auth-enforcing scan, and age-off masks
        expired rows at scan time — the pyramid aggregated them all."""
        from geomesa_tpu.ops.pyramid import agg_enabled

        if not agg_enabled():
            return False
        tables = self._tables.get(name)
        if not tables or "z2" not in tables:
            return False
        first = next(iter(tables.values()))
        if any(b.has_col("__vis__") for b in first.blocks):
            return False
        return self._age_off_cutoff(ft) is None

    def _pyramid_classify(self, name, ft, query: Query, plan):
        """The shared gate→build→classify pipeline under every pyramid
        consumer: eligibility, spatial-only shape, the pre-build and
        post-classify cost-model declines, and the (possibly degraded)
        build. Returns ``(pyr, interior_rows, boundary_cells,
        interior_mask)`` or None (the caller answers uncached)."""
        from geomesa_tpu.filter.parser import to_cql
        from geomesa_tpu.index.planner import (
            pyramid_worthwhile,
            spatial_only_shape,
        )
        from geomesa_tpu.ops.pyramid import agg_knobs, could_have_interior

        if not self._agg_eligible(name, ft):
            return None
        geoms = spatial_only_shape(plan, ft)
        if geoms is None:
            return None
        bits, _levels, _ttl, _cap = agg_knobs()
        if not could_have_interior(geoms, bits):
            # sub-cell region: decline BEFORE paying the O(table) build
            devstats.devstats_metrics().inc("agg.cache.declined")
            audit_mod.decision("pyramid", "sub_cell_region", type=name)
            return None
        pyr = self._pyramid_for(name, ft)
        if pyr is None:
            return None
        interior, boundary_rows, _cand, cells, imask = pyr.classify(
            geoms, memo_key=to_cql(query.filter)
        )
        if not pyramid_worthwhile(interior, boundary_rows):
            devstats.devstats_metrics().inc("agg.cache.declined")
            audit_mod.decision(
                "pyramid", "boundary_dominates",
                interior=int(interior), boundary_rows=int(boundary_rows),
            )
            return None
        return pyr, interior, cells, imask

    def _count_pyramid(self, name, ft, query: Query, plan) -> Optional[int]:
        """Exact count from the pyramid: interior partial sums + the
        exact boundary-ring scan. None -> the ordinary paths answer.
        ShardedDataStore overrides this with the per-worker fan-out."""
        got = self._pyramid_classify(name, ft, query, plan)
        if got is None:
            return None
        pyr, interior, cells, _imask = got
        n = interior
        if len(cells):
            parts = self._agg_boundary_parts(
                name, ft, plan, pyr.cell_ranges(cells)
            )
            n += sum(len(r) for _b, r in parts)
        return n

    def _agg_boundary_parts(self, name, ft, plan, ranges) -> List[tuple]:
        """The fallthrough half of the interior/boundary fusion: seek
        ONLY the boundary cells' z2 key spans (each pyramid cell is one
        contiguous z2 range) and evaluate the plan's own post-filter on
        those rows — identical per-row semantics to the uncached scan,
        so pyramid answers are exact by construction."""
        table = self._tables[name]["z2"]
        dl = deadline_mod.ambient()
        pf = plan.post_filter
        pf_props = set(ast.properties(pf)) if pf is not None else None
        parts: List[tuple] = []
        for block, rows in table.scan(ranges):
            if dl is not None:
                dl.check("agg.boundary")
            if pf_props is not None and len(rows):
                fcols = self._gather_filter_cols(block, rows, pf_props)
                mask = self.executor.post_filter(ft, plan, fcols)
                if not mask.all():
                    rows = rows[mask]
            if len(rows):
                parts.append((block, rows))
        return parts

    def _density_key(self, name: str, query: Query) -> Optional[tuple]:
        """Cache key of one density aggregation: everything that decides
        the grid — filter, grid spec, weight column, projection, and the
        schema generation (a write re-keys instead of serving stale)."""
        from geomesa_tpu.filter.parser import to_cql

        spec = query.hints.get("density") or {}
        try:
            env = tuple(float(v) for v in spec["envelope"])
            w, h = int(spec["width"]), int(spec["height"])
        except (KeyError, TypeError, ValueError):
            return None
        return (
            "density", name, self.schema_generation(name),
            to_cql(query.filter), env, w, h, spec.get("weight"),
            tuple(query.properties) if query.properties is not None else None,
        )

    @staticmethod
    def _untransformed(query: Query) -> bool:
        """Device aggregation push-downs (and the aggregate cache)
        evaluate STORED columns — a query transform (computed property)
        changes what the host path would aggregate, so any transform
        keeps aggregation on the host. Same containment test
        QueryTransforms.parse uses, without building and discarding the
        transform ASTs per query."""
        return not query.properties or not any(
            "=" in p for p in query.properties
        )

    def _agg_shortcut(
        self, name, ft, query: Query, plan, untransformed: bool
    ) -> Optional[QueryResult]:
        """Aggregate-cache lookups ahead of the push-down dispatch; the
        caller audits the returned result like any other (satisfying the
        cache-hit QueryEvent/receipt contract)."""
        from geomesa_tpu.ops.pyramid import agg_enabled, agg_knobs

        if not agg_enabled():
            return None
        # ANY non-aggregation hint declines: sampling/sample_by change
        # the row set, loose_bbox changes the filter contract (loose and
        # exact grids must never share a memo entry), and an unknown
        # future hint is assumed semantics-altering until proven not
        if set(query.hints) - set(AGGREGATION_HINTS) or not untransformed:
            return None
        hints = set(query.hints) & set(AGGREGATION_HINTS)
        if hints == {"density"}:
            key = self._density_key(name, query)
            if key is None:
                return None
            _b, _l, ttl, _c = agg_knobs()
            entry = self._agg_cache_obj().get(key, ttl)
            if entry is None:
                return None
            plan.scan_path = "agg-cache-density"
            trace.set_attr("agg.cache", "hit")
            plans_mod.note("pyramid", "hit")
            return QueryResult(
                ft, _empty_columns(ft), plan, {"density": entry.grid.copy()}
            )
        if hints == {"stats"}:
            stat = _count_only_stats(query.hints["stats"])
            if stat is None:
                return None
            n = self._count_pyramid(name, ft, query, plan)
            if n is None:
                return None
            for s in stat.stats if hasattr(stat, "stats") else [stat]:
                s.count = n
            plan.scan_path = "agg-pyramid-stats"
            trace.set_attr("agg.cache", "hit")
            plans_mod.note("pyramid", "hit")
            return QueryResult(ft, _empty_columns(ft), plan, {"stats": stat})
        return None

    def _agg_density_fill(
        self, name, query: Query, untransformed: bool, result: QueryResult
    ) -> None:
        """Memoize a just-computed density grid (device or host path) so
        the next identical dashboard tile answers with zero dispatch."""
        from geomesa_tpu.ops.pyramid import agg_enabled

        if not agg_enabled():
            return
        # same hint whitelist as _agg_shortcut: a loose_bbox (or sampled)
        # grid must never be memoized where an exact query could hit it
        if set(query.hints) - set(AGGREGATION_HINTS) or not untransformed:
            return
        if set(query.hints) & set(AGGREGATION_HINTS) != {"density"}:
            return
        grid = (result.aggregate or {}).get("density")
        if grid is None:
            return
        key = self._density_key(name, query)
        if key is None:
            return
        from geomesa_tpu.ops.pyramid import DensityMemo

        self._agg_cache_obj().put(key, DensityMemo(np.asarray(grid)))

    def aggregate(
        self,
        name: str,
        query: Union[str, Query] = "INCLUDE",
        columns: Optional[Sequence[str]] = None,
    ) -> Dict[str, Any]:
        """Lightweight spatial aggregate: exact matching-row count plus
        per-column sum/min/max/non-null-count for numeric ``columns``.

        Spatial-only filters over the default geometry answer from the
        aggregate pyramid (interior partial sums fused with the exact
        boundary-ring scan — ops/pyramid.py); anything else falls back
        to the ordinary exact query. Counts, integer sums, and min/max
        are identical between the two paths by construction; float sums
        may differ in the last ulp (summation order). Runs under the
        standard query envelope (budget + one admission slot)."""
        from geomesa_tpu.ops.pyramid import AggError

        ft = self.get_schema(name)
        q = self._as_query(query)
        cols = list(columns or [])
        for c in cols:
            a = next((a for a in ft.attributes if a.name == c), None)
            if a is None:
                raise AggError(f"unknown column {c!r}")
            dt = a.type.numpy_dtype
            if dt is None or np.dtype(dt).kind not in "iufb":
                raise AggError(f"column {c!r} is not numeric")
        import time as _time

        from geomesa_tpu.utils.audit import QueryTimeout, ShedLoad

        t0 = _time.perf_counter()
        root = trace.NOOP
        ptok = plans_mod.begin()
        wtok = workload_mod.op_begin()
        try:
            with trace.span(
                "query.aggregate", force=self.slow_query_s is not None,
                type=name,
            ) as root:
                try:
                    with deadline_mod.budget(self.query_timeout_s):
                        with self.admission.admit(
                            priority=admission_mod.classify(q.hints)
                        ):
                            self._prepare_query(name, q)
                            got = self._aggregate_pyramid(name, ft, q, cols)
                            if got is None:
                                # exact fallback: the ordinary scan
                                # (admission slot and budget are
                                # reentrant — PR 7 / PR 6 semantics)
                                res = self.query(name, q)
                                got = _aggregate_columns(ft, res.columns, cols)
                                agg_path = "agg-exact-fallback"
                            else:
                                agg_path = "agg-pyramid"
                                plans_mod.note("pyramid", "hit")
                                if root.recording:
                                    root.set_attr("agg.cache", "hit")
                            # aggregate-class accounting (the SLO engine's
                            # `aggregate` class, utils/slo.py): one counter
                            # + timer per surface call. The exact-fallback
                            # inner query also audits as a `query` — the
                            # classes are separate trails, like joins
                            if self.metrics is not None:
                                self.metrics.inc("queries.aggregate")
                                self.metrics.update_timer(
                                    "query.aggregate",
                                    _time.perf_counter() - t0,
                                )
                            fid = ""
                            if plans_mod.enabled():
                                # aggregate-class fingerprint; the exact
                                # fallback's inner query fingerprinted
                                # itself (and drained the pending scope)
                                # as a `query` already
                                fid = self._plans_obj().observe(
                                    "aggregate", name, query=q,
                                    scan_path=agg_path, outcome="ok",
                                    hits=int(got.get("count", 0)),
                                    duration_s=_time.perf_counter() - t0,
                                )
                            self._observe_workload(
                                "aggregate", name, query=q, outcome="ok",
                                duration_s=_time.perf_counter() - t0,
                                rows=int(got.get("count", 0)),
                                fingerprint=fid,
                                extra={"columns": cols} if cols else None,
                            )
                            return got
                except (QueryTimeout, ShedLoad) as e:
                    outcome = (
                        "timeout" if isinstance(e, QueryTimeout) else "shed"
                    )
                    if root.recording:
                        root.set_attr("outcome", outcome)
                    if self.metrics is not None:
                        # aggregate-scoped only (the query_join rule): an
                        # inner query's own timeout already audited into
                        # queries/queries.<outcome>
                        self.metrics.inc("queries.aggregate")
                        self.metrics.inc(f"queries.aggregate.{outcome}")
                    fid = ""
                    if plans_mod.enabled():
                        fid = self._plans_obj().observe(
                            "aggregate", name, query=q, outcome=outcome,
                            duration_s=_time.perf_counter() - t0,
                        )
                    self._observe_workload(
                        "aggregate", name, query=q, outcome=outcome,
                        duration_s=_time.perf_counter() - t0,
                        fingerprint=fid,
                        extra={"columns": cols} if cols else None,
                    )
                    raise
        finally:
            plans_mod.end(ptok)
            workload_mod.op_end(wtok)
            self._log_slow_query(name, None, root)

    def _aggregate_pyramid(
        self, name, ft, q: Query, cols: List[str]
    ) -> Optional[Dict[str, Any]]:
        if q.max_features is not None or q.hints:
            return None
        plan = self._plan_cached(name, q)
        got = self._pyramid_classify(name, ft, q, plan)
        if got is None:
            return None
        pyr, interior, cells, imask = got
        try:
            pyr.ensure_columns(self._tables[name]["z2"], ft, cols)
        except Exception as e:  # noqa: BLE001 - injected/device build failure
            from geomesa_tpu.utils.audit import QueryTimeout, robustness_metrics

            if isinstance(e, QueryTimeout):
                raise  # the query's budget died, not the build
            robustness_metrics().inc("degrade.agg_to_scan")
            trace.event(
                "degrade.agg_to_scan", reason=f"{type(e).__name__}: {e}"
            )
            audit_mod.decision(
                "pyramid", "build_degraded", error=type(e).__name__
            )
            return None  # the caller answers from the uncached exact scan
        parts = (
            self._agg_boundary_parts(name, ft, plan, pyr.cell_ranges(cells))
            if len(cells)
            else []
        )
        out: Dict[str, Any] = {
            "count": interior + sum(len(r) for _b, r in parts),
            "columns": {},
        }
        for c in cols:
            g = pyr.col_grids[c]
            occupied = imask & (g["count"] > 0)
            cnt = int(g["count"][imask].sum())
            total = g["sum"][imask].sum()
            mn = g["min"][occupied].min() if occupied.any() else np.inf
            mx = g["max"][occupied].max() if occupied.any() else -np.inf
            for block, rows in parts:
                v = block.gather(c, rows)
                nulls = np.asarray(
                    block.gather(c + "__null", rows), dtype=bool
                )
                vv = np.asarray(v)[~nulls]
                if len(vv):
                    cnt += len(vv)
                    total = total + vv.sum()
                    mn = min(mn, float(vv.min()))
                    mx = max(mx, float(vv.max()))
            out["columns"][c] = _column_summary(ft, c, cnt, total, mn, mx)
        return out

    # -- queries ------------------------------------------------------------

    def planner(self, name: str) -> QueryPlanner:
        return QueryPlanner(self.get_schema(name), self._indices[name], self.stats)

    def explain(self, name: str, query: Union[str, Query]) -> str:
        query = self._as_query(query)
        plan = self.planner(name).plan(query)
        return plan.explain

    def explain_analyze(
        self, name: str, query: Union[str, Query] = "INCLUDE"
    ) -> Dict[str, Any]:
        """EXPLAIN ANALYZE: run the query for real under a FORCED trace
        and return the plan tree annotated with what actually happened —
        per-stage wall/self-times, rows in/out per scanned block, the
        device cost receipt, every reason-coded decision taken
        (``utils.audit.decision``), and the plan-time estimate vs the
        consume-time actuals. Exposed as ``POST /explain`` (web.py).

        The query executes through the ordinary envelope (budget,
        admission, audit, fingerprinting) — an EXPLAIN ANALYZE is a real
        query whose answer is its own telemetry, so overload semantics
        (ShedLoad/QueryTimeout) apply unchanged."""
        q = self._as_query(query)
        # the forced wrapper span makes query()'s whole tree record even
        # with no exporter installed; capturing the child directly (not
        # through an exporter ring) keeps concurrent queries out
        with trace.span("explain.analyze", force=True, type=name) as wrap:
            result = self.query(name, q)
        root = next(
            (c for c in wrap.children if c.name == "query"), wrap
        )
        plan = result.plan
        blocks = root.find("scan.block")
        rows_in = sum(int(b.attributes.get("rows_in", 0)) for b in blocks)
        rows_out = sum(int(b.attributes.get("rows_out", 0)) for b in blocks)
        # self-time attribution: how much of the audited wall the NAMED
        # stages explain (the PR 2 >=90% contract, now per execution).
        # Concurrent stages (a sharded fan-out's parallel scans) can sum
        # past the wall; the fraction clamps at 1.0 — "fully attributed"
        attributed = sum(
            s.self_time_ms for s in root.walk() if s is not root
        )
        decisions = [
            {
                "point": ev["name"][len("decision."):],
                **{k: v for k, v in ev.items() if k not in ("name", "t_ms")},
            }
            for sp in root.walk()
            for ev in sp.events
            if ev["name"].startswith("decision.")
        ]
        est_cost = float(getattr(plan, "cost", 0.0) or 0.0)
        scan_path = self._collect_scan_path(plan)
        key = plans_mod.fingerprint_key(
            "query", name, plan=plan, query=q, scan_path=scan_path
        )
        out: Dict[str, Any] = {
            "type": name,
            "trace_id": root.trace_id,
            "fingerprint": plans_mod.fingerprint_id(key),
            "plan": {
                "index": getattr(plan.index, "name", ""),
                "scan_path": scan_path,
                "union_arms": len(plan.union) if plan.union else 0,
                "explain": plan.explain,
            },
            "estimate": {
                "cost": est_cost,
                "ranges": len(plan.ranges),
            },
            "actual": {
                "hits": len(result),
                "rows_scanned": rows_in,
                "rows_out": rows_out,
                "blocks": len(blocks),
                "duration_ms": round(root.duration_ms, 3),
            },
            # signed log2: +k = the cost model UNDER-estimated by ~2^k
            "misestimate_log2": round(
                math.log2((rows_in + 1.0) / (est_cost + 1.0)), 3
            ),
            "receipt": root.attributes.get("device", {}),
            "attribution": {
                "attributed_ms": round(attributed, 3),
                "total_ms": round(root.duration_ms, 3),
                "fraction": round(
                    min(attributed / root.duration_ms, 1.0), 4
                ) if root.duration_ms > 0 else 1.0,
            },
            "decisions": decisions,
            "stages": _stage_tree(root),
        }
        # fleet queries: how much of the plan executed with worker-side
        # attribution (parallel/fleet.py trace stitching) — each
        # fleet.rpc either carries its grafted worker subtree (the scan/
        # post-filter spans above came THROUGH the worker) or stands as
        # a reason-coded stub (trailer over budget / worker lost /
        # stitching off)
        rpcs = root.find("fleet.rpc")
        if rpcs:
            stitched = sum(
                1
                for s in rpcs
                if any(c.attributes.get("stitched") for c in s.children)
            )
            out["fleet"] = {
                "rpcs": len(rpcs),
                "stitched": stitched,
                "stubs": len(rpcs) - stitched,
            }
        return out

    def query(self, name: str, query: Union[str, Query] = "INCLUDE") -> QueryResult:
        import time as _time

        from geomesa_tpu.utils.audit import QueryTimeout, ShedLoad

        ft = self.get_schema(name)
        query = self._as_query(query)
        # one span tree per query: plan -> range decomposition -> per-block
        # scans -> device dispatch/fetch (or degradation) -> post-filter.
        # Forced (exporter or not) when a slow-query budget is set, so the
        # slow log always has a tree to dump — including for queries that
        # RAISE (a timeout is exactly the query the slow log exists for).
        root = trace.NOOP
        plan = None
        # plan-quality pending scope (utils/plans.py): decisions and
        # per-block row actuals collect here until _audit folds them
        # into the fingerprint. None (one flag read) when disabled.
        ptok = plans_mod.begin()
        # workload op-depth marker: a query invoked INSIDE a join or
        # aggregate captures as nested (not directly re-driven by replay)
        wtok = workload_mod.op_begin()
        try:
            with trace.span(
                "query", force=self.slow_query_s is not None, type=name
            ) as root:
                t_admit = _time.perf_counter()
                try:
                    # the deadline starts at ADMISSION: queue wait, lazy
                    # replay, planning, and every retry/backoff below all
                    # spend the same budget — a query can never cost more
                    # than its deadline (± one fault-point granularity)
                    with deadline_mod.budget(self.query_timeout_s):
                        with self.admission.admit(
                            priority=admission_mod.classify(query.hints)
                        ):
                            # cross-query coalescing (parallel/batch.py):
                            # STRICTLY after admit — shedding semantics
                            # untouched — concurrently admitted queries
                            # of this type may ride one stacked device
                            # sweep. None = run the solo path below
                            # (quiet store, disabled, or seam degraded:
                            # identical answers either way).
                            out = self._coalesced(name, ft, query)
                            if out is not None:
                                plan = out.plan
                                plans_mod.note(
                                    "coalesce",
                                    "joined" if out.group_n > 1 else "solo",
                                )
                                if root.recording:
                                    root.set_attr("hits", len(out.result))
                                    root.set_attr(
                                        "scan_path",
                                        self._collect_scan_path(plan),
                                    )
                                    root.set_attr("device", out.receipt)
                                    root.set_attr("coalesced", out.group_n)
                                if self._auditing():
                                    self._audit(
                                        name, query, plan, out.result,
                                        t_admit, t_admit + out.plan_s,
                                        out.receipt,
                                    )
                                return out.result
                            # device cost receipt baseline: taken BEFORE
                            # preparation so a lazy store's replay uploads
                            # attribute to the query that paid for them
                            # (three dict reads — hot-path safe)
                            dev0 = devstats.receipt_snapshot()
                            self._prepare_query(name, query)
                            # the audited clock starts AFTER preparation:
                            # a lazy store's partition replay is traced
                            # (fs.load) but must not inflate the audited
                            # planning time
                            t_start = _time.perf_counter()
                            plan = self._plan_cached(name, query)
                            t_planned = _time.perf_counter()
                            result = self._execute(
                                name, ft, query, plan, t_planned
                            )
                            receipt = devstats.receipt_since(dev0)
                            if root.recording:
                                root.set_attr("hits", len(result))
                                root.set_attr(
                                    "scan_path", self._collect_scan_path(plan)
                                )
                                # the receipt rides the root span too: the
                                # slow-query log renders it next to the
                                # tree it explains
                                root.set_attr("device", receipt)
                            if self._auditing():
                                self._audit(
                                    name, query, plan, result, t_start,
                                    t_planned, receipt,
                                )
                            return result
                except (QueryTimeout, ShedLoad) as e:
                    # crisp failure: a timed-out or shed query NEVER
                    # returns a truncated result set — but it still
                    # audits, so overload is visible in the same trail
                    # as the queries it protected
                    outcome = (
                        "timeout" if isinstance(e, QueryTimeout) else "shed"
                    )
                    if root.recording:
                        root.set_attr("outcome", outcome)
                    if self._auditing():
                        self._audit_failure(name, query, plan, t_admit, outcome)
                    raise
        finally:
            plans_mod.end(ptok)
            workload_mod.op_end(wtok)
            self._log_slow_query(name, plan, root)

    def _prepare_query(self, name: str, query: Query) -> None:
        """Pre-execution hook inside the query's root span — subclasses
        that must materialize state first (FsDataStore's lazy partition
        replay) override this so that work lands ON the query's trace."""

    # -- cross-query coalescing (parallel/batch.py) --------------------------

    def _coalescer_obj(self):
        """The per-store coalescer, created lazily (GIL-atomic
        setdefault, the _agg_cache_obj rule: two concurrent firsts must
        agree on ONE instance or their groups could never meet)."""
        co = getattr(self, "_coalescer", None)
        if co is None:
            from geomesa_tpu.parallel.batch import QueryCoalescer

            co = self.__dict__.setdefault("_coalescer", QueryCoalescer(self))
        return co

    def _coalesced(self, name: str, ft, query: Query):
        """Hand one ADMITTED query to the coalescer when coalescing can
        actually help. Returns a batch.MemberOutcome, or None for the
        solo path. Gates, cheapest first: the class opt-out, an executor
        without the stacked-sweep seam, the geomesa.batch.* knobs, and —
        the latency guard — actual concurrency (another query in flight,
        or a group already gathering): a quiet store's queries never pay
        the window."""
        if not self.COALESCE_QUERIES:
            return None
        if getattr(self.executor, "dispatch_coalesced", None) is None:
            return None
        from geomesa_tpu.parallel.batch import batch_knobs

        enabled, _window_s, _max_q = batch_knobs()
        if not enabled:
            return None
        co = self._coalescer_obj()
        if self.admission.inflight < 2 and not co.gathering(name):
            return None
        return co.submit(name, ft, query)

    def query_join(
        self,
        build,
        probe,
        predicate: Union[str, Any] = "contains",
        *,
        radius_m: Optional[float] = None,
    ):
        """Spatial join: which probe features match which build features.

        ``build``/``probe`` are type names or ``(name, query)`` pairs
        (per-side filters push down through the ordinary scan pipeline);
        ``predicate`` is ``"contains"`` (probe point in build polygon,
        boundary inclusive) or ``"dwithin(<meters>)"`` /
        ``("dwithin", radius_m=...)``. The build side is bucketed once
        per schema generation into an HBM-resident Z-grid (ops/join.py)
        with adaptive skew splits; the probe side streams through the
        device kernels with exact f64 verification of boundary pairs,
        and ANY device failure degrades to the host reference join with
        identical pairs. Returns ``ops.join.JoinResult``.

        The whole join runs under one query budget (inner build/probe
        queries link their own budgets to it, PR 4/6 semantics) and
        holds ONE admission slot end to end — the device probe loop is
        the expensive phase, so it must count against
        ``geomesa.query.max.inflight`` like any scan. The inner queries
        ride the outer slot (reentrant admit per controller), so a join
        costs exactly one slot and can never deadlock against itself."""
        import time as _time

        from geomesa_tpu.ops.join import JoinPlanner, JoinSpec
        from geomesa_tpu.utils.audit import QueryTimeout, ShedLoad

        spec = JoinSpec.parse(predicate, radius_m)
        build_name, build_q = self._join_side(build)
        probe_name, probe_q = self._join_side(probe)
        root = trace.NOOP
        t0 = _time.perf_counter()
        ptok = plans_mod.begin()
        wtok = workload_mod.op_begin()
        try:
            with trace.span(
                "query.join", force=self.slow_query_s is not None,
                build=build_name, probe=probe_name, predicate=spec.kind,
            ) as root:
                try:
                    with deadline_mod.budget(self.query_timeout_s):
                        # ONE admission slot for the whole join: the
                        # kernel probe loop is the expensive phase and
                        # must count against max_inflight like any scan;
                        # the inner build/probe queries ride this slot
                        # (reentrant admit), so a join can never
                        # deadlock against itself
                        with self.admission.admit(
                            priority=admission_mod.classify(probe_q.hints)
                        ):
                            dev0 = devstats.receipt_snapshot()
                            result = JoinPlanner(self).join(
                                build_name, build_q, probe_name, probe_q,
                                spec,
                            )
                        receipt = devstats.receipt_since(dev0)
                        if root.recording:
                            root.set_attr("join", result.stats)
                            root.set_attr("hits", len(result))
                            root.set_attr("device", receipt)
                        if self.metrics is not None:
                            self.metrics.inc("queries.join")
                            self.metrics.update_timer(
                                "query.join", _time.perf_counter() - t0
                            )
                        fid = ""
                        if plans_mod.enabled():
                            # join-class fingerprint: predicate kind as
                            # the shape, the answering path (device/host/
                            # degraded) as the scan path — the inner
                            # build/probe queries fingerprinted (and
                            # drained) themselves as `query`s already
                            fid = self._plans_obj().observe(
                                "join",
                                f"{build_name}+{probe_name}",
                                shape=f"join:{spec.kind}",
                                scan_path=str(
                                    result.stats.get("path", "")
                                ),
                                outcome="ok", hits=len(result),
                                duration_s=_time.perf_counter() - t0,
                                receipt=receipt,
                            )
                        self._observe_workload(
                            "join", f"{build_name}+{probe_name}",
                            tenant=self._join_tenant(build_q, probe_q),
                            outcome="ok",
                            duration_s=_time.perf_counter() - t0,
                            rows=len(result), receipt=receipt,
                            fingerprint=fid,
                            extra=self._join_extra(
                                build_name, build_q, probe_name, probe_q,
                                spec,
                            ),
                        )
                        return result
                except (QueryTimeout, ShedLoad) as e:
                    # crisp failure: a timed-out join never returns a
                    # truncated pair set — and it audits like any other
                    # query (a join shed at admission never ran its
                    # inner build/probe queries, so without this event
                    # the outcome would be invisible to the PR 4
                    # QueryEvent.outcome accounting)
                    outcome = (
                        "timeout" if isinstance(e, QueryTimeout) else "shed"
                    )
                    if root.recording:
                        root.set_attr("outcome", outcome)
                    if self.metrics is not None:
                        # join-scoped counters only: a timeout inside an
                        # inner build/probe query already audited itself
                        # into queries/queries.<outcome> — counting the
                        # join there too would show 2 failures for 1 join
                        self.metrics.inc("queries.join")
                        self.metrics.inc(f"queries.join.{outcome}")
                    fid = ""
                    if plans_mod.enabled():
                        fid = self._plans_obj().observe(
                            "join", f"{build_name}+{probe_name}",
                            shape=f"join:{spec.kind}", outcome=outcome,
                            duration_s=_time.perf_counter() - t0,
                        )
                    self._observe_workload(
                        "join", f"{build_name}+{probe_name}",
                        tenant=self._join_tenant(build_q, probe_q),
                        outcome=outcome,
                        duration_s=_time.perf_counter() - t0,
                        fingerprint=fid,
                        extra=self._join_extra(
                            build_name, build_q, probe_name, probe_q, spec,
                        ),
                    )
                    if self.audit_writer is not None:
                        self._audit_failure(
                            build_name + "+" + probe_name, probe_q, None,
                            t0, outcome, count_metrics=False,
                        )
                    raise
        finally:
            plans_mod.end(ptok)
            workload_mod.op_end(wtok)
            self._log_slow_query(build_name + "+" + probe_name, None, root)

    @staticmethod
    def _join_tenant(build_q, probe_q) -> str:
        """Tenant label for a join: probe hint wins, then build hint."""
        label = tenants_mod.tenant_of(probe_q)
        if label == tenants_mod.ANON:
            label = tenants_mod.tenant_of(build_q)
        return label

    @staticmethod
    def _join_extra(build_name, build_q, probe_name, probe_q, spec):
        """Replay payload for a captured join (both sides as CQL)."""
        if not workload_mod.enabled():
            return None
        from geomesa_tpu.filter.parser import to_cql

        return {
            "join": {
                "build": [build_name, to_cql(build_q.filter)],
                "probe": [probe_name, to_cql(probe_q.filter)],
                "predicate": spec.kind,
                "radius_m": spec.radius_m,
            }
        }

    def _join_side(self, side) -> tuple:
        """``"name"`` or ``(name, cql-or-Query)`` -> (name, Query)."""
        if isinstance(side, str):
            name, q = side, Query()
        else:
            name, q = side
            q = self._as_query(q)
        self.get_schema(name)  # fail fast on unknown types
        return name, q

    def query_many(
        self, name: str, queries: Sequence[Union[str, Query]]
    ) -> List[QueryResult]:
        """Execute many queries with PIPELINED device dispatch.

        Phase 1 plans every query and starts its device pre-filters
        back-to-back with no host synchronization between them; phase 2
        resolves and post-filters in order. Over a high-latency device link
        the round-trip cost is paid once per batch instead of once per
        query — the client-side BatchScanner thread-pool analog
        (AccumuloQueryPlan.scala:113-140 fans scans across tservers the
        same way). Results are positionally identical to [query(name, q)
        for q in queries].
        """
        ft = self.get_schema(name)
        qs = [self._as_query(q) for q in queries]
        # one batch root: shared preparation (a lazy store's partition
        # replay) and the per-query spans all land on ONE tree — without
        # it the fs.load span would export as an orphan root and the
        # batch queries' trees would omit the replay cost entirely.
        # Forced under a slow-query budget like query()'s root, so batch
        # overhead (replay, planning) stays slow-log-visible too.
        batch = trace.NOOP
        try:
            with trace.span(
                "query.batch", force=self.slow_query_s is not None,
                type=name, n=len(qs),
            ) as batch:
                # a batch admits as ONE unit: its queries share a
                # pipeline and must never deadlock against their own
                # batchmates waiting for slots. The queue wait itself is
                # bounded by one query budget (the per-phase budgets
                # below don't exist yet while we wait).
                # the batch classifies as its MOST important member
                # (lowest PRIORITIES index): a background flood must not
                # shed the one critical query riding the same batch
                batch_pri = min(
                    (admission_mod.classify(q.hints) for q in qs),
                    key=admission_mod.PRIORITIES.index,
                    default=None,
                )
                with self.admission.admit(self.query_timeout_s, batch_pri):
                    # batch-level cost receipt: the pipelined phase-1 work
                    # (mirror uploads, compiles triggered by dispatch_many)
                    # happens OUTSIDE the per-query resolve windows, so the
                    # batch root carries the whole stream's delta — the
                    # per-query receipts cover only each resolve phase
                    dev0 = devstats.receipt_snapshot()
                    # the shared pipeline phase (replay, planning, batched
                    # dispatch) is one query's worth of shared work: it
                    # gets one budget; each per-query resolve then runs
                    # under its own (so a batch of N costs at most N+1
                    # budgets, and any SINGLE query at most 2)
                    with deadline_mod.budget(self.query_timeout_s):
                        for q in qs:
                            self._prepare_query(name, q)
                    results = self._query_many_planned(name, ft, qs)
                    if batch.recording:
                        batch.set_attr("device", devstats.receipt_since(dev0))
                    return results
        finally:
            self._log_slow_batch(name, batch)

    def _log_slow_batch(self, name: str, batch) -> None:
        """query_many edition of the slow-query log: the batch's OWN
        overhead — shared preparation (a lazy store's partition replay)
        plus pipelined planning/dispatch, i.e. everything outside the
        per-query spans — over budget dumps the batch tree. Per-query
        trees log themselves via _log_slow_query.

        Members that rode a coalesced sweep get PER-MEMBER attribution:
        the shared batched-buffer fetch blocks inside whichever member
        resolves first, so that member's raw span wall carries the whole
        sweep. Each ``device.fetch.shared`` span records how many
        queries its buffer served (``shared_q``); the log re-attributes
        each member's wall as raw minus the (q-1)/q share of shared
        fetches that belong to its sweep-mates, so "which member was
        actually slow" stays answerable."""
        import logging as _logging

        from geomesa_tpu.utils.audit import slow_query_note

        if self.slow_query_s is None or not batch.recording:
            return
        own_ms = batch.duration_ms - sum(
            c.duration_ms for c in batch.children if c.name == "query"
        )
        if own_ms < self.slow_query_s * 1000.0:
            return
        if not slow_query_note({
            "kind": "batch",
            "type": name,
            "trace_id": batch.trace_id,
            "duration_ms": round(batch.duration_ms, 1),
            "overhead_ms": round(own_ms, 1),
            "budget_ms": round(self.slow_query_s * 1000.0, 1),
        }):
            return  # storm guard: render shed, summary retained
        members = []
        for i, c in enumerate(
            c for c in batch.children if c.name == "query"
        ):
            shared_ms = sum(
                s.duration_ms * (s.attributes.get("shared_q", 1) - 1)
                / max(s.attributes.get("shared_q", 1), 1)
                for s in c.find("device.fetch.shared")
            )
            attributed = c.duration_ms - shared_ms
            members.append(
                f"  member {i}: {attributed:.1f}ms attributed"
                + (
                    f" (raw {c.duration_ms:.1f}ms includes "
                    f"{shared_ms:.1f}ms of sweep-mates' shared fetch)"
                    if shared_ms > 0.0
                    else f" (raw {c.duration_ms:.1f}ms)"
                )
            )
        _logging.getLogger("geomesa_tpu.slowquery").warning(
            "slow query batch type=%s trace=%s overhead %.1fms of %.1fms "
            "total (budget %.0fms)\n%s\n%s",
            name, batch.trace_id, own_ms, batch.duration_ms,
            self.slow_query_s * 1000.0, "\n".join(members), batch.render(),
        )

    # -- streaming result delivery (arrow/vector.py) -------------------------

    def query_stream(
        self,
        name: str,
        query: Union[str, Query] = "INCLUDE",
        batch_rows: Optional[int] = None,
        dictionary_encode: Sequence[str] = (),
    ):
        """Streaming query: an iterator of Arrow ``RecordBatch``es, one
        (or more, capped at ``geomesa.stream.batch.rows`` rows) per
        scanned block — the first batch flushes while later blocks are
        still scanning, so first-byte latency stops paying for full
        materialization. Exposed over HTTP as chunked transfer encoding
        (web.py: ``GET /query?stream=1``, ``POST /query/stream``).

        Contract:

        * always yields at least ONE batch (an empty one for zero rows),
          so consumers can read the schema from the stream itself;
        * concatenating the batches equals ``query()`` on the same
          query — limit, projection, and union-arm dedupe included
          (order within the stream is scan order; a plain ``query()``
          streams in the same order);
        * sort / sampling / derived-transform queries cannot stream
          incrementally — they fall back to full materialization and
          then chunk the finished result (identical answers, no
          first-byte win); aggregation hints raise ``ValueError``
          (a density grid is not a feature stream);
        * runs under ONE admission slot and ONE query budget for the
          LIFETIME of the iteration — a consumer that stalls past the
          budget gets ``QueryTimeout`` at the next block, never a
          silently truncated stream; closing the iterator early
          releases the slot;
        * ``dictionary_encode`` names string columns to ship as Arrow
          dictionaries — ONE unified dictionary across every batch of
          the stream (append-only growth, delta dictionaries on the
          IPC wire), so the streamed concat equals the materialized
          table encoding included.
        """
        from geomesa_tpu.index.aggregators import has_aggregation as _has_agg
        from geomesa_tpu.utils.config import STREAM_BATCH_ROWS

        ft = self.get_schema(name)
        q = self._as_query(query)
        if _has_agg(q.hints):
            raise ValueError(
                "aggregation queries have no feature stream; use query()"
            )
        if batch_rows is None:
            batch_rows = STREAM_BATCH_ROWS.to_int() or 8192
        gen = self._stream_gen(
            name, ft, q, max(1, int(batch_rows)), tuple(dictionary_encode)
        )
        if self.metrics is None:
            return gen
        return self._stream_first_timed(gen)

    def _stream_first_timed(self, gen):
        """Wrap a result stream to time its FIRST batch — the
        ``query.stream.first`` timer behind the stream_first_batch SLO
        class (utils/slo.py) and the `stream` bench leg's headline
        number. The clock starts at the consumer's first ``next()``
        (this wrapper is itself a generator), so producer-side setup the
        consumer never waited on is not charged."""
        import time as _time

        t0 = _time.perf_counter()
        first = True
        try:
            for b in gen:
                if first:
                    first = False
                    self.metrics.update_timer(
                        "query.stream.first", _time.perf_counter() - t0
                    )
                yield b
        finally:
            # a consumer closing THIS wrapper must close the underlying
            # stream NOW (releasing its admission slot), not at GC
            gen.close()

    def _stream_gen(self, name, ft, q: Query, batch_rows: int,
                    dictionary_encode: tuple = ()):
        """query_stream's generator body. Context managers must not span
        a yield (a contextvar leaking into the consumer), so the budget
        is an EXPLICIT Deadline attached around each step's work, and
        admission uses the controller primitives directly (honoring the
        reentrant-slot contract) instead of the context manager."""
        import time as _time

        from geomesa_tpu.arrow.vector import SimpleFeatureVector
        from geomesa_tpu.index.transforms import QueryTransforms

        t0 = _time.perf_counter()
        dl = (
            deadline_mod.Deadline(self.query_timeout_s)
            if self.query_timeout_s is not None
            else None
        )
        ctl = self.admission
        pri = admission_mod.classify(q.hints)
        rode_slot = ctl._ctx_held.get()
        if not rode_slot:
            # the brownout gate runs here too (the _Admit context
            # manager's posture): a shed-class stream refuses in O(1)
            # before any slot bookkeeping
            bo = ctl.brownout
            if bo is not None and bo.level > 0 and bo.should_shed(pri):
                from geomesa_tpu.utils import brownout as brownout_mod

                if brownout_mod.enabled():
                    ctl._brownout_shed(
                        pri, bo.level, bo.retry_after_s(), fail_fast=False
                    )
            with deadline_mod.attach(dl):
                ctl._acquire(pri)
        hits = 0
        plan = None
        # plans pending scope, generator edition: the collector object
        # lives for the whole stream, but the contextvar is re-entered
        # around each step (plans_mod.attach — a contextvar must never
        # stay set across a yield, the deadline.attach posture)
        pend = plans_mod.pending()
        try:
            dev0 = devstats.receipt_snapshot()
            with deadline_mod.attach(dl), plans_mod.attach(pend):
                with trace.span("query.stream", type=name):
                    self._prepare_query(name, q)
                    plan = self._plan_cached(name, q)
            t_planned = _time.perf_counter()
            # merge-free shapes stream incrementally; sort/sampling/
            # derived-transform queries must see ALL rows first
            mergeless = (
                not q.sort_by
                and not q.hints.get("sampling")
                and QueryTransforms.parse(ft, q.properties) is None
            )
            streamable = self.STREAMS_LOCAL_PARTS and mergeless
            shard_parts = None
            if not streamable and mergeless and not plan.is_empty:
                # sharded coordinators stream per-shard partial batches
                # through the incremental gather (parallel/shards.py
                # _iter_stream_shard_cols); None = no such seam (or the
                # geomesa.stream.shard.incremental escape hatch is off)
                shard_parts = self._iter_stream_shard_cols(
                    name, ft, q, plan, t0
                )
            if streamable and not plan.is_empty:
                out_ft = (
                    _narrow_ft(ft, q.properties)
                    if q.properties is not None
                    else ft
                )
                # ONE vector for the whole stream: its unified per-column
                # dictionaries persist across batches (delta dictionaries
                # on the wire, not per-batch replacements)
                vec = SimpleFeatureVector(out_ft, dictionary_encode)
                remaining = q.max_features
                # union arms may overlap: first-occurrence fid dedupe,
                # incremental (same winners as _dedupe_by_fid's)
                seen = set() if plan.union is not None else None
                parts = self._iter_stream_parts(name, ft, q, plan, t0)
                while remaining is None or remaining > 0:
                    batches = []
                    with deadline_mod.attach(dl), plans_mod.attach(pend):
                        try:
                            block, rows = next(parts)
                        except StopIteration:
                            break
                        cols = _materialize(
                            self._columns_from_parts(
                                ft, q, [(block, rows)]
                            )
                        )
                        if seen is not None:
                            cols = _dedupe_against(cols, seen)
                        n = len(cols.get("__fid__", ()))
                        if remaining is not None and n > remaining:
                            cols = {k: v[:remaining] for k, v in cols.items()}
                            n = remaining
                        for lo in range(0, n, batch_rows):
                            sub = {
                                k: v[lo : lo + batch_rows]
                                for k, v in cols.items()
                            }
                            batches.append(vec.to_batch(sub))
                        hits += n
                        if remaining is not None:
                            remaining -= n
                    for b in batches:
                        yield b
                if hits == 0:
                    yield vec.to_batch(_empty_columns(out_ft))
            elif shard_parts is not None:
                out_ft = (
                    _narrow_ft(ft, q.properties)
                    if q.properties is not None
                    else ft
                )
                vec = SimpleFeatureVector(out_ft, dictionary_encode)
                remaining = q.max_features
                # cross-shard fid dedupe is ALWAYS on here (replica
                # failover, hedges, and mid-rebalance dual-target writes
                # can each surface a fid twice): incremental first-
                # occurrence winners, the same rows _merge_shards'
                # _dedupe_by_fid keeps over the full gather
                seen: set = set()
                try:
                    while remaining is None or remaining > 0:
                        batches = []
                        with deadline_mod.attach(dl), plans_mod.attach(pend):
                            try:
                                cols = next(shard_parts)
                            except StopIteration:
                                break
                            cols = _dedupe_against(_materialize(cols), seen)
                            n = len(cols.get("__fid__", ()))
                            if remaining is not None and n > remaining:
                                cols = {
                                    k: v[:remaining] for k, v in cols.items()
                                }
                                n = remaining
                            for lo in range(0, n, batch_rows):
                                sub = {
                                    k: v[lo : lo + batch_rows]
                                    for k, v in cols.items()
                                }
                                batches.append(vec.to_batch(sub))
                            hits += n
                            if remaining is not None:
                                remaining -= n
                        for b in batches:
                            yield b
                finally:
                    # closing the stream mid-iteration must poison the
                    # still-running shard scans NOW (the generator's
                    # abort path), not at GC
                    shard_parts.close()
                if hits == 0:
                    yield vec.to_batch(_empty_columns(out_ft))
            else:
                # sort/sampling/transforms (or an empty plan): the
                # finished result chunks into batches — same answers,
                # no first-byte win
                with deadline_mod.attach(dl), plans_mod.attach(pend):
                    result = self._execute(name, ft, q, plan, t0)
                    cols = _materialize(result.columns)
                    vec = SimpleFeatureVector(result.ft, dictionary_encode)
                    n = len(cols.get("__fid__", ()))
                    hits = n
                    batches = [
                        vec.to_batch(
                            {k: v[lo : lo + batch_rows] for k, v in cols.items()}
                        )
                        for lo in range(0, n, batch_rows)
                    ] or [vec.to_batch(_empty_columns(result.ft))]
                for b in batches:
                    yield b
            if self._auditing():
                # observe() drains the stream's pending collector (rows
                # scanned per block, any decisions fired mid-stream) so
                # a streamed query's fingerprint record matches the
                # non-streamed edition of the same shape
                with deadline_mod.attach(dl), plans_mod.attach(pend):
                    self._audit(
                        name, q, plan, None, t0, t_planned,
                        devstats.receipt_since(dev0), hits=hits,
                        wl_cls="stream",
                    )
                if self.metrics is not None:
                    self.metrics.inc("queries.stream")
        finally:
            if not rode_slot:
                ctl._release(pri)

    def _iter_stream_shard_cols(self, name, ft, q: Query, plan, t0):
        """Sharded-streaming seam: coordinators whose rows live in shard
        workers (parallel/shards.ShardedDataStore, and the fleet tier on
        top of it) return a generator of per-shard-group column dicts,
        each yielded the moment its group's outcome is FINAL — the
        incremental edition of gather-then-chunk. None (this base class)
        means no such seam exists and ``_stream_gen`` falls back to full
        materialization for non-local stores."""
        return None

    def _iter_stream_parts(self, name, ft, q: Query, plan, t0):
        """Route+scan for the streaming path: yields (block, rows) per
        resolved block across every routed unit. Device degradation
        covers the window BEFORE a unit's first part is out (identical
        results via the host scan); after first emission a device
        failure fails the stream crisply — the consumer already holds
        earlier bytes, and a silent re-scan could duplicate them."""
        from geomesa_tpu.utils.audit import QueryTimeout

        for arm in self._route(q, plan):
            table = self._tables[name][arm.index.name]
            scan = self.executor.scan_candidates(table, arm)
            device_scan = scan is not None
            arm.scan_path = _scan_label(scan)
            emitted = False
            gen = self._iter_consume(ft, q, arm, table, scan, device_scan, t0)
            while True:
                try:
                    part = next(gen)
                except StopIteration:
                    break
                except Exception as e:
                    if not device_scan or emitted or isinstance(e, QueryTimeout):
                        raise
                    degrade = getattr(self.executor, "degrade", None)
                    if degrade is not None:
                        degrade(table, e)
                    arm.scan_path = "host-table-degraded"
                    # one degrade only: a failure of the HOST re-scan
                    # must propagate, not loop back through another
                    # degrade (device_scan False ends re-entry)
                    device_scan = False
                    gen = self._iter_consume(
                        ft, q, arm, table, None, False, t0
                    )
                    continue
                emitted = True
                yield part
            if device_scan and arm.scan_path.startswith("device"):
                # the device scan resolved end-to-end: close a half-open
                # breaker probe (the _scan_parts contract — without this
                # a streamed probe query would leave the breaker latched
                # half-open and short-circuit every later dispatch)
                ok = getattr(self.executor, "record_device_success", None)
                if ok is not None:
                    ok()

    def _query_many_planned(self, name, ft, qs: List[Query]) -> List[QueryResult]:
        import time as _time

        from geomesa_tpu.utils.audit import QueryTimeout

        plan_s: List[float] = []
        plans = []
        dispatch = getattr(self.executor, "dispatch_candidates", None)
        dispatch_many = getattr(self.executor, "dispatch_many", None)
        pending: Dict[int, object] = {}
        # planning + pipelined dispatch: the batch's SHARED phase runs
        # under one budget (see query_many) — a stalled link fails the
        # phase crisply and every query degrades to the host scan
        with deadline_mod.budget(self.query_timeout_s):
            for q in qs:
                t0 = _time.perf_counter()
                plans.append(self._plan_cached(name, q))
                plan_s.append(_time.perf_counter() - t0)
            if dispatch is not None:
                try:
                    items = []
                    for q, plan in zip(qs, plans):
                        if "density" in q.hints:
                            continue  # fused density path dispatches its own compute
                        arms = plan.union if plan.union is not None else [plan]
                        for arm in arms:
                            if arm.is_empty or id(arm) in pending:
                                continue
                            table = self._tables[name][arm.index.name]
                            if dispatch_many is not None:
                                pending[id(arm)] = None  # placeholder, filled below
                                items.append((table, arm))
                            else:
                                pending[id(arm)] = dispatch(table, arm)
                    if dispatch_many is not None and items:
                        # exact-shape plans on the same table fuse into one batched
                        # device execution; the rest dispatch as before
                        pending.update(dispatch_many(items))
                except QueryTimeout:
                    # the shared phase's budget died mid-dispatch: the
                    # un-dispatched plans keep their None placeholders
                    # and every query resolves from the host scan under
                    # its OWN budget below — the batch itself survives
                    pending = {k: None for k in pending}
                except Exception as e:  # noqa: BLE001 - device failure
                    # batched dispatch died mid-stream: un-dispatched plans
                    # keep their None placeholders, which _scan_parts already
                    # resolves to the host scan — the whole batch degrades
                    # rather than the batch query dying
                    degrade = getattr(self.executor, "degrade", None)
                    if degrade is not None:
                        degrade(None, e)
        results = []
        for q, plan, dt in zip(qs, plans, plan_s):
            # per-query clock AND budget: the timeout and audited scan
            # time cover THIS query's resolve, not the whole batch's
            t_resolve = _time.perf_counter()
            root = trace.NOOP
            ptok = plans_mod.begin()
            try:
                with trace.span(
                    "query", force=self.slow_query_s is not None,
                    type=name, batched=True,
                ) as root:
                    with deadline_mod.budget(self.query_timeout_s):
                        dev0 = devstats.receipt_snapshot()
                        result = self._execute(name, ft, q, plan, t_resolve, pending)
                        receipt = devstats.receipt_since(dev0)
                        if root.recording:
                            root.set_attr("hits", len(result))
                            root.set_attr("scan_path", self._collect_scan_path(plan))
                            root.set_attr("device", receipt)
                        if self._auditing():
                            self._audit(name, q, plan, result, t_resolve - dt,
                                        t_resolve, receipt)
            finally:
                plans_mod.end(ptok)
                self._log_slow_query(name, plan, root)
            results.append(result)
        return results

    def _auditing(self) -> bool:
        """Whether the per-query audit step must run at all: an audit
        writer, a metrics registry, the plan-fingerprint registry
        (utils/plans.py), the tenant meter (utils/tenants.py), or the
        workload recorder (utils/workload.py) is listening.
        _audit/_audit_failure re-check each sink individually — this is
        just the hot-path gate."""
        return (
            self.audit_writer is not None
            or self.metrics is not None
            or plans_mod.enabled()
            or tenants_mod.enabled()
            or workload_mod.enabled()
        )

    @staticmethod
    def _collect_scan_path(plan) -> str:
        """This plan's audited execution path; union plans join their
        arms' labels (set by _scan_parts as each arm executes)."""
        if plan.union is not None:
            arms = [getattr(a, "scan_path", "") for a in plan.union]
            return "+".join(sorted({a for a in arms if a}))
        return getattr(plan, "scan_path", "")

    def _audit(self, name, query, plan, result, t_start, t_planned,
               receipt=None, hits=None, wl_cls="query"):
        import time as _time

        from geomesa_tpu.filter.parser import to_cql
        from geomesa_tpu.utils.audit import QueryEvent

        now = _time.perf_counter()
        receipt = receipt or {}
        if hits is None:
            hits = len(result)
        if self.metrics is not None:
            self.metrics.inc("queries")
            self.metrics.update_timer("query.plan", t_planned - t_start)
            self.metrics.update_timer("query.scan", now - t_planned)
        if self.audit_writer is not None:
            self.audit_writer.write_event(
                QueryEvent(
                    store=type(self).__name__,
                    type_name=name,
                    user=self.user,
                    filter=to_cql(query.filter),
                    hints=dict(query.hints),
                    date_ms=int(_time.time() * 1000),
                    planning_ms=1000 * (t_planned - t_start),
                    scanning_ms=1000 * (now - t_planned),
                    hits=hits,
                    scan_path=self._collect_scan_path(plan),
                    # called inside the query's root span: the audit row
                    # and the exported trace tree join on this id
                    trace_id=trace.current_trace_id() or "",
                    recompiles=int(receipt.get("recompiles", 0)),
                    h2d_bytes=int(receipt.get("h2d_bytes", 0)),
                    d2h_bytes=int(receipt.get("d2h_bytes", 0)),
                    pad_ratio=float(receipt.get("pad_ratio", 0.0)),
                )
            )
        fid = ""
        if plans_mod.enabled():
            # fold the finished query into its plan fingerprint
            # (utils/plans.py): plan-time estimates (QueryPlan.cost,
            # range count) meet the consume-time actuals and the
            # pending decision tallies here
            fid = self._plans_obj().observe(
                "query", name, plan=plan, query=query,
                scan_path=self._collect_scan_path(plan),
                outcome="ok", hits=hits, duration_s=now - t_start,
                receipt=receipt,
                est_cost=plan.cost,
                est_ranges=len(plan.ranges),
            )
        self._observe_workload(
            wl_cls, name, query=query, outcome="ok",
            duration_s=now - t_start, rows=hits, receipt=receipt,
            fingerprint=fid,
        )

    def _plans_obj(self):
        """The per-store plan-fingerprint registry (utils/plans.py),
        created lazily. GIL-atomic setdefault — the _agg_cache_obj rule:
        two concurrent first queries must agree on ONE registry.
        ShardWorker pre-assigns a shared registry to its partition
        sub-stores so a shard rolls up as one read."""
        reg = getattr(self, "_plans", None)
        if reg is None:
            from geomesa_tpu.utils.plans import PlanRegistry

            reg = self.__dict__.setdefault("_plans", PlanRegistry())
        return reg

    def _tenants_obj(self):
        """The per-store tenant-cost registry (utils/tenants.py),
        created lazily — the _plans_obj arrangement exactly: GIL-atomic
        setdefault so two concurrent first queries agree on ONE
        registry, and ShardWorker / fleet workers pre-assign a shared
        registry to their partition sub-stores so a worker rolls up as
        one read."""
        reg = getattr(self, "_tenants", None)
        if reg is None:
            from geomesa_tpu.utils.tenants import TenantRegistry

            reg = self.__dict__.setdefault("_tenants", TenantRegistry())
        return reg

    def _observe_workload(self, cls, type_name, *, query=None, cql=None,
                          outcome="ok", duration_s=0.0, rows=0,
                          receipt=None, fingerprint="", tenant=None,
                          extra=None):
        """The workload-intelligence seam: per-tenant metering
        (utils/tenants.py) + workload capture (utils/workload.py) for
        one finished request. Both are pure observers — off costs one
        cached flag read each, and the capture swallows its own
        failures — so this sits AFTER the result is final and can never
        change an answer. Runs inside the admission slot, so the
        recorded in-flight depth includes the request itself."""
        t_on = tenants_mod.enabled()
        w_on = workload_mod.enabled()
        if not (t_on or w_on):
            return
        if tenant is None:
            tenant = tenants_mod.tenant_of(query)
        if t_on:
            self._tenants_obj().observe(
                tenant, cls, outcome=outcome, duration_s=duration_s,
                rows=rows, receipt=receipt,
            )
        if w_on:
            adm = getattr(self, "admission", None)
            inflight = adm.peek()["inflight"] if adm is not None else 0
            workload_mod.record(
                self, cls, type_name, query=query, cql=cql,
                tenant=tenant, inflight=inflight, outcome=outcome,
                fingerprint=fingerprint, receipt=receipt,
                duration_s=duration_s, rows=rows, extra=extra,
            )

    def _audit_failure(self, name, query, plan, t_admit, outcome: str,
                       count_metrics: bool = True):
        """Audit trail for a query that FAILED crisply (timeout / shed):
        hits stay 0 — a failed query never has partial hits — and the
        elapsed wall (admission wait included) lands in scanning_ms so
        latency dashboards see the cost overload actually charged.
        ``count_metrics=False`` writes the event only — query_join keeps
        its failures in join-scoped counters (and its own join-class
        fingerprint) so an inner query that already audited its own
        timeout is not double-counted."""
        import time as _time

        from geomesa_tpu.filter.parser import to_cql
        from geomesa_tpu.utils.audit import QueryEvent

        elapsed_ms = 1000 * (_time.perf_counter() - t_admit)
        if count_metrics and self.metrics is not None:
            self.metrics.inc("queries")
            self.metrics.inc(f"queries.{outcome}")
        if self.audit_writer is not None:
            self.audit_writer.write_event(
                QueryEvent(
                    store=type(self).__name__,
                    type_name=name,
                    user=self.user,
                    filter=to_cql(query.filter),
                    hints=dict(query.hints),
                    date_ms=int(_time.time() * 1000),
                    planning_ms=0.0,
                    scanning_ms=elapsed_ms,
                    hits=0,
                    scan_path=self._collect_scan_path(plan) if plan is not None else "",
                    trace_id=trace.current_trace_id() or "",
                    outcome=outcome,
                )
            )
        fid = ""
        if count_metrics and plans_mod.enabled():
            # failed queries fingerprint too: a shape that times out is
            # exactly the shape the misestimate/decision record explains
            # (count_metrics=False = a join-level failure event that
            # already wrote its own join-class fingerprint)
            fid = self._plans_obj().observe(
                "query", name, plan=plan, query=query,
                scan_path=(
                    self._collect_scan_path(plan) if plan is not None else ""
                ),
                outcome=outcome, hits=0, duration_s=elapsed_ms / 1000.0,
                est_cost=plan.cost if plan is not None else None,
                est_ranges=len(plan.ranges) if plan is not None else None,
            )
        if count_metrics:
            # failed queries meter and capture too (conservation: the
            # per-tenant outcome sums must equal queries.<outcome>);
            # count_metrics=False = a join-level event whose join path
            # recorded its own tenant/workload observation already
            self._observe_workload(
                "query", name, query=query, outcome=outcome,
                duration_s=elapsed_ms / 1000.0, fingerprint=fid,
            )

    def _log_slow_query(self, name: str, plan, root) -> None:
        """Threshold slow-query log: any query over ``slow_query_s``
        dumps its full span tree + the plan explain (the per-query
        "why was this one slow" answer the aggregate timers can't give).
        ``root`` is real whenever a budget is set (query() forces it).

        Storm-guarded (utils/audit.slow_query_note): every slow query
        files a cheap summary into the bounded tail behind
        ``/debug/report``, but the EXPENSIVE part — rendering the span
        tree and explain — is rate-limited to
        ``geomesa.query.slow.max.per.min`` so an overload event cannot
        turn the observability layer into the bottleneck it measures."""
        import logging as _logging

        from geomesa_tpu.utils.audit import slow_query_note

        if self.slow_query_s is None or not root.recording:
            return
        if root.duration_ms < self.slow_query_s * 1000.0:
            return
        if not slow_query_note({
            "kind": "query",
            "type": name,
            "trace_id": root.trace_id,
            "duration_ms": round(root.duration_ms, 1),
            "budget_ms": round(self.slow_query_s * 1000.0, 1),
        }):
            return  # render shed; the summary survives in the tail
        _logging.getLogger("geomesa_tpu.slowquery").warning(
            "slow query type=%s trace=%s took %.1fms (budget %.0fms)\n%s\n"
            "explain:\n%s",
            name, root.trace_id, root.duration_ms,
            self.slow_query_s * 1000.0, root.render(),
            plan.explain if plan is not None else "<planning failed>",
        )

    def _execute(
        self, name, ft, query: Query, plan: QueryPlan, t_scan_start, pending=None
    ) -> QueryResult:
        """EXECUTE = route -> scan -> merge (PLAN ran in _plan_cached).

        The single-process pipeline: ``_route`` decomposes the plan into
        independently scannable units (cross-index union arms here; the
        sharded coordinator in parallel/shards.py overrides execution
        into per-shard partition scans instead), ``_scan_parts`` scans
        each unit, ``_merge`` assembles/dedupes/finishes. The device
        aggregation push-downs below are single-unit short-circuits that
        skip the scan entirely."""
        if plan.is_empty:
            empty = _empty_columns(ft)
            if has_aggregation(query.hints):
                return QueryResult(ft, empty, plan, run_aggregation(ft, query.hints, empty))
            return QueryResult(ft, empty, plan)

        untransformed = self._untransformed(query)

        # aggregate-cache shortcuts (ops/pyramid.py): a memoized density
        # grid answers with zero dispatch; a Count()-only stats spec over
        # a spatial-only plan answers from the pyramid's interior partial
        # sums plus the exact boundary ring. Either way the caller's
        # ordinary _audit still runs on the returned result — the
        # QueryEvent outcome row and the (zero-dispatch) cost receipt are
        # written for cache-answered push-downs too, with agg.cache=hit
        # stamped on the query root span.
        got = self._agg_shortcut(name, ft, query, plan, untransformed)
        if got is not None:
            return got

        if plan.union is not None:
            # cross-index OR: scan each arm on its own index, union by fid
            # (FilterSplitter.scala:64-110; dedup replaces makeDisjoint :303)
            parts: List[tuple] = []
            for arm in self._route(query, plan):
                parts.extend(
                    self._scan_parts(name, ft, query, arm, t_scan_start, pending)
                )
            result = self._merge(ft, query, plan, parts, unique=False)
            self._agg_density_fill(name, query, untransformed, result)
            return result

        tables = self._tables[name]
        table = tables[plan.index.name]

        # fused device density push-down: grid comes back, features don't
        # (the KryoLazyDensityIterator analog)
        if (
            set(query.hints) & set(AGGREGATION_HINTS) == {"density"}
            and not query.hints.get("sampling")
            and untransformed
            and not mesh_mod.device_tripped(
                self.executor, "GEOMESA_DENSITY_DEVICE"
            )
        ):
            try:
                grid = self.executor.density_scan(
                    table, plan, query.hints["density"]
                )
            except Exception as e:  # noqa: BLE001 - device failure
                from geomesa_tpu.utils.audit import QueryTimeout

                if isinstance(e, QueryTimeout):
                    raise  # the query's budget died, not the device
                # the host reducer (run_density over scanned columns)
                # answers identically — a lost device mid-execution must
                # not kill an aggregation query; see mesh.trip_device
                # for the session trip semantics
                mesh_mod.trip_device(
                    self.executor, "GEOMESA_DENSITY_DEVICE", "density", e
                )
                audit_mod.decision(
                    "degrade", "density_to_host", error=type(e).__name__
                )
                grid = None
            if grid is not None:
                plan.scan_path = "device-density"
                result = QueryResult(
                    ft, _empty_columns(ft), plan, {"density": grid}
                )
                self._agg_density_fill(name, query, untransformed, result)
                return result

        # device stats push-down: per-code count histograms come back,
        # features don't (the KryoLazyStatsIterator analog) — the host
        # reconstructs exact sketches via the observe_counts contract
        if (
            set(query.hints) & set(AGGREGATION_HINTS) == {"stats"}
            and not query.hints.get("sampling")
            and untransformed
            and not mesh_mod.device_tripped(
                self.executor, "GEOMESA_STATS_DEVICE"
            )
        ):
            try:
                stat = self.executor.stats_scan(
                    table, plan, query.hints["stats"]
                )
            except Exception as e:  # noqa: BLE001 - device failure
                from geomesa_tpu.utils.audit import QueryTimeout

                if isinstance(e, QueryTimeout):
                    raise  # the query's budget died, not the device
                mesh_mod.trip_device(
                    self.executor, "GEOMESA_STATS_DEVICE", "stats", e
                )
                audit_mod.decision(
                    "degrade", "stats_to_host", error=type(e).__name__
                )
                stat = None
            if stat is not None:
                plan.scan_path = "device-stats"
                return QueryResult(ft, _empty_columns(ft), plan, {"stats": stat})

        parts = self._scan_parts(name, ft, query, plan, t_scan_start, pending)
        # NO xz dedupe: unlike the reference's sharded XZ tables
        # (QueryPlanner.scala:83-85 dedupes multi-row extent features),
        # this layout writes exactly ONE row per feature per index, and
        # expand_intervals dedupes overlapping range hits within a block —
        # so extent results stay lazy like point results
        result = self._merge(ft, query, plan, parts, unique=True)
        self._agg_density_fill(name, query, untransformed, result)
        return result

    def _route(self, query: Query, plan: QueryPlan) -> List[QueryPlan]:
        """ROUTE stage: decompose a plan into independently scannable
        units. Single-process, that is the cross-index union arms — a
        non-union plan routes trivially to itself, so the hot path skips
        the call; the sharded coordinator's analog maps the query's
        partition covering onto shard placements (parallel/shards.py)."""
        if plan.is_empty:
            return []
        if plan.union is not None:
            return [arm for arm in plan.union if not arm.is_empty]
        return [plan]

    def _merge(
        self, ft, query: Query, plan: QueryPlan, parts: List[tuple],
        unique: bool,
    ) -> QueryResult:
        """MERGE stage: scanned parts -> result columns -> finish.
        Result assembly (column projection, dedupe, sort/limit,
        transforms) spans as its own stage so per-query self-times sum
        to the audited wall — scan time vs materialization time is
        exactly the split perf work needs. ``unique=False`` (union arms
        may overlap) dedupes by fid."""
        with trace.span("query.assemble"):
            columns = self._columns_from_parts(ft, query, parts)
            if not unique:
                columns = _dedupe_by_fid(_materialize(columns))
            return self._finish(ft, query, plan, columns)

    def _columns_from_parts(self, ft, query: Query, parts: List[tuple]):
        """Light (block, rows) parts -> LazyColumns exposing the query's
        observable key set (projection pushdown of the transform-schema
        pruning, QueryPlanner.scala:192-284, now fully deferred)."""
        if not parts:
            return _empty_columns(ft)
        out_needed = self._output_columns(ft, query)
        # observable keys come from the RECORD columns (full features);
        # index-own derived companions (e.g. xz envelopes) are scan
        # internals and never leak into results. A key must exist in EVERY
        # part's record (union arms share record layout per batch) —
        # except __null companions, whose absence means "no nulls in this
        # block" and materializes as zeros
        keysets = [
            set(b.record.columns) if getattr(b, "record", None) is not None
            else set(b.columns)
            for b, _ in parts
        ]
        common = set.intersection(*keysets)
        keys = {"__fid__"}
        keys.update(
            k
            for k in set.union(*keysets)
            if k != "__vis__"
            and not k.endswith(_INTERNAL_SUFFIXES)  # scan internals never leak
            and (k in common or k.endswith("__null"))
            and (out_needed is None or _column_base(k) in out_needed)
        )
        return LazyColumns(parts, keys)

    def _finish(self, ft, query: Query, plan: QueryPlan, columns: Columns) -> QueryResult:
        from geomesa_tpu.index.transforms import QueryTransforms

        if has_aggregation(query.hints):
            # sampling composes with aggregations (SamplingIterator stacks
            # under density/bin/arrow scans in the reference); transforms
            # apply BEFORE aggregation so arrow/bin streams carry the
            # derived schema (ArrowScan transform handling)
            columns = _apply_sampling(query, _materialize(columns))
            tf = QueryTransforms.parse(ft, query.properties)
            if tf is not None:
                ft, columns = tf.apply(columns)
            # the host reducer apart from the column gather above, which
            # stays in query.assemble's self time
            kind = next((h for h in AGGREGATION_HINTS if h in query.hints), "")
            rows = len(columns.get("__fid__", ()))
            with trace.span("agg.reduce", rows=rows, kind=kind):
                agg = run_aggregation(ft, query.hints, columns)
            return QueryResult(ft, _empty_columns(ft), plan, agg)
        if (
            isinstance(columns, LazyColumns)
            and not query.sort_by
            and query.max_features is None
            and not query.hints.get("sampling")
            and QueryTransforms.parse(ft, query.properties) is None
        ):
            # plain stream: nothing re-orders or derives columns, so the
            # lazy mapping (already key-restricted) passes straight through
            if query.properties is not None:
                ft = _narrow_ft(ft, query.properties)
            return QueryResult(ft, columns, plan)
        ft, columns = apply_projection(ft, query, _materialize(columns))
        return QueryResult(ft, columns, plan)

    def _scan_parts(
        self, name, ft, query: Query, plan: QueryPlan, t_scan_start, pending=None,
    ) -> List[tuple]:
        """Scan one plan into light (block, final_rows) parts.

        No output column ever leaves the blocks here: filtering gathers
        only the columns the post-filter/age-off read, and the result's
        attribute gathers are deferred to LazyColumns (the
        KryoBufferSimpleFeature lazy-read analog)."""
        tables = self._tables[name]
        table = tables[plan.index.name]
        with trace.span("scan", index=plan.index.name) as sp:
            if pending is not None and id(plan) in pending:
                scan = pending[id(plan)]  # pre-dispatched (query_many pipeline)
            else:
                scan = self.executor.scan_candidates(table, plan)
            device_scan = scan is not None
            # audited execution-path label (the reference audits plan/scan
            # timings; WHICH path answered is the extra operators need when
            # cost gates flip between host and device)
            plan.scan_path = _scan_label(scan)
            sp.set_attr("scan_path", plan.scan_path)
            try:
                parts = self._consume_scan(
                    ft, query, plan, table, scan, device_scan, t_scan_start
                )
                if device_scan and plan.scan_path.startswith("device"):
                    # a device scan resolved end-to-end: tell the
                    # executor's circuit breaker (a successful half-open
                    # probe closes the circuit here)
                    ok = getattr(self.executor, "record_device_success", None)
                    if ok is not None:
                        ok()
                return parts
            except Exception as e:
                from geomesa_tpu.utils.audit import QueryTimeout, robustness_metrics

                if not device_scan or isinstance(e, QueryTimeout):
                    raise
                # an executor scan died mid-resolution (device fetch / native
                # seek failure): degrade THIS query to the host table scan —
                # identical results, since the host path evaluates the full
                # filter — and let the executor rebuild its mirror. The
                # timeout clock keeps running across the rerun.
                degrade = getattr(self.executor, "degrade", None)
                if degrade is not None:
                    degrade(table, e)  # emits the degrade span event + counters
                else:
                    robustness_metrics().inc("degrade.device_to_host")
                    trace.event(
                        "degrade.device_to_host",
                        reason=f"{type(e).__name__}: {e}",
                    )
                    audit_mod.decision(
                        "degrade", "device_to_host", error=type(e).__name__
                    )
                plan.scan_path = "host-table-degraded"
                sp.set_attr("scan_path", plan.scan_path)
                return self._consume_scan(
                    ft, query, plan, table, None, False, t_scan_start
                )

    def _consume_scan(
        self, ft, query: Query, plan: QueryPlan, table, scan, device_scan,
        t_scan_start,
    ) -> List[tuple]:
        """Resolve one (possibly device-pending) scan into parts; the
        filtering tail of _scan_parts, split out so a device failure can
        re-enter with the host scan."""
        return list(
            self._iter_consume(
                ft, query, plan, table, scan, device_scan, t_scan_start
            )
        )

    def _iter_consume(
        self, ft, query: Query, plan: QueryPlan, table, scan, device_scan,
        t_scan_start,
    ) -> Iterator[tuple]:
        """Generator body of _consume_scan: yields each (block,
        final_rows) part as its block resolves — query_stream consumes
        this lazily so the first Arrow batch flushes while later blocks
        are still scanning; _consume_scan materializes the list."""
        import time as _time

        dl = deadline_mod.ambient()
        if scan is None:
            if plan.ranges:
                scan = table.scan(plan.ranges)
            else:
                scan = table.scan_all()
        # dtg age-off (DtgAgeOffIterator.scala:29-60 analog): a per-type
        # retention window ('geomesa.feature.expiry' in the SFT user data or
        # the system property, e.g. '7 days') masks expired rows at scan
        age_cutoff = self._age_off_cutoff(ft)
        # loose-bbox: for a residual-free rectangle-only point-index plan the
        # device candidate set (int-domain test, same granularity as the
        # reference's Z3Filter) IS the loose result (Z2Index.scala:26-40).
        # Non-rectangle predicates keep full ECQL even in the reference.
        gv = plan.values.geometries
        loose = (
            query.hints.get("loose_bbox")
            and plan.index.name in ("z2", "z3")
            and plan.secondary is None
            and device_scan  # device int-domain candidates only
            and not getattr(scan, "seek", False)  # range-granular rows
            and gv.values
            and gv.precise
            and all(g.is_rectangle() for g in gv.values)
        )
        if getattr(scan, "exact", False):
            # the device/native path evaluated the query's own f64/ms
            # predicate: candidates ARE the result set
            loose = True
        pf_props = (
            set(ast.properties(plan.post_filter))
            if plan.post_filter is not None and not loose
            else None
        )
        for item in scan:
            if len(item) == 3:
                block, rows, covered = item
                if covered is not None and not covered.any():
                    covered = None  # nothing to split: take the generic path
            else:
                block, rows = item
                covered = None
            # cooperative per-block check against the query's ambient
            # deadline (installed by query()/query_many from
            # query_timeout_s); direct _execute callers without a budget
            # fall back to the legacy between-blocks clock
            if dl is not None:
                dl.check("scan.block")
            elif self.query_timeout_s is not None and (
                _time.perf_counter() - t_scan_start > self.query_timeout_s
            ):
                from geomesa_tpu.utils.audit import QueryTimeout

                raise QueryTimeout(
                    f"query exceeded {self.query_timeout_s}s (geomesa.query.timeout analog)"
                )
            rows_in = len(rows)
            with trace.span("scan.block", rows_in=rows_in) as bsp:
                if covered is not None and pf_props is not None:
                    rows = self._filter_block_covered(
                        ft, plan, block, rows, covered, age_cutoff, pf_props
                    )
                else:
                    alive = self._age_off_keep(ft, block, rows, age_cutoff)
                    if alive is not None:
                        rows = rows[alive]
                    if pf_props is not None and len(rows):
                        fcols = self._gather_filter_cols(block, rows, pf_props)
                        with trace.span("scan.post_filter", rows=len(rows)):
                            mask = self.executor.post_filter(ft, plan, fcols)
                        if not mask.all():
                            rows = rows[mask]
                    vmask = self._visibility_keep(block, rows)
                    if vmask is not None:
                        rows = rows[vmask]
                bsp.set_attr("rows_out", len(rows))
            # per-block actuals for the plan fingerprint's estimate-vs-
            # actual record (one contextvar read when plans are off)
            plans_mod.note_scan(rows_in, len(rows))
            # the yield sits OUTSIDE the span: a streaming consumer may
            # suspend here indefinitely, and a span (contextvar) must
            # never stay open across a generator suspension
            if len(rows):
                yield block, rows

    def _age_off_keep(self, ft, block, rows, age_cutoff):
        """Bool keep-mask for the dtg age-off window, or None if all live
        (DtgAgeOffIterator analog; null dates never age off)."""
        if age_cutoff is None or not len(rows):
            return None
        dtg = ft.default_date.name
        alive = block.gather(dtg, rows) >= age_cutoff
        alive |= block.gather(dtg + "__null", rows)
        return None if alive.all() else alive

    @staticmethod
    def _gather_filter_cols(block, rows, props) -> Columns:
        """Gather exactly the columns a filter reads (incl. "__fid__" when
        an IdFilter is present — ast.properties reports it); property-free
        filters (e.g. EXCLUDE) get a length-carrier column so evaluate()
        can infer the row count. The record-row join mapping is computed
        at most once even when several record-backed columns are read."""
        wanted = [
            k
            for k in block.all_keys()
            if k != "__vis__"
            and (k != "__fid__" or "__fid__" in props)
            and _column_base(k) in props
        ]
        rr = None
        if any(
            k not in block.columns for k in wanted
        ) and getattr(block, "record", None) is not None:
            rr = block.rowid[rows]
        fcols = {}
        for k in wanted:
            if k.endswith("__vocab"):
                # dictionary vocab: whole sorted array, NOT row-aligned —
                # the evaluator maps literals through it in code space
                fcols[k] = block.full_col(k) if k in block.columns else (
                    block.record.columns[k]
                )
            else:
                fcols[k] = block.gather(k, rows, record_rows=rr)
        if not fcols:
            fcols["__rows__"] = rows
        return fcols

    def _visibility_keep(self, block, rows):
        """Bool keep-mask vs this store's authorizations, or None when all
        visible (VisibilityEvaluator.scala:21 / SecurityUtils analog)."""
        if not len(rows) or not block.has_col("__vis__"):
            return None
        from geomesa_tpu.security import visibility_mask

        vmask = visibility_mask(block.gather("__vis__", rows), self.authorizations)
        return None if vmask.all() else vmask

    def _filter_block_covered(
        self, ft, plan: QueryPlan, block, rows, covered, age_cutoff, pf_props
    ) -> np.ndarray:
        """Covered-split filtering of one block -> surviving rows.

        Rows marked ``covered`` came from ``contained`` ranges and provably
        satisfy the plan's exact primary predicate (strict-interior z skip
        boxes / precise attr-value ranges), so the full post-filter runs
        only on the uncovered remainder; covered rows check just the
        residual secondary predicate. The reference makes the analogous
        move by dropping the primary filter when ranges are covering and
        residual-free; here it is per-range, not per-plan."""
        from geomesa_tpu.filter import ast as _ast
        from geomesa_tpu.filter.evaluate import evaluate

        alive = self._age_off_keep(ft, block, rows, age_cutoff)
        if alive is not None:
            rows = rows[alive]
            covered = covered[alive]
        keep = covered.copy()
        uncov_idx = np.flatnonzero(~covered)
        if len(uncov_idx):
            rows_u = rows[uncov_idx]
            fcols = self._gather_filter_cols(block, rows_u, pf_props)
            with trace.span("scan.post_filter", rows=len(rows_u)):
                keep[uncov_idx] = self.executor.post_filter(ft, plan, fcols)
        if plan.secondary is not None:
            cov_idx = np.flatnonzero(covered)
            if len(cov_idx):
                rows_c = rows[cov_idx]
                sec_props = set(_ast.properties(plan.secondary))
                scols = self._gather_filter_cols(block, rows_c, sec_props)
                keep[cov_idx] = evaluate(plan.secondary, ft, scols)
        if not keep.all():
            rows = rows[keep]
        vmask = self._visibility_keep(block, rows)
        if vmask is not None:
            rows = rows[vmask]
        return rows

    def _output_columns(self, ft: FeatureType, query: Query) -> Optional[set]:
        """Base-names the query RESULT must carry; None = everything.
        A superset of the projection: sort and sampling read from the
        gathered columns after filtering (post-filter/age-off inputs are
        gathered separately by _gather_filter_cols and never reach the
        result)."""
        props = query.properties
        if props is None or has_aggregation(query.hints):
            return None
        if any("=" in p for p in props):
            return None  # derived transforms read arbitrary source columns
        out = set(props)
        if query.sort_by:
            out.update(a for a, _ in query.sort_by)
        sample_by = query.hints.get("sample_by")
        if sample_by:
            out.add(sample_by)
        return out

    def _age_off_cutoff(self, ft: FeatureType) -> Optional[int]:
        """Epoch-ms cutoff below which features are expired, or None.

        Retention comes from the SFT user data key 'geomesa.feature.expiry'
        (per-type, like the reference's table iterator config) or the
        system property of the same name (store-wide default)."""
        if ft.default_date is None:
            return None
        from geomesa_tpu.utils.config import FEATURE_EXPIRY, SystemProperty

        spec = (ft.user_data or {}).get("geomesa.feature.expiry")
        ms = None
        if spec is not None:
            ms = SystemProperty("", str(spec)).to_duration_ms()
        if ms is None:
            ms = FEATURE_EXPIRY.to_duration_ms()
        if ms is None:
            return None
        import time as _time

        return int(_time.time() * 1000) - ms

    def age_off(self, name: str) -> int:
        """Tombstone expired features (maintenance sweep; the age-off
        iterator drops them physically at compaction in the reference).
        Returns the number removed."""
        ft = self.get_schema(name)
        cutoff = self._age_off_cutoff(ft)
        if cutoff is None:
            return 0
        dtg = ft.default_date.name
        victims: List[str] = []
        table = next(iter(self._tables[name].values()))
        for b, rows in table.scan_all():
            t = b.gather(dtg, rows)
            dead = (t < cutoff) & ~b.gather(dtg + "__null", rows)
            victims.extend(b.gather("__fid__", rows[dead]))
        if victims:
            self.delete_features(name, victims)
        return len(victims)

    def _as_query(self, query: Union[str, Query]) -> Query:
        if isinstance(query, Query):
            return query
        return Query.cql(query)

    def _plan_cached(self, name: str, query: Query) -> QueryPlan:
        """Plan cache keyed on (type, filter text, table state) — the
        IteratorCache analog (iterators/IteratorCache.scala:1-97)."""
        from geomesa_tpu.filter.parser import to_cql

        with trace.span("query.plan") as sp:
            versions = tuple(t.version for t in self._tables[name].values())
            key = (name, to_cql(query.filter), versions)
            # LRU: hits move to the back, the oldest entry is evicted when full
            plan = self._plan_cache.pop(key, None)
            if plan is None:
                sp.set_attr("cache", "miss")
                plan = self.planner(name).plan(query)
                if len(self._plan_cache) >= 256:
                    self._plan_cache.pop(next(iter(self._plan_cache)))
            elif sp.recording:
                # cache hit: no planner child span, so the hit carries the
                # cached plan's provenance itself
                sp.set_attr("cache", "hit")
                sp.set_attr("index", plan.index.name)
                sp.set_attr("explain", plan.explain)
            self._plan_cache[key] = plan
        return plan


class ScanExecutor:
    """Pluggable scan execution (host numpy vs TPU kernels).

    ``scan_candidates`` may return an iterator of (block, rows) candidate
    sets computed on device (the tserver-iterator analog) or None to fall
    back to host range scanning; ``post_filter`` enforces exact semantics.
    """

    def scan_candidates(self, table, plan: QueryPlan):
        return None

    def density_scan(self, table, plan: QueryPlan, spec) -> Optional[np.ndarray]:
        """Fused filter+density on device; None -> host reducer fallback."""
        return None

    def stats_scan(self, table, plan: QueryPlan, spec: str):
        """Device stats sketches from per-code counts; None -> host
        extraction + run_stats fallback."""
        return None

    def post_filter(self, ft: FeatureType, plan: QueryPlan, columns: Columns) -> np.ndarray:
        raise NotImplementedError


class HostScanExecutor(ScanExecutor):
    def post_filter(self, ft: FeatureType, plan: QueryPlan, columns: Columns) -> np.ndarray:
        return evaluate(plan.post_filter, ft, columns)


# derived scan-internal companion suffixes (dictionary vocabs, envelope
# prescreen columns, rect flags): never exposed in query results, whether
# they were computed at ingest or supplied precomputed by a columnar writer
_INTERNAL_SUFFIXES = (
    "__vocab", "__bxmin", "__bymin", "__bxmax", "__bymax", "__isrect"
)


# span attributes worth carrying into an EXPLAIN ANALYZE stage row —
# plan/scan provenance and row counts, not free-form payloads
_STAGE_ATTRS = (
    "index", "scan_path", "type", "cost", "n_ranges", "union_arms",
    "rows_in", "rows_out", "rows", "hits", "coalesced", "n", "shards",
)


def _stage_tree(sp) -> Dict[str, Any]:
    """One span subtree as an EXPLAIN ANALYZE stage row: wall/self
    times, the provenance attributes, decision/degrade events, nested
    stages — the per-execution edition of the plan Explainer."""
    out: Dict[str, Any] = {
        "stage": sp.name,
        "duration_ms": round(sp.duration_ms, 3),
        "self_ms": round(sp.self_time_ms, 3),
    }
    attrs = {k: sp.attributes[k] for k in _STAGE_ATTRS if k in sp.attributes}
    if attrs:
        out["attrs"] = attrs
    events = [
        {k: v for k, v in ev.items() if k != "t_ms"}
        for ev in sp.events
        if ev["name"].startswith(("decision.", "degrade.", "fault."))
    ]
    if events:
        out["events"] = events
    if sp.children:
        out["stages"] = [_stage_tree(c) for c in sp.children]
    return out


def _scan_label(scan) -> str:
    """Human-readable execution-path label for audit events (None = the
    executor declined and the host table scan ran). Batched device scans
    carry a ``/bitmap`` or ``/runs`` suffix for the wire format."""
    if scan is None:
        return "host-table"
    name = type(scan).__name__
    labels = {
        "_HostSeekScan": "host-seek",
        "_DeviceSeekScan": "device-seek",
        "_DeviceSeekXZScan": "device-seek-xz",
    }
    if name in labels:
        return labels[name]
    if name in ("_PendingScan", "_XZBatchScan"):
        base = (
            "device-batch-dual" if name == "_XZBatchScan"
            else "device-exact" if getattr(scan, "exact", False)
            else "device-mask"
        )
        pending = getattr(scan, "pending", None)
        if pending:
            # wire format suffix: coalesced full-table masks, span-framed
            # bitmaps, else the single-query scans' RLE runs
            pname = type(pending[0][1]).__name__
            fmt = (
                "mask" if "Mask" in pname
                else "bitmap" if "Bitmap" in pname
                else "runs"
            )
            return f"{base}/{fmt}"
        return base
    return name.strip("_").lower()


def _column_base(k: str) -> str:
    """geom__x / dtg__null -> attribute base name (dunder-internal keys
    like __fid__ pass through unchanged)."""
    if k.startswith("__"):
        return k
    return k.split("__", 1)[0]


def _empty_columns(ft: FeatureType) -> Columns:
    cols: Columns = {"__fid__": np.empty(0, dtype=object)}
    for a in ft.attributes:
        if a.type == AttributeType.POINT:
            cols[a.name + "__x"] = np.empty(0)
            cols[a.name + "__y"] = np.empty(0)
        elif a.type.is_geometry:
            cols[a.name] = np.empty(0, dtype=object)
        else:
            dtype = a.type.numpy_dtype
            cols[a.name] = np.empty(0, dtype=dtype if dtype is not None else object)
    return cols


def _count_only_stats(spec):
    """Parsed stat when ``spec`` is composed solely of Count() stats (the
    pyramid can answer those exactly from partial sums), else None.
    Sketches with per-value state (MinMax's HLL registers, histograms)
    cannot be reconstructed from per-cell scalar aggregates and keep the
    ordinary device/host stats paths."""
    from geomesa_tpu.stats.parser import parse_stat
    from geomesa_tpu.stats.sketches import CountStat

    try:
        stat = parse_stat(spec)
    except Exception:  # noqa: BLE001 - malformed spec: let run_stats raise
        return None
    stats = stat.stats if hasattr(stat, "stats") else [stat]
    if not stats or not all(isinstance(s, CountStat) for s in stats):
        return None
    return stat


def _column_summary(ft, col, cnt, total, mn, mx):
    """Normalize one column's aggregate across the pyramid and fallback
    paths: integer-backed columns report integer sums, floats report
    floats; an all-null column reports None bounds."""
    a = next((a for a in ft.attributes if a.name == col), None)
    int_backed = (
        a is not None
        and a.type.numpy_dtype is not None
        and np.dtype(a.type.numpy_dtype).kind in "iub"
    )
    return {
        "count": int(cnt),
        "sum": int(total) if int_backed else float(total),
        "min": float(mn) if cnt else None,
        "max": float(mx) if cnt else None,
    }


def _aggregate_columns(ft, columns, cols) -> Dict[str, Any]:
    """Host-exact aggregate over already-filtered result columns — the
    uncached reference the pyramid path must match."""
    n = getattr(columns, "num_rows", None)
    if n is None:
        n = len(next(iter(columns.values()), []))
    out: Dict[str, Any] = {"count": int(n), "columns": {}}
    for c in cols:
        v = np.asarray(columns[c])
        nulls = columns.get(c + "__null")
        if nulls is not None:
            v = v[~np.asarray(nulls, dtype=bool)]
        cnt = len(v)
        total = v.sum() if cnt else 0
        mn = float(v.min()) if cnt else np.inf
        mx = float(v.max()) if cnt else -np.inf
        out["columns"][c] = _column_summary(ft, c, cnt, total, mn, mx)
    return out


def _materialize(columns) -> Columns:
    """LazyColumns -> plain dict (for code that mutates/re-orders); plain
    dicts pass through."""
    if isinstance(columns, LazyColumns):
        return columns.materialize()
    return columns


def _narrow_ft(ft: FeatureType, props: Sequence[str]) -> FeatureType:
    """The result TYPE narrows with a projection, like the reference's
    transform schema — consumers (exports, arrow) iterate result.ft and
    must only see present attributes."""
    keep = set(props)
    user_data = dict(ft.user_data)
    if user_data.get("geomesa.index.dtg") not in keep:
        # role bindings must not point at projected-away attributes
        user_data.pop("geomesa.index.dtg", None)
    return FeatureType(
        ft.name,
        [a for a in ft.attributes if a.name in keep],
        user_data,
    )


def _dedupe_against(columns: Columns, seen: set) -> Columns:
    """Incremental first-occurrence fid dedupe for the streaming union
    path: drop rows whose fid was already emitted by an earlier part,
    record the rest into ``seen`` — the same winners _dedupe_by_fid
    picks over the concatenated parts. Vectorized like its batch
    sibling: np.unique for in-part winners, np.isin vs the seen set."""
    fids = columns.get("__fid__")
    if fids is None or len(fids) == 0:
        return columns
    fids_s = np.asarray(fids).astype(str)
    _, first_idx = np.unique(fids_s, return_index=True)
    keep = np.zeros(len(fids_s), dtype=bool)
    keep[first_idx] = True
    if seen:
        keep &= ~np.isin(fids_s, np.array(list(seen), dtype=fids_s.dtype))
    seen.update(fids_s[keep].tolist())
    if keep.all():
        return columns
    return {k: v[keep] for k, v in columns.items()}


def _dedupe_by_fid(columns: Columns) -> Columns:
    fids = columns.get("__fid__")
    if fids is None or len(fids) == 0:
        return columns
    _, first_idx = np.unique(fids.astype(str), return_index=True)
    if len(first_idx) == len(fids):
        return columns
    return take_rows(columns, np.sort(first_idx))


def _apply_sampling(query: Query, columns: Columns) -> Columns:
    """hints['sampling'] = fraction in (0, 1]; optional hints['sample_by']
    threads the 1-in-n selection per attribute value (SamplingIterator /
    FeatureSampler analog)."""
    frac = query.hints.get("sampling")
    if not frac or frac >= 1.0:
        return columns
    n = len(next(iter(columns.values()), []))
    if n == 0:
        return columns
    nth = max(1, int(round(1.0 / float(frac))))
    by = query.hints.get("sample_by")
    if by and by in columns:
        keep = np.zeros(n, dtype=bool)
        col = columns[by]
        for v in np.unique(col):
            idx = np.flatnonzero(col == v)
            keep[idx[::nth]] = True
    else:
        keep = np.zeros(n, dtype=bool)
        keep[::nth] = True
    return {k: v[keep] for k, v in columns.items()}


def apply_projection(ft: FeatureType, query: Query, columns: Columns):
    """Sampling/sort/limit + projection, including derived-attribute
    transforms ("out=EXPR" properties — QueryPlanner.scala:192-284). Returns
    (possibly-derived feature type, projected columns)."""
    from dataclasses import replace

    from geomesa_tpu.index.transforms import QueryTransforms

    tf = QueryTransforms.parse(ft, query.properties)
    if tf is None:
        columns = _apply_query_options(ft, query, columns)
        if query.properties is not None:
            keep = set(query.properties)
            ft = _narrow_ft(ft, query.properties)
            columns = {
                k: v
                for k, v in columns.items()
                if k.startswith("__") or _column_base(k) in keep
            }
        return ft, columns
    # sort/limit/sampling run on the ORIGINAL attributes; the property
    # filter must not run (expressions still need their source columns)
    columns = _apply_query_options(ft, replace(query, properties=None), columns)
    return tf.apply(columns)


def _apply_query_options(ft: FeatureType, query: Query, columns: Columns) -> Columns:
    columns = _apply_sampling(query, columns)
    n = len(next(iter(columns.values()), []))
    if query.sort_by and n:
        keys = []
        for attr, ascending in reversed(query.sort_by):
            col = columns[attr] if attr in columns else columns[attr + "__x"]
            keys.append(col if ascending else _invert_order(col))
        order = np.lexsort(keys)
        columns = take_rows(columns, order)
    if query.max_features is not None and n > query.max_features:
        columns = {k: v[: query.max_features] for k, v in columns.items()}
    if query.properties is not None:
        keep = {"__fid__"}
        for p in query.properties:
            keep.add(p)
            keep.add(p + "__x")
            keep.add(p + "__y")
            keep.add(p + "__null")
        columns = {k: v for k, v in columns.items() if k in keep}
    return columns


def _invert_order(col: np.ndarray) -> np.ndarray:
    if col.dtype == object or col.dtype.kind in "US":
        # rank-invert for objects and (interned) strings — numpy has no
        # 'negative' loop for either
        order = np.argsort(col, kind="stable")
        ranks = np.empty(len(col), dtype=np.int64)
        ranks[order] = np.arange(len(col))
        return -ranks
    return -col
