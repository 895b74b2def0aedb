"""k-nearest-neighbor search via expanding index queries.

Reference: KNearestNeighborSearchProcess (knn/KNNQuery.scala,
knn/GeoHashSpiral.scala) spirals outward over geohash cells until k features
are in hand and the k-th distance bounds the search. Here the spiral is an
expanding bbox over the Z2/Z3 index (doubling radius), with the same
termination: once >= k candidates are found, one final query at the k-th
distance guarantees no closer feature was missed.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import numpy as np

from geomesa_tpu.process.geodesy import degrees_boxes, haversine_m


def _bbox_cql(ft, boxes, extra: Optional[str]) -> str:
    geom = ft.default_geometry.name
    parts = [
        f"bbox({geom}, {b[0]!r}, {b[1]!r}, {b[2]!r}, {b[3]!r})" for b in boxes
    ]
    cql = parts[0] if len(parts) == 1 else "(" + " OR ".join(parts) + ")"
    if extra:
        cql = f"({cql}) AND ({extra})"
    return cql


def _distances(ft, result, x: float, y: float) -> np.ndarray:
    geom = ft.default_geometry.name
    return haversine_m(result.columns[geom + "__x"], result.columns[geom + "__y"], x, y)


def knn_search(
    store,
    name: str,
    x: float,
    y: float,
    k: int = 10,
    initial_radius_m: float = 1000.0,
    max_radius_m: float = 2_000_000.0,
    cql: Optional[str] = None,
) -> List[Tuple[str, float]]:
    """[(fid, distance_m)] of the k nearest features to (x, y), ascending.
    Features beyond ``max_radius_m`` are never returned — identical
    semantics on the device top-k and host expanding-bbox paths.

    ``last_knn_path()`` reports which path answered this THREAD's most
    recent call ("device-topk" | "host-bbox") — benches and tests
    consult it per call so a silent fallback can never report host time
    as a device number (thread-local: concurrent callers, e.g. the REST
    server's threads, cannot clobber each other's marker)."""
    from geomesa_tpu.parallel.mesh import device_tripped, trip_device

    _PATH_LOCAL.path = "host-bbox"
    ft = store.get_schema(name)
    if (
        cql is None
        and _device_knn_wanted()
        and not device_tripped(store.executor, "GEOMESA_KNN_DEVICE")
    ):
        try:
            direct = _device_knn(store, name, ft, x, y, k, max_radius_m)
        except Exception as e:  # noqa: BLE001 - device failure
            # a lost device or backend compile error must not kill the
            # search: the host expanding-bbox path answers identically
            # (round-4 silicon: the suite's kNN config died on a TPU
            # setup/compile Unavailable mid-batch with no fallback)
            trip_device(store.executor, "GEOMESA_KNN_DEVICE", "knn", e)
            direct = None
        if direct is not None:
            _PATH_LOCAL.path = "device-topk"
            return direct
    radius = float(initial_radius_m)
    result = None
    while True:
        result = store.query(name, _bbox_cql(ft, degrees_boxes(x, y, radius), cql))
        if len(result) >= k or radius >= max_radius_m:
            break
        radius *= 2.0
    if len(result) == 0:
        return []
    d = _distances(ft, result, x, y)
    order = np.argsort(d, kind="stable")[:k]
    kth = float(d[order[-1]])
    # the bbox is not a circle: if the k-th distance exceeds the scanned
    # radius, a closer feature may sit in the circle's corners — requery at
    # the k-th distance to close the search (KNNQuery's final window)
    if kth > radius and radius < max_radius_m:
        result = store.query(name, _bbox_cql(ft, degrees_boxes(x, y, kth), cql))
        d = _distances(ft, result, x, y)
        order = np.argsort(d, kind="stable")[:k]
    fids = result.fids
    return [
        (str(fids[i]), float(d[i])) for i in order if d[i] <= max_radius_m
    ]


def _device_knn_wanted() -> bool:
    """Cost choice: the one-pass device top-k ranks EVERY resident row —
    a bargain on a LOCAL accelerator, a full scan on the CPU backend where
    the expanding-bbox seek path touches only candidate cells. Over a
    high-latency device link (a remote chip) the per-dispatch round
    trip alone dwarfs the host seek's sub-ms answer, so auto declines
    there too (measured link_latency_ms).
    GEOMESA_KNN_DEVICE: auto | 1 | 0."""
    import os

    env = os.environ.get("GEOMESA_KNN_DEVICE", "auto")
    if env == "0":
        return False
    if env == "1":
        return True
    import jax

    if jax.default_backend() == "cpu":
        return False
    from geomesa_tpu.parallel.mesh import link_latency_ms

    return link_latency_ms() <= _LINK_BUDGET_MS


# auto device paths decline when one round trip costs more than this
_LINK_BUDGET_MS = 10.0

_PATH_LOCAL = threading.local()


def last_knn_path() -> str:
    """Which path answered this thread's most recent knn_search call
    ("device-topk" | "host-bbox"; "?" before any call)."""
    return getattr(_PATH_LOCAL, "path", "?")


def _device_knn(store, name: str, ft, x: float, y: float, k: int,
                max_radius_m: float = np.inf):
    """One-pass device top-k (executor.knn_candidates): every chip ranks
    its resident rows and returns k candidates; exact f64 re-rank here.
    None when the store has no device executor / no point index."""
    import time as _time

    from geomesa_tpu.utils import devstats

    t0 = _time.perf_counter()
    dev0 = devstats.receipt_snapshot()
    knn = getattr(store.executor, "knn_candidates", None)
    if knn is None:
        return None
    if getattr(store, "_age_off_cutoff", lambda _ft: None)(ft) is not None:
        return None  # expired rows are masked by the query path only
    # lazy stores (FsDataStore) may have partitions on disk only; kNN has
    # no pruning filter, so everything must be resident before ranking
    ensure = getattr(store, "_ensure_loaded", None)
    if ensure is not None:
        ensure(name, None)
    tables = store._tables.get(name, {})
    table = tables.get("z3") or tables.get("z2")
    if table is None or table.num_rows == 0:
        return None
    parts = knn(table, x, y, k)
    if parts is None:
        return None
    geom = ft.default_geometry.name
    fids: List[str] = []
    dists: List[np.ndarray] = []
    seen = set()
    for block, rows in parts:
        px = block.gather(geom + "__x", rows)
        py = block.gather(geom + "__y", rows)
        bf = block.gather("__fid__", rows)
        keep = [i for i, f in enumerate(bf) if f not in seen]
        seen.update(bf[keep])
        fids.extend(bf[keep])
        dists.append(haversine_m(px[keep], py[keep], x, y))
    out: List[Tuple[str, float]]
    if not fids:
        out = []
    else:
        d = np.concatenate(dists)
        order = np.argsort(d, kind="stable")[:k]
        # radius bound applied BEFORE auditing so hits == returned results
        out = [
            (str(fids[i]), float(d[i])) for i in order if d[i] <= max_radius_m
        ]
    # the fast path bypasses store.query, so it must audit itself — the
    # host fallback is audited per bbox query it issues
    if store.metrics is not None:
        store.metrics.inc("queries")
        store.metrics.update_timer("query.scan", _time.perf_counter() - t0)
    if store.audit_writer is not None:
        from geomesa_tpu.utils.audit import QueryEvent

        # the device-heaviest path must carry its cost receipt like any
        # store.query row (compiles + both transfer directions)
        receipt = devstats.receipt_since(dev0)
        store.audit_writer.write_event(
            QueryEvent(
                store=type(store).__name__,
                type_name=name,
                user=store.user,
                filter=f"KNN({x}, {y}, k={k})",
                hints={"knn": k},
                date_ms=int(_time.time() * 1000),
                planning_ms=0.0,
                scanning_ms=1000 * (_time.perf_counter() - t0),
                hits=len(out),
                scan_path="device-topk",
                recompiles=int(receipt["recompiles"]),
                h2d_bytes=int(receipt["h2d_bytes"]),
                d2h_bytes=int(receipt["d2h_bytes"]),
                pad_ratio=float(receipt["pad_ratio"]),
            )
        )
    return out
