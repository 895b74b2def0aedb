"""Spans and counters where a heatmap request's time goes: the coalescer's
queue (``batch.gather``, ``batch.wait``), the density push-down
(``density``, ``density.certify``, ``density.band_bitmap``, the
``density.band.overflow`` counter),
the host reducer (``agg.reduce``), and every live span as a profiler
annotation on the device trace's clock.

Data: points on a one-degree lattice half the time, so a one-degree
density grid has a wide band of rows the device cannot certify in f32.
"""

import glob
import os
import threading

import jax
import numpy as np
import pytest

from geomesa_tpu.index.planner import Query
from geomesa_tpu.ops import aggregations
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore
from geomesa_tpu.utils import devstats, faults, trace
from geomesa_tpu.utils.config import properties

SPEC = "dtg:Date,*geom:Point:srid=4326"
BASE = int(np.datetime64("2026-01-01T00:00:00", "ms").astype("int64"))
ENV = (-180.0, -90.0, 180.0, 90.0)
CQL = "bbox(geom, -150, -60, 150, 60)"
NEW_SITES = ("batch.gather", "batch.wait", "density", "density.certify", "agg.reduce",
             "density.band_bitmap")


def _density_query(width=360, height=180, **hint):
    return Query.cql(CQL, hints={"density": {"envelope": ENV, "width": width,
                                             "height": height, **hint}})


def _columns(n=6000, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-170, 170, n)
    y = rng.uniform(-80, 80, n)
    x[::2] = np.round(x[::2])  # on the grid's cell edges
    y[::2] = np.round(y[::2])
    return {
        "__fid__": np.array([f"p{i}" for i in range(n)], dtype=object),
        "dtg": BASE + rng.integers(0, 20 * 86400_000, n),
        "geom__x": x,
        "geom__y": y,
    }


def _store(executor):
    store = TpuDataStore(executor=executor)
    store.create_schema(parse_spec("pts", SPEC))
    store.writer("pts").write_columns(_columns())
    return store


def _device_store():
    # one device: threaded solo queries on the 8-device CPU mesh can meet
    # in XLA's collective rendezvous (see tests/test_batch_coalesce.py)
    return _store(TpuScanExecutor(default_mesh(jax.devices()[:1])))


@pytest.fixture(scope="module")
def host_grid():
    with properties(geomesa_agg_enabled="false"):
        res = _store(HostScanExecutor()).query("pts", _density_query())
    return np.asarray(res.aggregate["density"])


@pytest.fixture(autouse=True)
def _device_density(monkeypatch):
    # the CPU backend declines the push-down in auto mode; the chip's branch
    monkeypatch.setenv("GEOMESA_DENSITY_DEVICE", "1")
    with properties(geomesa_agg_enabled="false"):
        yield


def _hold_slot(ctl):
    """Hold one admission slot, so every arrival passes the coalescer's
    concurrency gate and opens or joins a window."""
    import contextvars

    ctx = contextvars.Context()
    admit = ctl.admit()
    ctx.run(admit.__enter__)
    return lambda: ctx.run(admit.__exit__, None, None, None)


def _coalesced_density(store, n=3):
    """n concurrent density requests in one coalescer window; the results."""
    results = [None] * n
    errors = []
    barrier = threading.Barrier(n)

    def worker(i):
        try:
            barrier.wait(timeout=10)
            results[i] = store.query("pts", _density_query())
        except Exception as e:  # noqa: BLE001 - surfaced via errors
            errors.append(e)

    release = _hold_slot(store.admission)
    try:
        with properties(geomesa_batch_enabled="true", geomesa_batch_window_ms="100"):
            threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        release()
    assert not errors, errors
    return results


def _names(root):
    return {sp.name for sp in root.walk()}


def test_coalesced_members_wait_and_the_leader_gathers(host_grid):
    store = _device_store()
    for _attempt in range(6):
        with trace.exporting(trace.InMemoryTraceExporter()) as ring:
            results = _coalesced_density(store)
        roots = [r for r in ring.traces if r.name == "query"]
        leaders = [r for r in roots if "batch.gather" in _names(r)]
        members = [r for r in roots if "batch.wait" in _names(r)]
        if not (leaders and members):
            continue  # the schedule split the arrivals; try again
        gathered = sum(sp.attributes["n"] for r in leaders for sp in r.find("batch.gather"))
        assert gathered == len(roots) == 3
        assert len(leaders) + len(members) == 3
        # the leader runs every member's push-down on its own thread
        assert all(r.find("query.member") for r in leaders)
        assert not any("batch.gather" in _names(r) for r in members)
        for res in results:
            np.testing.assert_array_equal(res.aggregate["density"], host_grid)
        return
    pytest.fail("no coalesced group of a leader and members ever formed")


def test_device_grid_span_certifies_the_band(host_grid):
    store = _device_store()
    with trace.exporting(trace.InMemoryTraceExporter()) as ring:
        res = store.query("pts", _density_query())
    assert res.plan.scan_path == "device-density"
    np.testing.assert_array_equal(res.aggregate["density"], host_grid)
    (sp,) = ring.traces[-1].find("density")
    assert sp.attributes["outcome"] == "grid"
    assert sp.attributes["band"] == "indices"
    assert sp.attributes["segments"] >= 1
    (cert,) = sp.find("density.certify")
    assert sp.attributes["band_rows"] == cert.attributes["rows"] > 0
    # the band's D2H reads are device.fetch boundaries like every other
    assert len(sp.find("device.fetch")) >= 3


def test_band_overflow_counts_and_the_host_answers(host_grid, monkeypatch):
    """A band past the cap is still counted as an overflow; the device's
    grid stands and the band comes back as a bitmap to be certified."""
    store = _device_store()
    monkeypatch.setattr(aggregations, "DENSITY_BAND_CAP", 16)
    reg = devstats.devstats_metrics()
    before = reg.counter("density.band.overflow")
    with trace.exporting(trace.InMemoryTraceExporter()) as ring:
        res = store.query("pts", _density_query())
    assert reg.counter("density.band.overflow") == before + 1
    assert devstats.device_debug()["density"]["band_overflow"] == before + 1
    assert res.plan.scan_path == "device-density"
    np.testing.assert_array_equal(res.aggregate["density"], host_grid)
    (sp,) = ring.traces[-1].find("density")
    assert sp.attributes["outcome"] == "grid"
    assert sp.attributes["band"] == "bitmap"
    (ev,) = [e for e in sp.events if e["name"] == "density.band_overflow"]
    assert ev["cap"] == 16 and ev["cnt"] > 16
    (bm,) = sp.find("density.band_bitmap")
    assert bm.attributes["rows"] >= ev["cnt"]
    # the bitmap's read is a device.fetch like every other D2H
    assert bm.find("device.fetch")
    (cert,) = sp.find("density.certify")
    assert cert.attributes["rows"] == sp.attributes["band_rows"] == bm.attributes["rows"]


@pytest.mark.parametrize("why", ["band_overflow", "device_off"])
def test_host_density_reduces_under_assemble(host_grid, monkeypatch, why):
    store = _device_store()
    hint = {}
    if why == "band_overflow":
        # a band overflow no longer reaches the host reducer (the band comes
        # back as a bitmap); the exact hint is the way there with the device on
        hint["exact"] = True
    else:
        monkeypatch.setenv("GEOMESA_DENSITY_DEVICE", "0")
    with trace.exporting(trace.InMemoryTraceExporter()) as ring:
        res = store.query("pts", _density_query(**hint))
    np.testing.assert_array_equal(res.aggregate["density"], host_grid)
    root = ring.traces[-1]
    (asm,) = root.find("query.assemble")
    (red,) = [c for c in asm.children if c.name == "agg.reduce"]
    assert red.attributes == {"rows": int(host_grid.sum()), "kind": "density"}
    assert res.plan.scan_path != "device-density"
    assert not root.find("density")


def test_density_fetch_fault_degrades_to_the_host_answer(host_grid):
    """Chaos: a device.fetch fault inside the density push-down fails the
    device path (trip_device) and the host reducer answers the same grid."""
    store = _device_store()
    with trace.exporting(trace.InMemoryTraceExporter()) as ring:
        with faults.inject("device.fetch:error"):
            res = store.query("pts", _density_query())
    assert res.plan.scan_path != "device-density"
    np.testing.assert_array_equal(res.aggregate["density"], host_grid)
    (sp,) = ring.traces[-1].find("density")
    assert "outcome" not in sp.attributes
    assert [e["type"] for e in sp.events if e["name"] == "error"] == ["InjectedFault"]


def test_query_span_is_a_profiler_annotation(tmp_path):
    from jax.profiler import ProfileData

    store = _device_store()
    store.query("pts", _density_query())  # warm: mirror and compile
    with trace.exporting(trace.InMemoryTraceExporter()) as ring:
        with jax.profiler.trace(str(tmp_path)):
            store.query("pts", _density_query())
    root = ring.traces[-1]
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    found = [
        ev.duration_ns / 1e6
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host")
        for line in plane.lines for ev in line.events if ev.name == "query"
    ]
    assert len(found) == 1, found
    assert found[0] == pytest.approx(root.duration_ms, rel=0.1)


@pytest.fixture(scope="module")
def untraced_sites():
    """What each new site's trace.span returned on a run with no exporter
    installed: a coalesced group of device grids with a band, a band
    overflow fetched as a bitmap, and an exact request answered by the host
    reducer."""
    assert not trace.active()
    got = {}
    real = trace.span

    def spy(name, *a, **kw):
        sp = real(name, *a, **kw)
        if name in NEW_SITES:
            got.setdefault(name, []).append(sp)
        return sp

    mp = pytest.MonkeyPatch()
    try:
        mp.setenv("GEOMESA_DENSITY_DEVICE", "1")
        mp.setattr(trace, "span", spy)
        with properties(geomesa_agg_enabled="false"):
            store = _device_store()
            for _attempt in range(6):
                _coalesced_density(store)
                if "batch.gather" in got and "batch.wait" in got:
                    break
            mp.setattr(aggregations, "DENSITY_BAND_CAP", 16)
            store.query("pts", _density_query())
            store.query("pts", _density_query(exact=True))
    finally:
        mp.undo()
    return got


@pytest.mark.parametrize("name", NEW_SITES)
def test_untraced_site_returns_noop(untraced_sites, name):
    assert untraced_sites.get(name), f"{name} never opened"
    assert all(sp is trace.NOOP for sp in untraced_sites[name])


@pytest.mark.parametrize("jax_loaded", [True, False])
def test_live_span_annotates_only_once_jax_is_loaded(monkeypatch, jax_loaded):
    """A live span enters a profiler annotation where jax is loaded; in a
    process that never imported jax a span imports nothing, and the
    lookup stays open for when jax arrives."""
    import sys

    monkeypatch.setattr(trace, "_ANNOTATION", None)
    if not jax_loaded:
        monkeypatch.delitem(sys.modules, "jax")
    ctx = trace.span("query", force=True)
    with ctx:
        entered = ctx._ann
    assert (entered is not None) == jax_loaded
    assert ctx._ann is None  # left with the span
    assert (trace._ANNOTATION is jax.profiler.TraceAnnotation) == jax_loaded
