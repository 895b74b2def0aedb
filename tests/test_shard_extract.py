"""Per-shard bitmap extraction (the true multi-chip shape): mask AND
span framing run inside shard_map — each chip frames only its local hit
window, the host stitches shard windows with row offsets. No cross-chip
collectives at all: the per-tablet partial results merged client-side
(AccumuloQueryPlan.scala:113-140), redone as static shard windows.
"""

import numpy as np
import pytest

from geomesa_tpu.geom.base import Point
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.parallel import executor as ex
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore

SPEC = "dtg:Date,kind:String,*geom:Point:srid=4326"
BASE = int(np.datetime64("2026-01-01T00:00:00", "ms").astype("int64"))


@pytest.fixture(autouse=True)
def _force(monkeypatch):
    monkeypatch.setenv("GEOMESA_EXACT_DEVICE", "1")
    monkeypatch.setenv("GEOMESA_DEVBATCH", "1")
    monkeypatch.setenv("GEOMESA_SEEK", "0")


def _stores(n=60_000, seed=31):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-170, 170, n)
    y = rng.uniform(-80, 80, n)
    t = BASE + rng.integers(0, 20 * 86400_000, n)
    kinds = np.array([f"k{i % 4}" for i in range(n)], dtype=object)
    fids = np.array([f"f{i}" for i in range(n)], dtype=object)
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
    for s in (host, tpu):
        s.create_schema(parse_spec("t", SPEC))
        with s.writer("t") as w:
            w.write_columns({
                "__fid__": fids, "dtg": t.astype(np.int64), "kind": kinds,
                "geom__x": x, "geom__y": y,
            })
    return host, tpu


def _parity(host, tpu, cqls):
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        assert sorted(res.fids) == sorted(host.query("t", cql).fids), cql
    return got


def test_shard_extract_parity_and_fn_used():
    host, tpu = _stores()
    cqls = [
        "bbox(geom, -30, -20, 20, 25)",
        "bbox(geom, 0, 0, 60, 50)",
        "bbox(geom, -160, -70, -100, 0)",
    ]
    before = len(ex._EXACT_SHARD_BITMAP_FNS)
    _parity(host, tpu, cqls)
    assert len(ex._EXACT_SHARD_BITMAP_FNS) > 0
    # repeat stream reuses the learned shard window
    _parity(host, tpu, cqls)
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    for seg in dev.segments:
        assert seg._shard_span_cap > 0  # learned from the stream
        assert seg.shard_span_cap() <= seg.shard_n()
    assert before <= len(ex._EXACT_SHARD_BITMAP_FNS)


def test_shard_extract_with_time_window():
    host, tpu = _stores()
    _parity(host, tpu, [
        "bbox(geom, -40, -30, 30, 35) AND "
        "dtg DURING 2026-01-02T00:00:00Z/2026-01-10T00:00:00Z",
        "bbox(geom, -90, -60, 70, 60) AND "
        "dtg DURING 2026-01-05T00:00:00Z/2026-01-18T00:00:00Z",
    ])


def test_shard_extract_attr_plane():
    host, tpu = _stores()
    _parity(host, tpu, [
        "kind = 'k1' AND bbox(geom, -60, -40, 40, 30)",
        "kind = 'k3' AND bbox(geom, -100, -60, 80, 60)",
    ])


def test_shard_window_overflow_falls_back():
    """A crushed per-shard window far narrower than the local spans must
    fall back to the single-query path, then learn back out."""
    host, tpu = _stores(n=100_000)
    cqls = ["bbox(geom, -160, -70, 160, 70)", "bbox(geom, -80, -60, 80, 60)"]
    tpu.query_many("t", cqls)  # build mirror
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    for seg in dev.segments:
        seg._shard_span_cap = 1 << 13  # << true local spans at this n
    _parity(host, tpu, cqls)
    assert all(s.shard_span_cap() > (1 << 13) for s in dev.segments)


def test_shard_extract_polygon_dual_plane():
    """Non-rect INTERSECTS on a point schema rides the per-shard DUAL
    (hit/decided) windows; band rows still take the host test."""
    host, tpu = _stores(n=30_000)
    _parity(host, tpu, [
        "intersects(geom, POLYGON ((-40 -40, 30 -35, 10 30, -35 20, -40 -40)))",
        "intersects(geom, POLYGON ((-15 -50, 50 -40, 25 15, -15 -50)))",
    ])
    assert any(k[0] == "poly" for k in ex._DUAL_SHARD_BITMAP_FNS)


def test_shard_extract_extent_dual_plane():
    """Extent schemas (mixed rects/triangles/lines/points/nulls) ride the
    per-shard dual windows on the xz indices."""
    from geomesa_tpu.geom.base import LineString, Polygon

    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
    for s in (host, tpu):
        s.create_schema(parse_spec("e", "dtg:Date,*geom:Geometry:srid=4326"))
    rng = np.random.default_rng(33)
    rows = []
    for i in range(3000):
        x0 = float(rng.uniform(-170, 160))
        y0 = float(rng.uniform(-80, 70))
        k = i % 4
        if k == 0:
            g = Polygon([[x0, y0], [x0 + 1, y0], [x0 + 1, y0 + 1],
                         [x0, y0 + 1], [x0, y0]])
        elif k == 1:
            g = Polygon([[x0, y0], [x0 + 2, y0], [x0 + 1, y0 + 2], [x0, y0]])
        elif k == 2:
            g = LineString([(x0, y0), (x0 + 1.5, y0 + 0.7)])
        else:
            g = None
        t = int(BASE + int(rng.integers(0, 10 * 86400_000)))
        rows.append((t, g))
    for s in (host, tpu):
        with s.writer("e") as w:
            for i, (t, g) in enumerate(rows):
                w.write([t, g], fid=f"e{i}")
    cqls = [
        "bbox(geom, -60, -40, 10, 20)",
        "bbox(geom, -100, -60, 80, 50)",
        "bbox(geom, -30, -30, 40, 35) AND "
        "dtg DURING 2026-01-02T00:00:00Z/2026-01-08T00:00:00Z",
        "bbox(geom, 20, -20, 100, 45) AND "
        "dtg DURING 2026-01-03T00:00:00Z/2026-01-09T00:00:00Z",
    ]
    got = tpu.query_many("e", cqls)
    for cql, res in zip(cqls, got):
        assert sorted(res.fids) == sorted(host.query("e", cql).fids), cql
    assert any(k[0] == "xz" for k in ex._DUAL_SHARD_BITMAP_FNS)


def test_shard_extract_empty_and_deletes():
    host, tpu = _stores(n=20_000)
    for s in (host, tpu):
        s.delete_features("t", "IN ('f5', 'f100', 'f15000')")
    _parity(host, tpu, [
        "bbox(geom, 179.5, 89.0, 179.9, 89.9)",  # ~empty
        "bbox(geom, -30, -20, 20, 25)",
    ])


def test_default_dispatch_is_shard_extraction_at_multi_device(monkeypatch):
    """VERDICT r4 #6: a multi-device mesh must dispatch batched scans
    through the per-shard bitmap edition — no full-mask collective
    (_gathered) anywhere in the trace. The wire format follows the mesh
    size alone: no switch picks another."""
    from geomesa_tpu.utils import trace

    monkeypatch.delenv("GEOMESA_PALLAS", raising=False)
    mesh = default_mesh()
    assert mesh.devices.size > 1  # the conftest 8-device CPU mesh
    host, tpu = _stores(n=20_000, seed=77)
    cqls = ["bbox(geom, -30, -20, 20, 25)", "bbox(geom, 0, 0, 60, 50)"]
    with trace.exporting(trace.InMemoryTraceExporter()) as ring:
        _parity(host, tpu, cqls)
    kinds = {
        sp.attributes["proto"]
        for root in ring.traces
        for sp in root.find("device.fetch.shared")
    }
    assert kinds == {"bitmap_shard"}, kinds
