"""Banded polygon ray cast on device (point schemas): query_many fuses
INTERSECTS(polygon) plans into one dual-plane device execution; rows the
f32 cast can't certify (the band near edges/vertices) take the host's
exact test. Results must match per-query host execution bit-for-bit,
including points placed exactly ON edges and vertices."""

import jax
import numpy as np
import pytest

from geomesa_tpu.geom.base import MultiPolygon, Point, Polygon
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.parallel import executor as ex
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore

BASE = int(np.datetime64("2026-01-01T00:00:00", "ms").astype("int64"))


@pytest.fixture(autouse=True)
def _force_batch(monkeypatch):
    monkeypatch.setenv("GEOMESA_EXACT_DEVICE", "1")
    monkeypatch.setenv("GEOMESA_DEVBATCH", "1")
    monkeypatch.setenv("GEOMESA_SEEK", "0")


def _stores(x, y, t, devices=None):
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(
        executor=TpuScanExecutor(default_mesh(jax.devices()[:devices]))
    )
    for s in (host, tpu):
        s.create_schema(parse_spec("t", "dtg:Date,*geom:Point:srid=4326"))
        with s.writer("t") as w:
            for i in range(len(x)):
                w.write([int(t[i]), Point(float(x[i]), float(y[i]))], fid=f"f{i}")
    return host, tpu


def _fids(res):
    return sorted(res.fids)


def _parity(host, tpu, cqls):
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql


TRIANGLE = "POLYGON ((-20 -20, 30 -10, 5 35, -20 -20))"
CONCAVE = "POLYGON ((-40 -40, 40 -40, 40 40, 0 0, -40 40, -40 -40))"
HOLED = ("POLYGON ((-30 -30, 30 -30, 30 30, -30 30, -30 -30), "
         "(-10 -10, 10 -10, 10 10, -10 10, -10 -10))")
MULTI = ("MULTIPOLYGON (((-60 -60, -45 -60, -45 -45, -60 -45, -60 -60)), "
         "((45 45, 60 45, 52 60, 45 45)))")


def test_polygon_batch_parity(monkeypatch):
    rng = np.random.default_rng(1)
    n = 30_000
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 20 * 86400_000, n)
    host, tpu = _stores(x, y, t)
    cqls = [f"intersects(geom, {g})" for g in (TRIANGLE, CONCAVE, HOLED, MULTI)]
    # the batch must actually take the poly path
    calls = {"n": 0}
    orig = ex.DeviceSegment.dispatch_poly_batch

    def counting(self, *a, **k):
        calls["n"] += 1
        return orig(self, *a, **k)

    monkeypatch.setattr(ex.DeviceSegment, "dispatch_poly_batch", counting)
    _parity(host, tpu, cqls)
    assert calls["n"] >= 1


def test_polygon_batch_parity_with_time():
    rng = np.random.default_rng(2)
    n = 25_000
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 20 * 86400_000, n)
    host, tpu = _stores(x, y, t)
    cqls = [
        f"intersects(geom, {g}) AND dtg DURING "
        f"2026-01-{d:02d}T00:00:00Z/2026-01-{d + 8:02d}T00:00:00Z"
        for g, d in ((TRIANGLE, 2), (CONCAVE, 5), (HOLED, 1), (TRIANGLE, 9))
    ]
    _parity(host, tpu, cqls)


def test_polygon_boundary_points():
    """Points exactly on edges, vertices, and horizontal edges: the band
    must route them to the host so inclusion matches exactly."""
    # triangle edge from (-20,-20) to (30,-10): param points on the edge
    ts = np.linspace(0, 1, 41)
    ex_x = -20 + ts * 50
    ex_y = -20 + ts * 10
    # horizontal edge of HOLED at y=-30, x in [-30, 30]
    hx = np.linspace(-30, 30, 31)
    hy = np.full_like(hx, -30.0)
    # vertices of everything
    vx = np.array([-20.0, 30.0, 5.0, -40.0, 40.0, 0.0, -30.0, 30.0, -10.0, 10.0])
    vy = np.array([-20.0, -10.0, 35.0, -40.0, 40.0, 0.0, -30.0, 30.0, -10.0, 10.0])
    rng = np.random.default_rng(3)
    bx = rng.uniform(-70, 70, 4000)
    by = rng.uniform(-70, 70, 4000)
    x = np.concatenate([ex_x, hx, vx, bx])
    y = np.concatenate([ex_y, hy, vy, by])
    t = BASE + rng.integers(0, 86400_000, len(x))
    host, tpu = _stores(x, y, t)
    cqls = [f"intersects(geom, {g})" for g in (TRIANGLE, CONCAVE, HOLED, MULTI)]
    _parity(host, tpu, cqls)


def test_polygon_bitmap_protocol():
    rng = np.random.default_rng(4)
    n = 20_000
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 86400_000, n)
    host, tpu = _stores(x, y, t)
    cqls = [f"intersects(geom, {g})" for g in (TRIANGLE, CONCAVE, HOLED, MULTI)]
    _parity(host, tpu, cqls)
    _parity(host, tpu, cqls)  # learned span window on the second stream


def test_polygon_respects_deletes():
    rng = np.random.default_rng(5)
    n = 12_000
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 86400_000, n)
    host, tpu = _stores(x, y, t)
    doomed = [f"f{i}" for i in range(0, n, 11)]
    for s in (host, tpu):
        s.delete_features("t", doomed)
    cqls = [f"intersects(geom, {g})" for g in (TRIANGLE, CONCAVE)] * 2
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql
        assert not set(res.fids) & set(doomed)


def test_overlapping_multipolygon_declines():
    """Overlapping members break crossing parity; the descriptor must
    return None so such queries ride the conservative path (still
    correct results)."""
    rng = np.random.default_rng(6)
    n = 8000
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 86400_000, n)
    host, tpu = _stores(x, y, t)
    overlap = ("MULTIPOLYGON (((-20 -20, 20 -20, 20 20, -20 20, -20 -20)), "
               "((0 0, 30 0, 30 30, 0 30, 0 0)))")
    cqls = [f"intersects(geom, {overlap})"] * 2
    _parity(host, tpu, cqls)


def test_rect_polygon_stays_on_box_path(monkeypatch):
    """Rect INTERSECTS must keep riding the exact box batch, not the
    raycast."""
    rng = np.random.default_rng(7)
    n = 6000
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 86400_000, n)
    host, tpu = _stores(x, y, t)
    rect = "POLYGON ((-10 -10, 10 -10, 10 10, -10 10, -10 -10))"
    calls = {"n": 0}
    orig = ex.DeviceSegment.dispatch_poly_batch

    def counting(self, *a, **k):
        calls["n"] += 1
        return orig(self, *a, **k)

    monkeypatch.setattr(ex.DeviceSegment, "dispatch_poly_batch", counting)
    cqls = [f"intersects(geom, {rect})"] * 3
    _parity(host, tpu, cqls)
    assert calls["n"] == 0


def test_polygon_overflow_escalates_per_query():
    """Crushed run capacity on a NON-temporal poly batch: the single-query
    escalation refetch must share the batch's argument layout (the dummy
    window rides along) and return identical results."""
    rng = np.random.default_rng(8)
    n = 12_000
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 86400_000, n)
    host, tpu = _stores(x, y, t)
    cqls = [f"intersects(geom, {g})" for g in (TRIANGLE, CONCAVE, HOLED, MULTI)]
    tpu.query_many("t", cqls)  # build mirror
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    for seg in dev.segments:
        seg._rcap = 4
    _parity(host, tpu, cqls)


def test_polygon_bitmap_span_overflow():
    """A crushed span window on one device (where the bitmap is framed
    over the whole segment) falls back per query, exactly."""
    rng = np.random.default_rng(9)
    n = 12_000
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 86400_000, n)
    host, tpu = _stores(x, y, t, devices=1)
    cqls = [f"intersects(geom, {g})" for g in (CONCAVE, HOLED, TRIANGLE, MULTI)]
    tpu.query_many("t", cqls)
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    for seg in dev.segments:
        seg._span_cap = 8
    _parity(host, tpu, cqls)


def test_near_horizontal_long_edge_band():
    """A long, slightly-tilted edge: xint's f32 error amplifies with the
    slope, so rows near it must be banded (slope-scaled tolerance) and
    certified by the host — results exactly match."""
    rng = np.random.default_rng(10)
    # points scattered in a thin strip around the tilted edge y ~= 50
    n = 20_000
    x = rng.uniform(-65, 65, n)
    y = 50.0 + rng.uniform(-0.002, 0.002, n)
    # plus background
    xb = rng.uniform(-70, 70, 5000)
    yb = rng.uniform(20, 70, 5000)
    x = np.concatenate([x, xb])
    y = np.concatenate([y, yb])
    t = BASE + rng.integers(0, 86400_000, len(x))
    host, tpu = _stores(x, y, t)
    sliver = ("POLYGON ((-60 50, 60 50.0003, 60 65, -60 65, -60 50))")
    cqls = [f"intersects(geom, {sliver})"] * 2
    _parity(host, tpu, cqls)


def test_polygon_chunking_past_batch_max():
    host, tpu = (None, None)
    rng = np.random.default_rng(11)
    n = 9000
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 86400_000, n)
    host, tpu = _stores(x, y, t)
    saved = ex.TpuScanExecutor.BATCH_MAX
    ex.TpuScanExecutor.BATCH_MAX = 3  # force multiple chunks + a lone tail
    try:
        polys = [TRIANGLE, CONCAVE, HOLED, MULTI, TRIANGLE, CONCAVE, HOLED]
        cqls = [f"intersects(geom, {g})" for g in polys]
        got = tpu.query_many("t", cqls)
    finally:
        ex.TpuScanExecutor.BATCH_MAX = saved
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql
