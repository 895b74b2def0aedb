"""Attr plane fused into the batched EXTENT scans (round-4 xz edition):
the rank-code test (member qcode vectors / [lo, hi] intervals) ANDs into
the hit plane BEFORE decided derives, so decided rows are final for the
full spatial-AND-attr predicate and the boundary ring only carries
attr-passing rows (the host per-geometry test needs no attr re-check).

Reference role: the join attribute strategy evaluated at the data
(AttributeIndex.scala:42,392) extended to extent schemas.
"""

import jax
import numpy as np
import pytest

from geomesa_tpu.geom.base import LineString, Polygon
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore

SPEC = "dtg:Date,kind:String,size:Double,*geom:Geometry:srid=4326"
BASE = int(np.datetime64("2026-01-01T00:00:00", "ms").astype("int64"))


@pytest.fixture(autouse=True)
def _force_batch(monkeypatch):
    monkeypatch.setenv("GEOMESA_EXACT_DEVICE", "1")
    monkeypatch.setenv("GEOMESA_DEVBATCH", "1")
    monkeypatch.setenv("GEOMESA_SEEK", "0")


def _rows(n, seed, null_every=13):
    """Mixed extents: axis-rects (decidable), triangles + lines (ring
    material), null geometries (placeholders)."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        x0 = float(rng.uniform(-170, 160))
        y0 = float(rng.uniform(-80, 70))
        k = i % 5
        if k in (0, 1):
            w = float(rng.uniform(0.5, 4.0))
            g = Polygon([[x0, y0], [x0 + w, y0], [x0 + w, y0 + w],
                         [x0, y0 + w], [x0, y0]])
        elif k == 2:
            g = Polygon([[x0, y0], [x0 + 3, y0], [x0 + 1.5, y0 + 3], [x0, y0]])
        elif k == 3:
            g = LineString([(x0, y0), (x0 + 2.5, y0 + 1.2)])
        else:
            g = None
        rows.append([
            int(BASE + rng.integers(0, 15 * 86400_000)),
            None if i % null_every == 0 else f"c{rng.integers(0, 6)}",
            None if i % null_every == 1 else float(np.round(rng.uniform(0, 9), 2)),
            g,
        ])
    return rows


def _stores(n=8000, seed=51, batches=2, devices=None):
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(
        executor=TpuScanExecutor(default_mesh(jax.devices()[:devices]))
    )
    rows = _rows(n, seed)
    for s in (host, tpu):
        s.create_schema(parse_spec("e", SPEC))
        for b in range(batches):
            sl = slice(b * n // batches, (b + 1) * n // batches)
            with s.writer("e") as w:
                for i in range(sl.start, sl.stop):
                    w.write(rows[i], fid=f"e{i}")
    return host, tpu


def _parity(host, tpu, cqls):
    got = tpu.query_many("e", cqls)
    for cql, res in zip(cqls, got):
        want = sorted(map(str, host.query("e", cql).fids))
        assert sorted(map(str, res.fids)) == want, cql
    return got


def _plane_loaded(tpu, attr):
    loaded = False
    for idx in ("xz2", "xz3"):
        table = tpu._tables["e"].get(idx)
        if table is None:
            continue
        dev = tpu.executor.device_index(table)
        for s in dev.segments:
            if getattr(s, "_attr_codes", {}).get(attr) is not None:
                loaded = True
    assert loaded, f"xz attr plane never loaded for {attr}"


BOX = "bbox(geom, -40, -30, 30, 25)"
BOX2 = "bbox(geom, -80, -50, 60, 45)"
WIN = "dtg DURING 2026-01-02T00:00:00Z/2026-01-10T00:00:00Z"


@pytest.mark.parametrize("devices", [1, 8])
def test_xz_attr_member_parity(devices):
    host, tpu = _stores(devices=devices)
    got = _parity(host, tpu, [
        f"kind = 'c2' AND {BOX}",
        f"kind = 'c4' AND {BOX2}",
        f"kind IN ('c0', 'c3', 'zz') AND {BOX}",
        f"kind = 'absent' AND {BOX2}",
    ])
    assert any(len(r.fids) > 0 for r in got[:3])
    _plane_loaded(tpu, "kind")


@pytest.mark.parametrize("devices", [1, 8])
def test_xz_attr_range_parity(devices):
    host, tpu = _stores(devices=devices)
    _parity(host, tpu, [
        f"size > 2.5 AND size <= 7.0 AND {BOX}",
        f"size BETWEEN 1.0 AND 4.0 AND {BOX2}",
        f"kind >= 'c2' AND kind < 'c5' AND {BOX}",
        f"kind LIKE 'c%' AND {BOX2}",
        f"size IS NULL AND {BOX}",
        f"kind IS NOT NULL AND kind <= 'c1' AND {BOX2}",
        f"size > 8.0 AND size < 1.0 AND {BOX}",  # empty interval
    ])
    _plane_loaded(tpu, "size")
    _plane_loaded(tpu, "kind")


def test_xz3_attr_with_window():
    """xz3 edition: spatial AND window AND attr all decided on device."""
    host, tpu = _stores()
    _parity(host, tpu, [
        f"kind = 'c1' AND {BOX} AND {WIN}",
        f"size < 5.0 AND {BOX2} AND {WIN}",
        f"kind IN ('c2', 'c5') AND {BOX} AND {WIN}",
        f"size >= 3.0 AND {BOX2} AND {WIN}",
    ])
    _plane_loaded(tpu, "kind")
    _plane_loaded(tpu, "size")


def test_xz_attr_shard_extract():
    """Per-shard dual-window extraction with the attr plane fused in."""
    host, tpu = _stores()
    # every (table, has_time, attr, kind) group needs >= 2 members or
    # the lone query routes to the host single path
    _parity(host, tpu, [
        f"kind = 'c3' AND {BOX2}",
        f"kind = 'c1' AND {BOX}",
        f"size BETWEEN 2.0 AND 6.0 AND {BOX2}",
        f"size > 1.0 AND size < 8.0 AND {BOX}",
        f"kind = 'c0' AND {BOX} AND {WIN}",
        f"kind = 'c5' AND {BOX2} AND {WIN}",
    ])
    _plane_loaded(tpu, "kind")
    _plane_loaded(tpu, "size")


def test_xz_attr_nongeometry_predicates_on_intersects():
    """Non-rect INTERSECTS query geometry + attr preds: decided stays
    empty (rect flag off) and the whole ring takes the host geometry
    test — attr already excluded non-matching rows from the ring."""
    host, tpu = _stores(n=5000)
    tri = "POLYGON ((-35 -25, 25 -20, 0 22, -35 -25))"
    tri2 = "POLYGON ((-60 -35, 10 -40, -20 15, -60 -35))"
    got = _parity(host, tpu, [
        f"kind = 'c1' AND intersects(geom, {tri})",
        f"kind = 'c4' AND intersects(geom, {tri2})",
        f"size > 3.0 AND intersects(geom, {tri})",
        f"size < 6.5 AND intersects(geom, {tri2})",
    ])
    assert any(len(r.fids) > 0 for r in got)
    _plane_loaded(tpu, "kind")
    _plane_loaded(tpu, "size")


def test_xz_attr_after_delete_and_fallbacks():
    host, tpu = _stores(n=5000)
    for s in (host, tpu):
        s.delete_features("e", [f"e{i}" for i in range(0, 5000, 9)])
    _parity(host, tpu, [
        f"kind = 'c2' AND {BOX2}",
        f"size > 4.0 AND {BOX2}",
        # ineligible shapes stay exact on the host path
        f"kind = 'c1' AND size > 2.0 AND {BOX2}",  # two attrs
        f"kind LIKE '%2' AND {BOX2}",  # non-prefix LIKE
    ])


# -- polygon ray-cast edition (point schemas) --------------------------------

from geomesa_tpu.geom.base import Point  # noqa: E402

PT_SPEC = "dtg:Date,kind:String,score:Int,*geom:Point:srid=4326"


def _point_stores(n=20_000, seed=61, devices=None):
    rng = np.random.default_rng(seed)
    # rows precomputed ONCE — generating inside the store loop would give
    # host and tpu different data (the rng state advances)
    rows = [
        [
            int(BASE + rng.integers(0, 15 * 86400_000)),
            None if i % 17 == 0 else f"k{rng.integers(0, 5)}",
            None if i % 19 == 0 else int(rng.integers(0, 40)),
            Point(float(rng.uniform(-170, 170)),
                  float(rng.uniform(-80, 80))),
        ]
        for i in range(n)
    ]
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(
        executor=TpuScanExecutor(default_mesh(jax.devices()[:devices]))
    )
    for s in (host, tpu):
        s.create_schema(parse_spec("p", PT_SPEC))
        with s.writer("p") as w:
            for i, r in enumerate(rows):
                w.write(r, fid=f"p{i}")
    return host, tpu


def _pparity(host, tpu, cqls):
    got = tpu.query_many("p", cqls)
    for cql, res in zip(cqls, got):
        want = sorted(map(str, host.query("p", cql).fids))
        assert sorted(map(str, res.fids)) == want, cql
    return got


TRI = "POLYGON ((-40 -40, 30 -35, 10 30, -35 20, -40 -40))"
TRI2 = "POLYGON ((-15 -50, 50 -40, 25 15, -15 -50))"


@pytest.mark.parametrize("devices", [1, 8])
def test_poly_attr_member_and_range(devices):
    """Attr plane fused into the banded ray-cast batches: the band ring
    only carries attr-passing rows; decided rows are final for the full
    polygon-AND-attr predicate."""
    host, tpu = _point_stores(devices=devices)
    got = _pparity(host, tpu, [
        f"kind = 'k1' AND intersects(geom, {TRI})",
        f"kind = 'k3' AND intersects(geom, {TRI2})",
        f"score > 10 AND score <= 30 AND intersects(geom, {TRI})",
        f"score BETWEEN 5 AND 20 AND intersects(geom, {TRI2})",
    ])
    assert any(len(r.fids) > 0 for r in got)
    table = tpu._tables["p"]["z2"]
    dev = tpu.executor.device_index(table)
    assert all(
        getattr(s, "_attr_codes", {}).get("kind") is not None
        for s in dev.segments
    )


def test_poly_attr_with_window_and_shard_extract():
    host, tpu = _point_stores()
    _pparity(host, tpu, [
        f"kind = 'k0' AND intersects(geom, {TRI}) AND "
        "dtg DURING 2026-01-02T00:00:00Z/2026-01-09T00:00:00Z",
        f"kind = 'k2' AND intersects(geom, {TRI2}) AND "
        "dtg DURING 2026-01-03T00:00:00Z/2026-01-11T00:00:00Z",
        f"score < 25 AND intersects(geom, {TRI}) AND "
        "dtg DURING 2026-01-02T00:00:00Z/2026-01-09T00:00:00Z",
        f"score >= 8 AND intersects(geom, {TRI2}) AND "
        "dtg DURING 2026-01-03T00:00:00Z/2026-01-11T00:00:00Z",
    ])


def test_poly_attr_after_delete():
    host, tpu = _point_stores(n=8000)
    for s in (host, tpu):
        s.delete_features("p", [f"p{i}" for i in range(0, 8000, 11)])
    _pparity(host, tpu, [
        f"kind = 'k2' AND intersects(geom, {TRI})",
        f"kind = 'k4' AND intersects(geom, {TRI2})",
        f"score IS NULL AND intersects(geom, {TRI})",
        f"kind IS NOT NULL AND intersects(geom, {TRI2})",
    ])
