"""Batched exact EXTENT device scans (xz2/xz3): dual span-framed bitmaps
(hit + decided planes) per query in one execution; the boundary ring takes the
host's per-geometry test. Results must match per-query host execution."""

import jax
import numpy as np
import pytest

from geomesa_tpu.geom.base import LineString, Point, Polygon
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.parallel import executor as ex
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore

BASE = int(np.datetime64("2026-01-01T00:00:00", "ms").astype("int64"))


@pytest.fixture(autouse=True)
def _force_batch(monkeypatch):
    monkeypatch.setenv("GEOMESA_DEVBATCH", "1")
    monkeypatch.setenv("GEOMESA_SEEK", "0")


def _pair(n=1200, seed=31, devices=None):
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(
        executor=TpuScanExecutor(default_mesh(jax.devices()[:devices]))
    )
    for s in (host, tpu):
        s.create_schema(parse_spec("e", "dtg:Date,*geom:Geometry:srid=4326"))
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        x0 = float(rng.uniform(-170, 160))
        y0 = float(rng.uniform(-80, 70))
        k = i % 5
        if k == 0:  # axis-aligned rect (isrect fast path)
            g = Polygon([[x0, y0], [x0 + 1, y0], [x0 + 1, y0 + 1],
                         [x0, y0 + 1], [x0, y0]])
        elif k == 1:  # triangle (ring rows)
            g = Polygon([[x0, y0], [x0 + 2, y0], [x0 + 1, y0 + 2], [x0, y0]])
        elif k == 2:
            g = LineString([(x0, y0), (x0 + 1.5, y0 + 0.7)])
        elif k == 3:
            g = Point(x0, y0)
        else:
            g = None
        t = None if i % 37 == 0 else int(BASE + int(rng.integers(0, 20 * 86400_000)))
        rows.append((t, g))
    for s in (host, tpu):
        with s.writer("e") as w:
            for i, (t, g) in enumerate(rows):
                w.write([t, g], fid=f"e{i}")
    return host, tpu


def _queries(rng, k, time_frac=0.0, poly_frac=0.3):
    out = []
    for _ in range(k):
        x0 = float(rng.uniform(-150, 100))
        y0 = float(rng.uniform(-70, 30))
        w_ = float(rng.uniform(5, 60))
        if rng.random() < poly_frac:
            spatial = (
                f"INTERSECTS(geom, POLYGON(({x0} {y0}, {x0 + w_} {y0}, "
                f"{x0 + w_ / 2} {y0 + w_}, {x0} {y0})))"
            )
        else:
            spatial = f"bbox(geom, {x0}, {y0}, {x0 + w_}, {y0 + w_})"
        if rng.random() < time_frac:
            d0 = int(rng.integers(1, 12))
            d1 = d0 + int(rng.integers(1, 7))
            spatial += (
                f" AND dtg DURING 2026-01-{d0:02d}T00:00:00Z"
                f"/2026-01-{d1:02d}T00:00:00Z"
            )
        out.append(spatial)
    return out


def _fids(res):
    return sorted(map(str, res.fids))


def test_xz2_batched_parity():
    host, tpu = _pair()
    rng = np.random.default_rng(1)
    cqls = _queries(rng, 10, time_frac=0.0)
    calls = {"n": 0}
    # spy both xz batch-kernel factories: the wire format follows the mesh
    # (whole-segment bitmap on one device, per-shard bitmap on several)
    spied = ("_xz_bitmap_batch_fn", "_dual_shard_bitmap_batch_fn")
    origs = {name: getattr(ex, name) for name in spied}

    def counting(orig):
        def wrapped(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)
        return wrapped

    for name in spied:
        setattr(ex, name, counting(origs[name]))
    try:
        got = tpu.query_many("e", cqls)
    finally:
        for name in spied:
            setattr(ex, name, origs[name])
    assert calls["n"] >= 1
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("e", cql)), cql


def test_xz3_batched_parity_with_time():
    host, tpu = _pair(seed=33)
    rng = np.random.default_rng(2)
    cqls = _queries(rng, 10, time_frac=1.0)
    got = tpu.query_many("e", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("e", cql)), cql


def test_mixed_point_and_extent_tables_absent():
    # bbox-only and time-bounded extent queries in one stream: xz2 and xz3
    # groups dispatch independently and must not cross-contaminate
    host, tpu = _pair(seed=35)
    rng = np.random.default_rng(3)
    cqls = _queries(rng, 4, time_frac=0.0) + _queries(rng, 4, time_frac=1.0)
    got = tpu.query_many("e", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("e", cql)), cql


def test_xz_batch_overflow_escalates():
    host, tpu = _pair(seed=37)
    rng = np.random.default_rng(4)
    cqls = _queries(rng, 6, time_frac=0.0, poly_frac=0.5)
    table = tpu._tables["e"]["xz2"]
    dev = tpu.executor.device_index(table)
    for seg in dev.segments:
        seg._rcap = 4
    got = tpu.query_many("e", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("e", cql)), cql


def test_xz_batch_respects_deletes():
    host, tpu = _pair(seed=39)
    rng = np.random.default_rng(5)
    doomed = [f"e{i}" for i in range(0, 1200, 9)]
    for s in (host, tpu):
        s.delete_features("e", doomed)
    cqls = _queries(rng, 8, time_frac=0.4)
    got = tpu.query_many("e", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("e", cql)), cql
        assert not set(map(str, res.fids)) & set(doomed)


def test_xz_bitmap_protocol_parity():
    """The span-framed dual-bitmap wire format must produce identical
    results, including the ring rows that take the host's per-geometry
    test, across two streams (second rides the learned span window)."""
    host, tpu = _pair(seed=41)
    rng = np.random.default_rng(6)
    cqls = _queries(rng, 5, time_frac=0.0, poly_frac=0.5) + _queries(rng, 4, time_frac=1.0)
    for _ in range(2):
        got = tpu.query_many("e", cqls)
        for cql, res in zip(cqls, got):
            assert _fids(res) == _fids(host.query("e", cql)), cql


def test_xz_bitmap_span_overflow_falls_back():
    """A crushed span window on one device (where the bitmap is framed
    over the whole segment) falls back per query, exactly."""
    host, tpu = _pair(seed=43, devices=1)
    rng = np.random.default_rng(7)
    cqls = _queries(rng, 5, time_frac=0.3)
    tpu.query_many("e", cqls)  # build mirror
    for fam in ("xz2", "xz3"):
        table = tpu._tables["e"].get(fam)
        if table is None:
            continue
        dev = tpu.executor.device_index(table)
        for seg in dev.segments:
            seg._span_cap = 8  # comically narrow: every query overflows
    got = tpu.query_many("e", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("e", cql)), cql
