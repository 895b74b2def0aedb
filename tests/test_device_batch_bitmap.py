"""Span-framed bitmap batches, the one wire format of the batched exact
scans. The device masks each query, frames the span between its first
and last hit and bit-packs that window; the host decodes the rows. A
single-device mesh frames the whole segment (_exact_bitmap_batch_fn and
its xz/poly editions); a multi-device mesh frames each shard's local
rows (_exact_shard_bitmap_batch_fn, _dual_shard_bitmap_batch_fn). The
mesh alone picks between them.
"""

import jax
import numpy as np
import pytest

from geomesa_tpu.geom.base import LineString, Polygon
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.parallel import executor as ex
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore
from geomesa_tpu.utils import trace

SPEC = "dtg:Date,*geom:Point:srid=4326"
BASE = int(np.datetime64("2026-01-01T00:00:00", "ms").astype("int64"))


@pytest.fixture(autouse=True)
def _force_batch(monkeypatch):
    monkeypatch.setenv("GEOMESA_EXACT_DEVICE", "1")
    monkeypatch.setenv("GEOMESA_DEVBATCH", "1")
    monkeypatch.setenv("GEOMESA_SEEK", "0")


def _tpu(devices):
    return TpuDataStore(
        executor=TpuScanExecutor(default_mesh(jax.devices()[:devices]))
    )


def _stores(x, y, t, devices=1):
    """Columnar bulk insert of a point schema into a host reference store
    and a device store on the first ``devices`` devices."""
    n = len(x)
    fids = np.array([f"f{i}" for i in range(n)], dtype=object)
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = _tpu(devices)
    for s in (host, tpu):
        s.create_schema(parse_spec("t", SPEC))
        with s.writer("t") as w:
            w.write_columns(
                {"__fid__": fids, "dtg": np.asarray(t, np.int64),
                 "geom__x": np.asarray(x, float),
                 "geom__y": np.asarray(y, float)}
            )
    return host, tpu


def _extent_stores(n, seed, devices=1):
    """Axis rects, triangles, lines and null geometries: decided rows and
    the boundary ring that takes the host's per-geometry test."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
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
        rows.append((int(BASE + int(rng.integers(0, 10 * 86400_000))), g))
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = _tpu(devices)
    for s in (host, tpu):
        s.create_schema(parse_spec("t", "dtg:Date,*geom:Geometry:srid=4326"))
        with s.writer("t") as w:
            for i, (t, g) in enumerate(rows):
                w.write([t, g], fid=f"e{i}")
    return host, tpu


def _fids(res):
    return sorted(map(str, res.fids))


def _parity(host, tpu, cqls):
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql


def _points(n=20_000, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-70, 70, n)
    y = rng.uniform(-70, 70, n)
    t = BASE + rng.integers(0, 10 * 86400_000, n)
    return x, y, t


WINDOW = "dtg DURING 2026-01-02T00:00:00Z/2026-01-08T00:00:00Z"
TRI = "POLYGON ((-40 -40, 30 -35, 10 30, -35 20, -40 -40))"
TRI2 = "POLYGON ((-15 -50, 50 -40, 25 15, -15 -50))"

# family -> (store factory, queries, local kernel factory, proto by mesh)
FAMILIES = {
    "z2": (
        lambda devices: _stores(*_points(), devices=devices),
        ["bbox(geom, -30, -20, 20, 25)", "bbox(geom, 0, 0, 60, 50)",
         "bbox(geom, -65, -65, -40, 10)"],
        "_exact_bitmap_batch_fn",
        {1: "bitmap", 8: "bitmap_shard"},
    ),
    "z3": (
        lambda devices: _stores(*_points(), devices=devices),
        [f"bbox(geom, -30, -20, 20, 25) AND {WINDOW}",
         f"bbox(geom, 0, 0, 60, 50) AND {WINDOW}"],
        "_exact_bitmap_batch_fn",
        {1: "bitmap", 8: "bitmap_shard"},
    ),
    "xz2": (
        lambda devices: _extent_stores(3000, 33, devices=devices),
        ["bbox(geom, -60, -40, 10, 20)", "bbox(geom, -100, -60, 80, 50)",
         "bbox(geom, 20, -20, 100, 45)"],
        "_xz_bitmap_batch_fn",
        {1: "bitmap_xz", 8: "bitmap_shard_xz"},
    ),
    "poly": (
        lambda devices: _stores(*_points(), devices=devices),
        [f"intersects(geom, {TRI})", f"intersects(geom, {TRI2})"],
        "_poly_bitmap_batch_fn",
        {1: "bitmap_poly", 8: "bitmap_shard_poly"},
    ),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_bitmap_batch_parity(family, monkeypatch):
    """One device: every family's batch runs the whole-segment bitmap
    kernel and answers exactly as the host scan does."""
    build, cqls, local_fn, _protos = FAMILIES[family]
    host, tpu = build(1)
    calls = {"n": 0}
    orig = getattr(ex, local_fn)

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(ex, local_fn, counting)
    _parity(host, tpu, cqls)
    assert calls["n"] >= 1  # the batched device path ran
    _parity(host, tpu, cqls)  # second stream rides the learned span


@pytest.mark.parametrize("family", list(FAMILIES))
@pytest.mark.parametrize("devices", [1, 8])
def test_shared_fetch_span_names_the_mesh_wire_format(devices, family):
    """The ``device.fetch.shared`` span's ``proto`` names the one wire
    format the mesh picks: the whole-segment bitmap on one device, the
    per-shard bitmap on eight."""
    build, cqls, _local_fn, protos = FAMILIES[family]
    host, tpu = build(devices)
    with trace.exporting(trace.InMemoryTraceExporter()) as ring:
        _parity(host, tpu, cqls)
    kinds = {
        sp.attributes["proto"]
        for root in ring.traces
        for sp in root.find("device.fetch.shared")
    }
    assert kinds == {protos[devices]}, kinds


def test_bitmap_protocol_parity():
    rng = np.random.default_rng(8)
    n = 60_000
    x = rng.uniform(-60, 60, n)
    y = rng.uniform(-60, 60, n)
    t = BASE + rng.integers(0, 20 * 86400_000, n)
    host, tpu = _stores(x, y, t)
    cqls = []
    for _ in range(10):
        x0 = float(rng.uniform(-55, 20))
        y0 = float(rng.uniform(-55, 20))
        c = f"bbox(geom, {x0}, {y0}, {x0 + 25}, {y0 + 25})"
        if rng.integers(0, 2):
            d0 = int(rng.integers(1, 12))
            c += (f" AND dtg DURING 2026-01-{d0:02d}T00:00:00Z"
                  f"/2026-01-{d0 + 7:02d}T00:00:00Z")
        cqls.append(c)
    _parity(host, tpu, cqls)
    _parity(host, tpu, cqls)  # second stream rides the learned span window


def test_bitmap_span_overflow_falls_back():
    """A crushed span window far narrower than the queries' true spans
    (~100k rows at this n, verified offline) forces the single-query runs
    fallback; learning must then widen the window back out."""
    rng = np.random.default_rng(9)
    n = 150_000
    x = rng.uniform(-170, 170, n)
    y = rng.uniform(-80, 80, n)
    t = BASE + rng.integers(0, 86400_000, n)
    host, tpu = _stores(x, y, t)
    cqls = [f"bbox(geom, {x0}, -70, {x0+60}, 70)" for x0 in (-160, -80, 0, 80)]
    tpu.query_many("t", cqls)  # build mirror
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    for seg in dev.segments:
        seg._span_cap = 1 << 16  # far narrower than these wide queries
    _parity(host, tpu, cqls)
    # learning must widen the window back out after seeing the true spans
    assert all(s.span_cap() > (1 << 16) for s in dev.segments)


def test_bitmap_span_seeded_from_plan():
    """An UNLEARNED segment must not stream the full n_padded window on
    its first bitmap batch: the plan's range cover seeds a narrow span
    BEFORE dispatch (VERDICT r3 #2), and results stay parity-exact."""
    rng = np.random.default_rng(12)
    n = 150_000  # n_padded 262144 > the 65536 span floor
    x = rng.uniform(-170, -60, n)
    y = rng.uniform(-80, -10, n)
    # a tight cluster: hits live in a narrow z-span
    x[:2000] = rng.uniform(10, 11, 2000)
    y[:2000] = rng.uniform(10, 11, 2000)
    t = BASE + rng.integers(0, 86400_000, n)
    host, tpu = _stores(x, y, t)
    cqls = ["bbox(geom, 9, 9, 12, 12)", "bbox(geom, 9.5, 9.5, 11.5, 11.5)"]
    from geomesa_tpu.index.planner import Query

    plans = [tpu.planner("t").plan(Query.cql(c)) for c in cqls]
    tpu.query_many("t", ["bbox(geom, -100, -50, -99, -49)"])  # build mirror
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    for seg in dev.segments:
        seg._span_cap = 0  # force the unlearned state the seed targets
    tpu.executor._seed_spans(dev, plans)
    # seeded strictly below the full segment, before any device stream
    assert all(0 < s._span_cap < s.n_padded for s in dev.segments)
    _parity(host, tpu, cqls)  # the seeded window answers exactly
