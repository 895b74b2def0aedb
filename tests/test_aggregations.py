"""Aggregation push-down tests: density / stats / bin hints, device vs host.

Mirrors the reference's aggregating-iterator tests (DensityIteratorTest,
StatsIteratorTest, BinAggregatingIteratorTest shapes): same store contents,
aggregation via hints, host reducer is the oracle for the device fused path.
"""

import numpy as np
import pytest

from geomesa_tpu.geom.base import Point
from geomesa_tpu.index.planner import Query
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore

SPEC = "actor:String,val:Double,dtg:Date,*geom:Point:srid=4326"
CQL = "bbox(geom, -20, -20, 20, 20) AND dtg DURING 2026-01-02T00:00:00Z/2026-01-12T00:00:00Z"


@pytest.fixture(autouse=True)
def _force_device_density(monkeypatch):
    # 'auto' routes density to the host seek path on the CPU backend;
    # these tests exercise the DEVICE fused kernel, so force it on
    monkeypatch.setenv("GEOMESA_DENSITY_DEVICE", "1")


def _fill(store, n=5000, seed=11):
    rng = np.random.default_rng(seed)
    ft = parse_spec("agg", SPEC)
    store.create_schema(ft)
    base = np.datetime64("2026-01-01T00:00:00", "ms").astype("int64")
    cols = {
        "__fid__": np.array([f"f{i}" for i in range(n)], dtype=object),
        "geom__x": rng.uniform(-50, 50, n),
        "geom__y": rng.uniform(-50, 50, n),
        "dtg": base + rng.integers(0, 20 * 86400, n) * 1000,  # whole seconds
        "actor": np.array([["USA", "FRA", "CHN"][i % 3] for i in range(n)], dtype=object),
        "val": rng.uniform(0, 10, n),
    }
    store._insert_columns(ft, cols)
    return ft, cols


@pytest.fixture(scope="module")
def host_store():
    s = TpuDataStore(executor=HostScanExecutor())
    _fill(s)
    return s


@pytest.fixture(scope="module")
def tpu_store():
    s = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
    _fill(s)
    return s


DENSITY = {"envelope": (-20.0, -20.0, 20.0, 20.0), "width": 32, "height": 16}


def test_density_host_matches_brute(host_store):
    q = Query.cql(CQL, hints={"density": dict(DENSITY)})
    res = host_store.query("agg", q)
    grid = res.aggregate["density"]
    assert grid.shape == (16, 32)
    plain = host_store.query("agg", CQL)
    assert grid.sum() == len(plain)


def test_density_device_matches_host(host_store, tpu_store):
    q = Query.cql(CQL, hints={"density": dict(DENSITY)})
    want = host_store.query("agg", q).aggregate["density"]
    got = tpu_store.query("agg", q).aggregate["density"]
    np.testing.assert_allclose(got, want)


def test_density_device_fused_path_taken(tpu_store):
    plan = tpu_store._plan_cached("agg", Query.cql(CQL))
    table = tpu_store._tables["agg"][plan.index.name]
    grid = tpu_store.executor.density_scan(table, plan, DENSITY)
    assert grid is not None


def test_density_weighted(host_store):
    q = Query.cql(CQL, hints={"density": {**DENSITY, "weight": "val"}})
    res = host_store.query("agg", q)
    plain = host_store.query("agg", CQL)
    want = np.asarray(plain.columns["val"]).sum()
    np.testing.assert_allclose(res.aggregate["density"].sum(), want)


def test_stats_hint(host_store):
    q = Query.cql(CQL, hints={"stats": "Count();MinMax(val)"})
    res = host_store.query("agg", q)
    stat = res.aggregate["stats"]
    plain = host_store.query("agg", CQL)
    assert stat.stats[0].count == len(plain)
    vals = np.asarray(plain.columns["val"])
    assert stat.stats[1].min == vals.min()
    assert stat.stats[1].max == vals.max()


def test_bin_hint(host_store):
    q = Query.cql(CQL, hints={"bin": {"track": "actor", "sort": True}})
    res = host_store.query("agg", q)
    recs = res.aggregate["bin"]
    plain = host_store.query("agg", CQL)
    assert len(recs) == len(plain)
    assert recs.dtype.itemsize == 16
    assert (np.diff(recs["dtg"]) >= 0).all()
    # 3 distinct track ids
    assert len(np.unique(recs["track"])) == 3
    # lat/lon round-trip within f32
    assert np.abs(recs["lon"]).max() <= 20.0 + 1e-3


def test_aggregation_parity_host_vs_tpu_bin(host_store, tpu_store):
    q = Query.cql(CQL, hints={"bin": {"track": "actor"}})
    a = host_store.query("agg", q).aggregate["bin"]
    b = tpu_store.query("agg", q).aggregate["bin"]
    a = np.sort(a, order=["track", "dtg", "lon"])
    b = np.sort(b, order=["track", "dtg", "lon"])
    np.testing.assert_array_equal(a, b)


def test_empty_plan_with_aggregation_returns_zero_grid(host_store):
    q = Query.cql(
        "bbox(geom, 100, 100, 101, 101) AND bbox(geom, -50, -50, -40, -40)",
        hints={"density": dict(DENSITY)},
    )
    res = host_store.query("agg", q)
    assert res.aggregate["density"].sum() == 0


def test_duplicate_fid_rows_counted_consistently(host_store, tpu_store):
    # re-inserting a fid leaves two live rows (reference point indices do
    # the same: only XZ dedupes, QueryPlanner.scala:83-85); query and fused
    # density must agree with each other
    base = np.datetime64("2026-01-05T00:00:00", "ms").astype("int64")
    # keep both module fixtures in the same state for later parity tests
    for store in (host_store, tpu_store):
        ft = store.get_schema("agg")
        store._insert_columns(ft, {
            "__fid__": np.array(["f0"], dtype=object),
            "geom__x": np.array([0.0]), "geom__y": np.array([0.0]),
            "dtg": np.array([base]),
            "actor": np.array(["USA"], dtype=object),
            "val": np.array([1.0]),
        })
    q = Query.cql(CQL, hints={"density": dict(DENSITY)})
    grid = tpu_store.query("agg", q).aggregate["density"]
    assert grid.sum() == len(tpu_store.query("agg", CQL))
    assert grid.sum() == len(host_store.query("agg", CQL))


def test_minmax_geom_gives_envelope(host_store):
    q = Query.cql(CQL, hints={"stats": "MinMax(geom)"})
    st = host_store.query("agg", q).aggregate["stats"]
    b = st.bounds
    assert b is not None
    assert -20 <= b[0] <= b[2] <= 20 and -20 <= b[1] <= b[3] <= 20


def test_device_density_exact_exclusive_bounds(host_store, tpu_store):
    # AFTER creates an exclusive lower bound at ms precision
    cql = "bbox(geom, -20, -20, 20, 20) AND dtg AFTER 2026-01-02T00:00:00.500Z AND dtg BEFORE 2026-01-12T00:00:00Z"
    q = Query.cql(cql, hints={"density": dict(DENSITY)})
    want = host_store.query("agg", q).aggregate["density"]
    got = tpu_store.query("agg", q).aggregate["density"]
    np.testing.assert_allclose(got, want)


def _fill_boundary(store, seed=23):
    """Adversarial density data: points engineered within f32 error of
    density-cell boundaries and query-box edges — the rows the dual
    edition must defer to host f64 certification."""
    rng = np.random.default_rng(seed)
    ft = parse_spec("aggb", SPEC)
    store.create_schema(ft)
    env = BOUNDARY_DENSITY["envelope"]
    w, h = BOUNDARY_DENSITY["width"], BOUNDARY_DENSITY["height"]
    dx = (env[2] - env[0]) / w
    dy = (env[3] - env[1]) / h
    n_uniform, n_edge = 2000, 2000
    xs = [rng.uniform(env[0], env[2], n_uniform)]
    ys = [rng.uniform(env[1], env[3], n_uniform)]
    # straddle cell boundaries at f32 scale (offsets far below f32 ulp
    # of |x| ~ 1e-6, so f32 rounding can move points across)
    bx = env[0] + rng.integers(0, w + 1, n_edge) * dx
    by = env[1] + rng.integers(0, h + 1, n_edge) * dy
    off = rng.uniform(-1e-9, 1e-9, n_edge)
    xs.append(bx + off)
    ys.append(by + rng.uniform(-1e-9, 1e-9, n_edge))
    # straddle the query box's edges too
    for edge_x in (BOUNDARY_BOX[0], BOUNDARY_BOX[2]):
        xs.append(np.full(200, edge_x) + rng.uniform(-1e-9, 1e-9, 200))
        ys.append(rng.uniform(env[1], env[3], 200))
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    n = len(x)
    base = np.datetime64("2026-01-01T00:00:00", "ms").astype("int64")
    cols = {
        "__fid__": np.array([f"b{i}" for i in range(n)], dtype=object),
        "geom__x": x,
        "geom__y": y,
        "dtg": base + rng.integers(0, 20 * 86400, n) * 1000,
        "actor": np.array(["USA"] * n, dtype=object),
        "val": rng.uniform(0, 10, n),
    }
    store._insert_columns(ft, cols)
    return ft, cols


# awkward bounds: dx = 2.1/21 = 0.1 is not f32-representable, so cell
# boundaries land between f32 values and the band is exercised for real
BOUNDARY_DENSITY = {"envelope": (-1.05, -0.55, 1.05, 0.55), "width": 21, "height": 11}
BOUNDARY_BOX = (-0.7, -0.35, 0.7, 0.35)
BOUNDARY_CQL = (
    f"bbox(geom, {BOUNDARY_BOX[0]}, {BOUNDARY_BOX[1]}, "
    f"{BOUNDARY_BOX[2]}, {BOUNDARY_BOX[3]}) AND "
    "dtg DURING 2026-01-02T00:00:00Z/2026-01-12T00:00:00Z"
)


def test_density_device_grid_exact_at_boundaries():
    """The dual edition's device grid must equal the host oracle EXACTLY
    (zero L1) on data engineered to straddle cell boundaries and box
    edges at f32 scale — the band rows are host-certified from the f64
    columns, so f32 rounding cannot show through."""
    host = TpuDataStore(executor=HostScanExecutor())
    _fill_boundary(host)
    tpu = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
    _fill_boundary(tpu)
    q = Query.cql(BOUNDARY_CQL, hints={"density": dict(BOUNDARY_DENSITY)})
    want = host.query("aggb", q).aggregate["density"]
    res = tpu.query("aggb", q)
    assert res.plan.scan_path == "device-density"
    np.testing.assert_array_equal(res.aggregate["density"], want)
    assert want.sum() > 0
    # the z2 (no-time) dual leg: bbox-only query through the same band
    bbox_only = BOUNDARY_CQL.split(" AND ")[0]
    q2 = Query.cql(bbox_only, hints={"density": dict(BOUNDARY_DENSITY)})
    want2 = host.query("aggb", q2).aggregate["density"]
    res2 = tpu.query("aggb", q2)
    assert res2.plan.scan_path == "device-density"
    np.testing.assert_array_equal(res2.aggregate["density"], want2)


def test_density_band_actually_engaged():
    """Witness that the adversarial data produces a non-empty band (the
    exactness test above must not pass vacuously)."""
    import jax.numpy as jnp

    from geomesa_tpu.ops.aggregations import density_band

    rng = np.random.default_rng(23)
    env = np.asarray(BOUNDARY_DENSITY["envelope"], dtype=np.float32)
    w, h = BOUNDARY_DENSITY["width"], BOUNDARY_DENSITY["height"]
    dx = (env[2] - env[0]) / w
    bx = env[0] + rng.integers(0, w + 1, 500).astype(np.float32) * np.float32(dx)
    x = jnp.asarray(bx)
    y = jnp.zeros(500, jnp.float32)
    boxes = jnp.asarray([BOUNDARY_BOX], dtype=jnp.float32)
    band, near = density_band(x, y, jnp.asarray(env), w, h, boxes)
    assert int(band.sum()) > 0
    assert int(near.sum()) > 0


def test_density_band_overflow_falls_back_to_host(monkeypatch):
    """A band larger than the per-shard index budget keeps the device
    grid and fetches the band whole as a bitmap: the grid is exact and the
    device still answers (no host fallback, no truncated band)."""
    from geomesa_tpu.ops import aggregations as agg

    # the cap is read inside density_scan from the aggregations module
    # (one read keys the compiled buffer size AND the overflow check)
    monkeypatch.setattr(agg, "DENSITY_BAND_CAP", 4)

    host = TpuDataStore(executor=HostScanExecutor())
    _fill_boundary(host)
    tpu = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
    _fill_boundary(tpu)
    q = Query.cql(BOUNDARY_CQL, hints={"density": dict(BOUNDARY_DENSITY)})
    want = host.query("aggb", q).aggregate["density"]
    res = tpu.query("aggb", q)
    np.testing.assert_array_equal(res.aggregate["density"], want)
    assert res.plan.scan_path == "device-density"


@pytest.mark.parametrize("cap", [1, 16, 8192])
@pytest.mark.parametrize("edition", ["time", "no_time"])
def test_density_band_bitmap_grid_exact_across_shards(monkeypatch, cap, edition):
    """On the 8-device CPU mesh the band straddles every shard: whichever
    layout the band comes back in (the bitmap below the band's size, the
    indices at 8192), the grid equals the host's exactly."""
    from geomesa_tpu.ops import aggregations as agg
    from geomesa_tpu.utils import trace

    monkeypatch.setattr(agg, "DENSITY_BAND_CAP", cap)
    cql = BOUNDARY_CQL if edition == "time" else BOUNDARY_CQL.split(" AND ")[0]
    q = Query.cql(cql, hints={"density": dict(BOUNDARY_DENSITY)})
    host = TpuDataStore(executor=HostScanExecutor())
    _fill_boundary(host)
    mesh = default_mesh()
    assert mesh.devices.size == 8
    tpu = TpuDataStore(executor=TpuScanExecutor(mesh))
    _fill_boundary(tpu)
    with trace.exporting(trace.InMemoryTraceExporter()) as ring:
        res = tpu.query("aggb", q)
    np.testing.assert_array_equal(
        res.aggregate["density"], host.query("aggb", q).aggregate["density"]
    )
    assert res.plan.scan_path == "device-density"
    (sp,) = ring.traces[-1].find("density")
    assert sp.attributes["band"] == ("indices" if cap == 8192 else "bitmap")
    assert sp.attributes["band_rows"] > 8 * cap or cap == 8192


def _band_inputs(n, seed=3):
    """Boundary-heavy rows for the band programs: half on the cell edges
    of BOUNDARY_DENSITY's grid, a time column of two bins, a ragged tail
    of invalid rows."""
    rng = np.random.default_rng(seed)
    env = np.asarray(BOUNDARY_DENSITY["envelope"], np.float32)
    w, h = BOUNDARY_DENSITY["width"], BOUNDARY_DENSITY["height"]
    x = rng.uniform(env[0], env[2], n).astype(np.float32)
    y = rng.uniform(env[1], env[3], n).astype(np.float32)
    edge = rng.random(n) < 0.5
    x[edge] = env[0] + rng.integers(0, w + 1, edge.sum()) * np.float32((env[2] - env[0]) / w)
    bins = rng.integers(0, 2, n).astype(np.int32)
    offs = rng.integers(0, 1000, n).astype(np.int32)
    valid = np.arange(n) < n - n // 7
    boxes = np.asarray([BOUNDARY_BOX], np.float32)
    windows = np.asarray([[1, 100, 800]], np.int32)
    return x, y, bins, offs, valid, boxes, windows, env


def _reference_candidates(args, with_time):
    import jax.numpy as jnp

    from geomesa_tpu.ops.aggregations import density_band
    from geomesa_tpu.ops.filters import temporal_mask

    x, y, bins, offs, valid, boxes, windows, env = (jnp.asarray(a) for a in args)
    w, h = BOUNDARY_DENSITY["width"], BOUNDARY_DENSITY["height"]
    _, near = density_band(x, y, env, w, h, boxes)
    m = near & valid
    if with_time:
        m = m & temporal_mask(bins, offs, windows)
    return np.flatnonzero(np.asarray(m))


def _band_call(fns, args, with_time, mesh):
    from geomesa_tpu.parallel.mesh import replicate, shard_array

    x, y, bins, offs, valid, boxes, windows, env = args
    rows = [shard_array(mesh, a) for a in (x, y, bins, offs, valid)]
    small = [replicate(mesh, a) for a in (boxes, windows, env)]
    if with_time:
        return fns[0](*rows, *small)
    return fns[1](rows[0], rows[1], rows[4], small[0], small[2])


@pytest.mark.parametrize("n_devices, rows", [(1, 4096), (1, 64), (8, 8192), (8, 512)])
@pytest.mark.parametrize("with_time", [True, False])
def test_band_bitmap_decodes_to_the_reference_mask(n_devices, rows, with_time):
    """The packed bitmap, decoded as the executor decodes it, is exactly
    the candidate mask the dual pass compacts: whole (8, 128) row tiles
    and shards narrower than one."""
    import jax

    from geomesa_tpu.ops.aggregations import make_sharded_band_bitmap, row_tile_rows
    from geomesa_tpu.parallel.executor import _decode_bitmap_rows

    mesh = default_mesh(jax.devices()[:n_devices])
    args = _band_inputs(rows)
    want = _reference_candidates(args, with_time)
    assert 0 < want.size < rows
    fns = make_sharded_band_bitmap(mesh, BOUNDARY_DENSITY["width"], BOUNDARY_DENSITY["height"])
    packed = np.asarray(_band_call(fns, args, with_time, mesh))
    assert packed.dtype == np.uint8 and packed.size == rows // 8
    pos = _decode_bitmap_rows(packed, 0, want.size)
    np.testing.assert_array_equal(row_tile_rows(pos, rows // n_devices), want)


@pytest.mark.parametrize("local_n, shards", [
    (8, 1), (24, 3), (64, 2), (1024, 1), (1536, 2), (4096, 4), (8192, 1),
])
def test_row_tile_pack_round_trips(local_n, shards):
    """pack_row_tiles per shard, the shards' bytes concatenated, decoded
    as the executor decodes them: the set rows, in order, for every tile
    width the shard size allows (gcd(128, local_n / 8) lanes)."""
    import jax
    import jax.numpy as jnp

    from geomesa_tpu.ops.aggregations import pack_row_tiles, row_tile_rows
    from geomesa_tpu.parallel.executor import _decode_bitmap_rows

    m = np.random.default_rng(local_n + shards).random(local_n * shards) < 0.3
    pack = jax.jit(pack_row_tiles)
    packed = np.concatenate([
        np.asarray(pack(jnp.asarray(part))) for part in np.split(m, shards)
    ])
    assert packed.size == m.size // 8
    want = np.flatnonzero(m)
    got = row_tile_rows(_decode_bitmap_rows(packed, 0, want.size), local_n)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cap", [16, 8192])
@pytest.mark.parametrize("with_time", [True, False])
def test_band_bitmap_rows_equal_the_index_set(cap, with_time):
    """Where the band fits the cap, the bitmap's rows are the dual pass's
    band indices; where it does not, the indices are its first ``cap``
    rows a shard and the bitmap holds every counted row."""
    import jax

    from geomesa_tpu.ops.aggregations import (
        make_sharded_band_bitmap,
        make_sharded_density_dual,
        row_tile_rows,
    )
    from geomesa_tpu.parallel.executor import _decode_bitmap_rows

    mesh = default_mesh(jax.devices()[:8])
    rows = 8192
    w, h = BOUNDARY_DENSITY["width"], BOUNDARY_DENSITY["height"]
    args = _band_inputs(rows, seed=9)
    _, gidx, cnt = _band_call(make_sharded_density_dual(mesh, w, h, "xla", band_cap=cap),
                              args, with_time, mesh)
    gidx, cnt = np.asarray(gidx), np.asarray(cnt)
    pos_bits = np.asarray(_band_call(make_sharded_band_bitmap(mesh, w, h), args, with_time, mesh))
    got = row_tile_rows(_decode_bitmap_rows(pos_bits, 0, int(cnt.sum())), rows // 8)
    assert got.size == int(cnt.sum())
    idx = gidx[gidx >= 0]
    if int(cnt.max()) <= cap:
        np.testing.assert_array_equal(got, np.sort(idx))
    else:
        assert set(idx.tolist()) < set(got.tolist())
