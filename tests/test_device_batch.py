"""Batched exact device scans: query_many fuses many exact-shape plans
into ONE device execution per segment (dispatch_exact_batch). Results must
match per-query host execution bit-for-bit, and the batch must actually
take the fused path (one batch dispatch, not Q singles)."""

import numpy as np
import pytest

from geomesa_tpu.geom.base import Point
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.parallel import executor as ex
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore

SPEC = "name:String,age:Integer,dtg:Date,*geom:Point:srid=4326"
BASE = int(np.datetime64("2026-01-01T00:00:00", "ms").astype("int64"))


@pytest.fixture(autouse=True)
def _force_batch(monkeypatch):
    # auto gates decline on the CPU jax backend; tests force the batch
    # path and disable the host-seek chooser so batches actually dispatch
    monkeypatch.setenv("GEOMESA_EXACT_DEVICE", "1")
    monkeypatch.setenv("GEOMESA_DEVBATCH", "1")
    monkeypatch.setenv("GEOMESA_SEEK", "0")


def _pair(n=3000, seed=11):
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
    for s in (host, tpu):
        s.create_schema(parse_spec("t", SPEC))
    rng = np.random.default_rng(seed)
    rows = [
        [
            f"n{int(rng.integers(0, 7))}",
            int(rng.integers(0, 90)),
            int(BASE + int(rng.integers(0, 30 * 86400_000))),
            Point(float(rng.uniform(-60, 60)), float(rng.uniform(-60, 60))),
        ]
        for _ in range(n)
    ]
    for s in (host, tpu):
        with s.writer("t") as w:
            for i, row in enumerate(rows):
                w.write(list(row), fid=f"f{i}")
    return host, tpu


def _boxes(rng, k):
    out = []
    for _ in range(k):
        x0 = float(rng.uniform(-55, 40))
        y0 = float(rng.uniform(-55, 40))
        out.append((x0, y0, x0 + float(rng.uniform(1, 15)), y0 + float(rng.uniform(1, 15))))
    return out


def _cqls(rng, k, with_time=True):
    cqls = []
    for x0, y0, x1, y1 in _boxes(rng, k):
        c = f"bbox(geom, {x0}, {y0}, {x1}, {y1})"
        if with_time:
            d0 = int(rng.integers(0, 20))
            c += (
                f" AND dtg DURING 2026-01-{d0 + 1:02d}T00:00:00Z"
                f"/2026-01-{d0 + 9:02d}T12:00:00Z"
            )
        cqls.append(c)
    return cqls


def _fids(res):
    return sorted(res.fids)


def test_batched_query_many_parity_time():
    host, tpu = _pair()
    rng = np.random.default_rng(3)
    cqls = _cqls(rng, 12, with_time=True)
    calls = {"batch": 0}
    # spy on both batch-kernel factories: the wire format follows the mesh
    # (whole-segment bitmap on one device, per-shard bitmap on several)
    spied = ("_exact_bitmap_batch_fn", "_exact_shard_bitmap_batch_fn")
    origs = {name: getattr(ex, name) for name in spied}

    def counting(orig):
        def wrapped(*a, **k):
            calls["batch"] += 1
            return orig(*a, **k)
        return wrapped

    for name in spied:
        setattr(ex, name, counting(origs[name]))
    try:
        got = tpu.query_many("t", cqls)
    finally:
        for name in spied:
            setattr(ex, name, origs[name])
    assert calls["batch"] >= 1  # the fused path ran
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql


def test_batched_query_many_parity_bbox_only_z2():
    # bbox-only filters plan onto the z2 table -> the no-time batch branch
    host, tpu = _pair(seed=5)
    rng = np.random.default_rng(8)
    cqls = _cqls(rng, 9, with_time=False)
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql


def test_batch_matches_single_query_path():
    _, tpu = _pair(seed=9)
    rng = np.random.default_rng(1)
    cqls = _cqls(rng, 6)
    many = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, many):
        assert _fids(res) == _fids(tpu.query("t", cql))


def test_mixed_stream_batches_exact_and_dispatches_rest():
    # attribute-equality queries are not exact-shape; they must ride their
    # own path inside the same query_many call without disturbing batches
    host, tpu = _pair(seed=13)
    rng = np.random.default_rng(2)
    cqls = _cqls(rng, 5) + ["name = 'n3'", "age > 70"] + _cqls(rng, 4, False)
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql


def test_batch_overflow_escalates_per_query():
    host, tpu = _pair(seed=21)
    rng = np.random.default_rng(4)
    cqls = _cqls(rng, 5)
    # crush the run capacity so the shared batch buffer overflows and the
    # per-query escalation refetch path runs
    table = tpu._tables["t"]["z3"]
    dev = tpu.executor.device_index(table)
    for seg in dev.segments:
        seg._rcap = 4
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql


def test_batch_respects_deletes():
    host, tpu = _pair(seed=17)
    rng = np.random.default_rng(6)
    doomed = [f"f{i}" for i in range(0, 3000, 7)]
    for s in (host, tpu):
        s.delete_features("t", doomed)
    cqls = _cqls(rng, 6)
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql
        assert not set(res.fids) & set(doomed)


def test_chunking_past_batch_max():
    host, tpu = _pair(n=1200, seed=23)
    rng = np.random.default_rng(7)
    saved = TpuScanExecutor.BATCH_MAX
    TpuScanExecutor.BATCH_MAX = 4  # force multiple chunks
    try:
        cqls = _cqls(rng, 11)
        got = tpu.query_many("t", cqls)
    finally:
        TpuScanExecutor.BATCH_MAX = saved
    for cql, res in zip(cqls, got):
        assert _fids(res) == _fids(host.query("t", cql)), cql
