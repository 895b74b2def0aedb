"""Device top-k kNN: per-shard lax.top_k candidates + exact host re-rank
must match the host expanding-bbox search (and brute force)."""

import numpy as np
import pytest

from geomesa_tpu.geom.base import Point
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.process.geodesy import haversine_m
from geomesa_tpu.process.knn import knn_search
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore

SPEC = "name:String,dtg:Date,*geom:Point:srid=4326"


@pytest.fixture(autouse=True)
def _force_device_knn(monkeypatch):
    # 'auto' routes kNN to the expanding-bbox seek on the CPU backend;
    # these tests are about the DEVICE top-k path, so force it on
    monkeypatch.setenv("GEOMESA_KNN_DEVICE", "1")


def _mk(executor, n=3000, seed=11):
    ds = TpuDataStore(executor=executor)
    ds.create_schema(parse_spec("t", SPEC))
    rng = np.random.default_rng(seed)
    base = np.datetime64("2026-01-01T00:00:00", "ms").astype("int64")
    with ds.writer("t") as w:
        for i in range(n):
            w.write(
                [f"n{i % 7}", int(base + i),
                 Point(float(rng.uniform(-60, 60)), float(rng.uniform(-60, 60)))],
                fid=f"f{i}",
            )
    return ds


def _brute(ds, x, y, k):
    res = ds.query("t")
    ft = ds.get_schema("t")
    d = haversine_m(res.columns["geom__x"], res.columns["geom__y"], x, y)
    order = np.argsort(d, kind="stable")[:k]
    return [(str(res.fids[i]), float(d[i])) for i in order]


def test_device_knn_matches_host_and_brute():
    tpu = _mk(TpuScanExecutor(default_mesh()))
    host = _mk(HostScanExecutor())
    for (x, y) in [(0.0, 0.0), (-55.0, 30.0), (59.0, -59.0)]:
        got = knn_search(tpu, "t", x, y, k=15)
        brute = _brute(tpu, x, y, 15)
        assert [f for f, _ in got] == [f for f, _ in brute]
        via_host = knn_search(host, "t", x, y, k=15)
        assert [f for f, _ in got] == [f for f, _ in via_host]


def test_device_knn_used_directly():
    tpu = _mk(TpuScanExecutor(default_mesh()))
    table = tpu._tables["t"]["z3"]
    parts = tpu.executor.knn_candidates(table, 0.0, 0.0, 10)
    assert parts is not None
    n_cand = sum(len(rows) for _, rows in parts)
    assert 10 <= n_cand <= 8 * 10 * 2  # per-shard k, not the whole table


def test_device_knn_respects_deletes():
    tpu = _mk(TpuScanExecutor(default_mesh()))
    first = knn_search(tpu, "t", 10.0, 10.0, k=5)
    victims = [f for f, _ in first[:2]]
    tpu.delete_features("t", victims)
    after = knn_search(tpu, "t", 10.0, 10.0, k=5)
    assert not (set(f for f, _ in after) & set(victims))
    brute = _brute(tpu, 10.0, 10.0, 5)
    assert [f for f, _ in after] == [f for f, _ in brute]


def test_device_knn_spmd_mode(monkeypatch):
    """shard_map per-chip top-k (interpret-mode Pallas masks off-TPU) must
    produce the same neighbors as the XLA single-shard path."""
    monkeypatch.setenv("GEOMESA_PALLAS", "spmd")
    tpu = _mk(TpuScanExecutor(default_mesh()))
    got = knn_search(tpu, "t", -20.0, 20.0, k=12)
    brute = _brute(tpu, -20.0, 20.0, 12)
    assert [f for f, _ in got] == [f for f, _ in brute]


def test_knn_with_filter_falls_back():
    tpu = _mk(TpuScanExecutor(default_mesh()))
    got = knn_search(tpu, "t", 0.0, 0.0, k=8, cql="name = 'n3'")
    assert len(got) == 8
    n3 = set(tpu.query("t", "name = 'n3'").fids)
    assert all(f in n3 for f, _ in got)  # filter actually honored
    res = tpu.query("t", "name = 'n3'")
    d = haversine_m(res.columns["geom__x"], res.columns["geom__y"], 0.0, 0.0)
    order = np.argsort(d, kind="stable")[:8]
    assert [f for f, _ in got] == [str(res.fids[i]) for i in order]


def test_device_failure_falls_back_to_host(monkeypatch):
    """A lost device / backend compile error inside the device top-k must
    degrade to the host expanding-bbox path, not kill the search (the
    round-4 silicon suite lost its kNN number to exactly this)."""
    import geomesa_tpu.process.knn as K

    monkeypatch.setenv("GEOMESA_KNN_DEVICE", "1")

    def boom(*a, **kw):
        raise RuntimeError("UNAVAILABLE: TPU backend setup/compile error")

    monkeypatch.setattr(K, "_device_knn", boom)
    tpu = _mk(TpuScanExecutor(default_mesh()))
    got = knn_search(tpu, "t", 10.0, 10.0, k=5)
    brute = _brute(tpu, 10.0, 10.0, 5)
    assert [f for f, _ in got] == [f for f, _ in brute]


def test_device_failure_trips_auto_mode_once(monkeypatch):
    """After one device failure, auto-mode searches skip the device
    attempt for the session (no per-query failure latency); forced =1
    keeps retrying."""
    import geomesa_tpu.process.knn as K

    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        raise RuntimeError("UNAVAILABLE")

    monkeypatch.setattr(K, "_device_knn", boom)
    monkeypatch.setattr(K, "_device_knn_wanted", lambda: True)
    monkeypatch.delenv("GEOMESA_KNN_DEVICE", raising=False)
    tpu = _mk(TpuScanExecutor(default_mesh()))
    brute = _brute(tpu, 10.0, 10.0, 5)
    for _ in range(3):
        got = knn_search(tpu, "t", 10.0, 10.0, k=5)
        assert [f for f, _ in got] == [f for f, _ in brute]
    assert calls["n"] == 1  # tripped after the first failure
    monkeypatch.setenv("GEOMESA_KNN_DEVICE", "1")
    knn_search(tpu, "t", 10.0, 10.0, k=5)
    assert calls["n"] == 2  # forced mode retries despite the trip


def test_last_path_marker(monkeypatch):
    """last_knn_path() truthfully records which path answered this
    thread's most recent call — benches consult it per call so a
    fallback can never report host time as a device number."""
    from geomesa_tpu.process.knn import last_knn_path

    tpu = _mk(TpuScanExecutor(default_mesh()))
    monkeypatch.setenv("GEOMESA_KNN_DEVICE", "1")
    got = knn_search(tpu, "t", 10.0, 10.0, k=5)
    assert last_knn_path() == "device-topk"
    assert [f for f, _ in got] == [f for f, _ in _brute(tpu, 10.0, 10.0, 5)]
    monkeypatch.setenv("GEOMESA_KNN_DEVICE", "0")
    knn_search(tpu, "t", 10.0, 10.0, k=5)
    assert last_knn_path() == "host-bbox"
