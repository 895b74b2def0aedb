"""Attribute-equality device batch (VERDICT r3 #9): the join attribute
strategy evaluated AT the data — ``attr = literal`` decided on device
via unified dictionary codes, fused into the same batched exact scans
as the box(+window) test (AttributeIndex.scala:42,392 role).
"""

import jax
import numpy as np
import pytest

from geomesa_tpu.geom.base import Point
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore

SPEC = "dtg:Date,kind:String,*geom:Point:srid=4326"
BASE = int(np.datetime64("2026-01-01T00:00:00", "ms").astype("int64"))


@pytest.fixture(autouse=True)
def _force_batch(monkeypatch):
    monkeypatch.setenv("GEOMESA_EXACT_DEVICE", "1")
    monkeypatch.setenv("GEOMESA_DEVBATCH", "1")
    monkeypatch.setenv("GEOMESA_SEEK", "0")


def _stores(n=40_000, seed=21, batches=3, null_every=11, devices=None):
    """Multiple write batches -> multiple blocks with DISTINCT per-block
    vocabs (the unified re-encode is the correctness risk). ``devices``
    cuts the mesh to its first N devices (the wire format follows it)."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(-170, 170, n)
    y = rng.uniform(-80, 80, n)
    t = BASE + rng.integers(0, 20 * 86400_000, n)
    # kinds skew per batch so block vocabs differ
    kinds = np.empty(n, dtype=object)
    for b in range(batches):
        sl = slice(b * n // batches, (b + 1) * n // batches)
        pool = [f"k{(b + j) % 5}" for j in range(3)]
        kinds[sl] = rng.choice(pool, (sl.stop or n) - sl.start)
    kinds[::null_every] = None
    fids = np.array([f"f{i}" for i in range(n)], dtype=object)
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(
        executor=TpuScanExecutor(default_mesh(jax.devices()[:devices]))
    )
    for s in (host, tpu):
        s.create_schema(parse_spec("t", SPEC))
        # one columnar write per batch keeps the multiple-blocks /
        # distinct-vocabs shape without the per-row writer wall
        for b in range(batches):
            sl = slice(b * n // batches, (b + 1) * n // batches)
            with s.writer("t") as w:
                w.write_columns({
                    "__fid__": fids[sl], "dtg": t[sl].astype(np.int64),
                    "kind": kinds[sl],
                    "geom__x": x[sl], "geom__y": y[sl],
                })
    return host, tpu


def _parity(host, tpu, cqls):
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        want = sorted(host.query("t", cql).fids)
        assert sorted(res.fids) == want, cql
    return got


CQLS_Z2 = [
    "kind = 'k1' AND bbox(geom, -60, -40, 40, 30)",
    "kind = 'k2' AND bbox(geom, -100, -60, 80, 60)",
    "kind = 'k0' AND bbox(geom, 0, 0, 90, 70)",
    "kind = 'nope' AND bbox(geom, -60, -40, 40, 30)",  # absent literal
]
CQLS_Z3 = [
    "kind = 'k1' AND bbox(geom, -60, -40, 40, 30) AND "
    "dtg DURING 2026-01-03T00:00:00Z/2026-01-12T00:00:00Z",
    "kind = 'k3' AND bbox(geom, -100, -60, 80, 60) AND "
    "dtg DURING 2026-01-05T00:00:00Z/2026-01-15T00:00:00Z",
]


@pytest.mark.parametrize("devices", [1, 2, 8])
def test_attr_batch_parity_all_protocols(devices):
    """One device frames the span bitmap over the whole segment; two and
    eight frame it per shard."""
    host, tpu = _stores(devices=devices)
    _parity(host, tpu, CQLS_Z2)
    # the device attr plane actually ran: unified code columns exist
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    assert all(
        getattr(s, "_attr_codes", {}).get("kind") is not None
        for s in dev.segments
    )


def test_attr_batch_parity_with_time():
    host, tpu = _stores()
    _parity(host, tpu, CQLS_Z3)
    table = tpu._tables["t"]["z3"]
    dev = tpu.executor.device_index(table)
    assert all(
        getattr(s, "_attr_codes", {}).get("kind") is not None
        for s in dev.segments
    )


def test_attr_batch_null_rows_never_match():
    host, tpu = _stores(null_every=3)  # a third of kinds are null
    got = _parity(host, tpu, CQLS_Z2[:2])
    for res in got:
        assert all(f is not None for f in res.fids)


def test_attr_batch_after_delete():
    host, tpu = _stores(n=9000)
    for s in (host, tpu):
        s.delete_features("t", "IN ('f10', 'f500', 'f8000')")
    _parity(host, tpu, CQLS_Z2[:2])


@pytest.mark.parametrize("devices", [1, 8])
def test_attr_in_list_parity(devices):
    """attr IN (...) rides the membership plane: K-padded qcode vectors
    (equality is the K=1 case), mixed list sizes in one stream, absent
    members, duplicates deduped."""
    host, tpu = _stores(devices=devices)
    cqls = [
        "kind IN ('k0', 'k2') AND bbox(geom, -60, -40, 40, 30)",
        "kind IN ('k1', 'k3', 'k4', 'nope') AND bbox(geom, -100, -60, 80, 60)",
        "kind IN ('k2') AND bbox(geom, 0, 0, 90, 70)",
        "kind IN ('k1', 'k1', 'k1') AND bbox(geom, -60, -40, 40, 30)",
        "kind = 'k0' AND bbox(geom, -40, -30, 30, 20)",  # mixed with eq
    ]
    _parity(host, tpu, cqls)
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    assert all(
        getattr(s, "_attr_codes", {}).get("kind") is not None
        for s in dev.segments
    )


def test_attr_in_list_with_time_and_lone():
    host, tpu = _stores()
    _parity(host, tpu, [
        "kind IN ('k0', 'k3') AND bbox(geom, -60, -40, 40, 30) AND "
        "dtg DURING 2026-01-03T00:00:00Z/2026-01-12T00:00:00Z",
        "kind IN ('k1', 'k2') AND bbox(geom, -90, -50, 70, 55) AND "
        "dtg DURING 2026-01-04T00:00:00Z/2026-01-14T00:00:00Z",
    ])
    # lone IN-list query: single-dispatch edition
    _parity(host, tpu, ["kind IN ('k2', 'k4') AND bbox(geom, -50, -35, 35, 28)"])


def test_attr_in_list_too_long_falls_back():
    """Lists past the K bucket cap (32) keep the conservative host path
    and still answer exactly."""
    host, tpu = _stores(n=6000)
    vals = ", ".join(f"'v{i}'" for i in range(40))
    _parity(host, tpu, [f"kind IN ({vals}, 'k1') AND bbox(geom, -60, -40, 40, 30)"])


def test_attr_in_list_wide_k_rides_device():
    """K in (8, 32] — the round-5 cap raise: a 13-distinct-value IN-list
    pads into the K=16 bucket and decides on device."""
    from geomesa_tpu.parallel import executor as ex

    host, tpu = _stores(n=6000)
    vals = ", ".join(f"'v{i}'" for i in range(11))
    cql = f"kind IN ({vals}, 'k1', 'k2') AND bbox(geom, -60, -40, 40, 30)"
    from geomesa_tpu.index.planner import Query

    plan = tpu.planner("t").plan(Query.cql(cql))
    table = tpu._tables["t"][plan.index.name]
    desc = tpu.executor._attr_batch_desc(table, plan)
    assert desc is not None and desc[1] == "member"
    assert len(desc[2][2]) == 13
    _parity(host, tpu, [cql, cql.replace("40, 30", "50, 40")])


def test_attr_not_equal_rides_notmember_plane():
    """`<>` chains decide on device via the complement-membership
    edition: null rows never match, absent excluded literals exclude
    nothing, chains AND together."""
    host, tpu = _stores()
    cqls = [
        "kind <> 'k1' AND bbox(geom, -60, -40, 40, 30)",
        "kind <> 'k0' AND kind <> 'k3' AND bbox(geom, -100, -60, 80, 60)",
        "kind <> 'absent' AND bbox(geom, -60, -40, 40, 30)",
        "kind <> 'k2' AND bbox(geom, 0, 0, 90, 70) AND "
        "dtg DURING 2026-01-03T00:00:00Z/2026-01-12T00:00:00Z",
    ]
    from geomesa_tpu.index.planner import Query

    plan = tpu.planner("t").plan(Query.cql(cqls[1]))
    table = tpu._tables["t"][plan.index.name]
    desc = tpu.executor._attr_batch_desc(table, plan)
    assert desc is not None and desc[1] == "notmember"
    assert desc[2][2] == ("k0", "k3")
    got = _parity(host, tpu, cqls)
    # the complement must actually exclude nulls (data has them)
    assert all("kind" not in r.columns or None not in r.columns["kind"]
               for r in got)


def test_attr_not_equal_mixed_with_range_stays_host():
    """`<>` combined with order predicates on the same attr declines the
    device plane (host path answers exactly)."""
    host, tpu = _stores(n=6000)
    cql = ("kind <> 'k1' AND kind > 'k0' AND "
           "bbox(geom, -60, -40, 40, 30)")
    from geomesa_tpu.index.planner import Query

    plan = tpu.planner("t").plan(Query.cql(cql))
    table = tpu._tables["t"][plan.index.name]
    assert tpu.executor._attr_batch_desc(table, plan) is None
    _parity(host, tpu, [cql])


def test_lone_attr_query_stays_on_device():
    """A single eligible query (no batch partner) must still run the
    device attr plane via the single-query dispatch, exactly."""
    host, tpu = _stores(n=8000)
    got = _parity(host, tpu, CQLS_Z2[:1])
    assert len(got[0].fids) > 0
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    assert all(
        getattr(s, "_attr_codes", {}).get("kind") is not None
        for s in dev.segments
    )


def test_attr_shape_rejects_non_eligible():
    """LIKE / inequality / json attrs / multiple attr predicates keep the
    conservative path (host post-filter) and still answer exactly."""
    host, tpu = _stores(n=6000)
    cqls = [
        "kind LIKE 'k%' AND bbox(geom, -60, -40, 40, 30)",
        "kind <> 'k1' AND bbox(geom, -60, -40, 40, 30)",
        "kind = 'k1' AND kind = 'k2' AND bbox(geom, -60, -40, 40, 30)",
    ]
    _parity(host, tpu, cqls)


def test_ilike_and_wildcards_ride_vocabmask_plane():
    """ILIKE and general LIKE wildcards ('_', interior '%') decide on
    device via the vocab-mask edition — the oracle's own regex evaluated
    over each segment's unified vocab, so parity is by construction."""
    from geomesa_tpu.index.planner import Query

    host, tpu = _stores()
    cqls = [
        "kind ILIKE 'K1' AND bbox(geom, -60, -40, 40, 30)",
        "kind ILIKE 'k%' AND bbox(geom, -100, -60, 80, 60)",
        "kind LIKE 'k_' AND bbox(geom, -60, -40, 40, 30)",
        "kind LIKE '%1%' AND bbox(geom, 0, 0, 90, 70)",
        "kind ILIKE 'K_' AND bbox(geom, -60, -40, 40, 30) AND "
        "dtg DURING 2026-01-03T00:00:00Z/2026-01-12T00:00:00Z",
    ]
    plan = tpu.planner("t").plan(Query.cql(cqls[0]))
    table = tpu._tables["t"][plan.index.name]
    desc = tpu.executor._attr_batch_desc(table, plan)
    assert desc is not None and desc[1] == "vocabmask"
    assert desc[2][2] == ("K1", True)
    _parity(host, tpu, cqls)


def test_vocabmask_lone_and_count():
    host, tpu = _stores(n=8000)
    cql = "kind ILIKE 'K2' AND bbox(geom, -60, -40, 40, 30)"
    _parity(host, tpu, [cql])  # lone query: single-dispatch edition
    assert tpu.count("t", cql) == len(host.query("t", cql))


def test_vocabmask_declines_oversized_vocab(monkeypatch):
    """A unified vocab past the cap keeps the host path (still exact)."""
    host, tpu = _stores(n=6000)
    # crush the cap on every live segment instead of synthesizing a
    # 65k-value vocab
    table = tpu._tables["t"]["z2"]
    dev = tpu.executor.device_index(table)
    tpu.query_many("t", CQLS_Z2[:2])  # build mirror + codes
    for seg in dev.segments:
        monkeypatch.setattr(type(seg), "ATTR_VOCAB_MASK_CAP", 2,
                            raising=False)
    _parity(host, tpu, ["kind ILIKE 'K1' AND bbox(geom, -60, -40, 40, 30)",
                        "kind ILIKE 'K3' AND bbox(geom, -90, -50, 70, 55)"])
