"""Attr device plane: numeric equality + range predicates in rank-code
space (round-4 extension of the r3 attribute-equality batch).

The segment's unified code space generalizes from dictionary vocabs to
sorted ranks over ANY orderable column (np.unique of raw values for
int/long/float/double/date and high-cardinality fixed-width strings), so
the device decides:

- numeric equality / IN-lists on the existing membership edition, and
- order predicates (<, <=, >, >=, BETWEEN; DURING/BEFORE/AFTER on
  secondary dates) as ONE inclusive [lo, hi] interval test per query —
  code order == value order because the space is sorted.

Reference role: the join attribute strategy evaluated at the data
(AccumuloDataStore AttributeIndex.scala:42,392), extended to the range
scans its attribute index serves host-side.
"""

import jax
import numpy as np
import pytest

from geomesa_tpu.geom.base import Point
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore

SPEC = "dtg:Date,kind:String,score:Double,cnt:Int,seen:Date,tag:String,*geom:Point:srid=4326"
BASE = int(np.datetime64("2026-01-01T00:00:00", "ms").astype("int64"))


@pytest.fixture(autouse=True)
def _force_batch(monkeypatch):
    monkeypatch.setenv("GEOMESA_EXACT_DEVICE", "1")
    monkeypatch.setenv("GEOMESA_DEVBATCH", "1")
    monkeypatch.setenv("GEOMESA_SEEK", "0")


def _stores(n=30_000, seed=33, batches=3, null_every=13, nan_every=17,
            devices=None):
    """Multi-batch writes -> multiple blocks whose value pools differ
    (the unified re-encode across mixed dict/raw layouts is the
    correctness risk). ``tag`` is per-row-unique so blocks store the
    high-cardinality fixed-width-unicode fallback, not a vocab."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(-170, 170, n)
    y = rng.uniform(-80, 80, n)
    t = BASE + rng.integers(0, 20 * 86400_000, n)
    seen = BASE + rng.integers(0, 40 * 86400_000, n)
    score = np.round(rng.uniform(0, 1, n), 3)
    cnt = rng.integers(0, 12, n)
    kinds = np.array([f"k{v}" for v in rng.integers(0, 6, n)], dtype=object)
    rows = []
    for i in range(n):
        rows.append([
            int(t[i]),
            None if i % null_every == 0 else str(kinds[i]),
            (None if i % null_every == 1 else
             (float("nan") if i % nan_every == 0 else float(score[i]))),
            None if i % null_every == 2 else int(cnt[i]),
            None if i % null_every == 3 else int(seen[i]),
            f"tag-{i:07d}",
            Point(float(x[i]), float(y[i])),
        ])
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(
        executor=TpuScanExecutor(default_mesh(jax.devices()[:devices]))
    )
    for s in (host, tpu):
        s.create_schema(parse_spec("t", SPEC))
        for b in range(batches):
            sl = slice(b * n // batches, (b + 1) * n // batches)
            with s.writer("t") as w:
                for i in range(sl.start, sl.stop):
                    w.write(rows[i], fid=f"f{i}")
    return host, tpu


def _parity(host, tpu, cqls):
    got = tpu.query_many("t", cqls)
    for cql, res in zip(cqls, got):
        want = sorted(host.query("t", cql).fids)
        assert sorted(res.fids) == want, cql
    return got


def _plane_loaded(tpu, index, attr):
    table = tpu._tables["t"][index]
    dev = tpu.executor.device_index(table)
    assert dev.segments
    assert all(
        getattr(s, "_attr_codes", {}).get(attr) is not None
        for s in dev.segments
    ), f"device plane not loaded for {attr}"


BOX = "bbox(geom, -100, -60, 80, 60)"
BOX2 = "bbox(geom, -60, -40, 40, 30)"


@pytest.mark.parametrize("devices", [1, 8])
def test_numeric_equality_and_in_list(devices):
    host, tpu = _stores(devices=devices)
    _parity(host, tpu, [
        f"cnt = 5 AND {BOX}",
        f"cnt = 0 AND {BOX2}",
        f"cnt = 99 AND {BOX}",  # absent literal: matches nothing
        f"cnt IN (2, 5, 7) AND {BOX}",
        f"score = 0.25 AND {BOX}",
    ])
    _plane_loaded(tpu, "z2", "cnt")
    _plane_loaded(tpu, "z2", "score")


@pytest.mark.parametrize("devices", [1, 8])
def test_numeric_ranges(devices):
    host, tpu = _stores(devices=devices)
    _parity(host, tpu, [
        f"score > 0.2 AND score <= 0.8 AND {BOX}",
        f"score < 0.5 AND {BOX2}",
        f"cnt BETWEEN 3 AND 6 AND {BOX}",
        f"cnt >= 10 AND {BOX}",
        f"cnt > 3 AND cnt < 5 AND {BOX2}",  # single-value interval
        f"cnt >= 3 AND cnt >= 5 AND {BOX}",  # two lower bounds
        f"cnt > 8 AND cnt < 3 AND {BOX}",  # empty interval
    ])
    _plane_loaded(tpu, "z2", "score")
    _plane_loaded(tpu, "z2", "cnt")


def test_string_ranges_dict_and_highcard():
    host, tpu = _stores()
    _parity(host, tpu, [
        f"kind >= 'k1' AND kind < 'k3' AND {BOX}",
        f"kind > 'k4' AND {BOX2}",
        f"kind BETWEEN 'k0' AND 'k2' AND {BOX}",
        f"kind > 'k9' AND {BOX}",  # empty: above the whole vocab
        # high-cardinality column: fixed-width-unicode blocks, no vocab
        f"tag < 'tag-0005000' AND {BOX}",
        f"tag BETWEEN 'tag-0010000' AND 'tag-0020000' AND {BOX2}",
    ])
    _plane_loaded(tpu, "z2", "kind")
    _plane_loaded(tpu, "z2", "tag")


def test_date_attr_ranges():
    """Secondary date attribute: Cmp coercion + the exclusive temporal
    forms ride the interval edition (the default dtg keeps the window
    plane)."""
    host, tpu = _stores()
    _parity(host, tpu, [
        f"seen AFTER 2026-01-20T00:00:00Z AND {BOX}",
        f"seen BEFORE 2026-01-10T00:00:00Z AND {BOX2}",
        "seen DURING 2026-01-05T00:00:00Z/2026-01-25T00:00:00Z AND "
        + BOX,
        f"seen > '2026-01-15T00:00:00Z' AND seen <= '2026-02-01T00:00:00Z' AND {BOX}",
    ])
    _plane_loaded(tpu, "z2", "seen")


def test_range_with_z3_window():
    host, tpu = _stores()
    _parity(host, tpu, [
        f"score > 0.3 AND score < 0.9 AND {BOX} AND "
        "dtg DURING 2026-01-03T00:00:00Z/2026-01-12T00:00:00Z",
        f"cnt <= 4 AND {BOX2} AND "
        "dtg DURING 2026-01-05T00:00:00Z/2026-01-15T00:00:00Z",
    ])
    _plane_loaded(tpu, "z3", "score")
    _plane_loaded(tpu, "z3", "cnt")


def test_lone_range_query_stays_on_device():
    host, tpu = _stores(n=8000)
    _parity(host, tpu, [f"score >= 0.4 AND score < 0.6 AND {BOX2}"])
    _plane_loaded(tpu, "z2", "score")


def test_nulls_and_nans_never_match():
    """None kinds/scores/cnts and NaN scores are -1 in code space; the
    oracle's valid mask excludes them too (including the stored-as-0.0
    None double)."""
    host, tpu = _stores(null_every=3, nan_every=5)
    got = _parity(host, tpu, [
        f"score >= 0.0 AND {BOX}",  # full range still excludes null/NaN
        f"cnt >= 0 AND {BOX}",
        f"score = 0.0 AND {BOX}",
        f"kind >= 'k0' AND {BOX2}",
    ])
    assert all(len(r.fids) > 0 for r in got[:2])


def test_range_after_delete():
    host, tpu = _stores(n=9000)
    for s in (host, tpu):
        s.delete_features("t", "IN ('f7', 'f123', 'f8000')")
    _parity(host, tpu, [f"cnt BETWEEN 2 AND 8 AND {BOX}"])


def test_mixed_member_and_range_stream():
    """One query_many stream mixing member-kind (equality/IN) and
    range-kind plans: they group into separate batches over the same
    codes column and both stay device-exact."""
    host, tpu = _stores()
    _parity(host, tpu, [
        f"cnt = 5 AND {BOX}",
        f"cnt > 2 AND cnt < 9 AND {BOX}",
        f"cnt IN (1, 3) AND {BOX2}",
        f"cnt <= 6 AND {BOX2}",
        f"cnt = 7 AND {BOX2}",
    ])
    _plane_loaded(tpu, "z2", "cnt")


def test_like_prefix_rides_code_range():
    """Single-trailing-% LIKE = a prefix interval on the sorted value
    space; wildcard-free LIKE = equality; both device-decided. Dict and
    high-cardinality string layouts."""
    host, tpu = _stores()
    got = _parity(host, tpu, [
        f"kind LIKE 'k1%' AND {BOX}",
        f"kind LIKE 'k%' AND {BOX2}",
        f"kind LIKE 'k3' AND {BOX}",  # wildcard-free: equality
        f"kind LIKE 'zz%' AND {BOX}",  # empty prefix interval
        f"tag LIKE 'tag-000%' AND {BOX}",  # high-card layout
        f"tag LIKE 'tag-001234%' AND {BOX2}",
    ])
    assert len(got[0].fids) > 0
    _plane_loaded(tpu, "z2", "kind")
    _plane_loaded(tpu, "z2", "tag")


def test_like_non_prefix_falls_back():
    """Leading/multiple %, _, case-insensitive: host path, still exact."""
    host, tpu = _stores(n=6000)
    _parity(host, tpu, [
        f"kind LIKE '%1' AND {BOX2}",
        f"kind LIKE 'k%1' AND {BOX2}",
        f"kind LIKE 'k_' AND {BOX2}",
        f"kind ILIKE 'K1%' AND {BOX2}",
    ])


def test_is_null_and_not_null_on_device():
    """IS NULL = the [-1, -1] code interval (nulls AND float NaN rank
    -1, matching the oracle's ~valid); IS NOT NULL = [0, U-1]."""
    host, tpu = _stores(null_every=5, nan_every=7)
    got = _parity(host, tpu, [
        f"kind IS NULL AND {BOX}",
        f"score IS NULL AND {BOX}",  # includes the NaN rows
        f"cnt IS NULL AND {BOX2}",
        f"kind IS NOT NULL AND {BOX2}",
        f"score IS NOT NULL AND {BOX}",
        f"score IS NOT NULL AND score < 0.4 AND {BOX2}",
        f"cnt IS NULL AND cnt > 3 AND {BOX}",  # contradiction: empty
    ])
    assert all(len(r.fids) > 0 for r in got[:5])
    _plane_loaded(tpu, "z2", "kind")
    _plane_loaded(tpu, "z2", "score")
    _plane_loaded(tpu, "z2", "cnt")


def test_ineligible_shapes_fall_back_exactly():
    """IN + range on one attr, predicates on TWO attrs, <>: the
    conservative host path still answers exactly."""
    host, tpu = _stores(n=6000)
    _parity(host, tpu, [
        f"cnt IN (1, 2) AND cnt < 9 AND {BOX2}",
        f"cnt > 3 AND score < 0.5 AND {BOX2}",
        f"cnt <> 4 AND {BOX2}",
        f"kind = 'k1' AND kind = 'k2' AND {BOX2}",  # empty intersection
    ])
