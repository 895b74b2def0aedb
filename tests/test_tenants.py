"""Per-tenant cost metering (utils/tenants.py) and its surfaces.

The contract under test:

* attribution — the ``tenant`` query hint wins, the
  ``X-Geomesa-Tenant`` HTTP header fills it in when absent, everything
  else meters as ``anon``;
* conservation — per-tenant per-class call sums equal the store-level
  counters EXACTLY (ok and failed outcomes both), single-store and
  through the sharded rollup;
* the per-tenant SLO fold — one sick tenant's availability burn
  degrades the spec as ``<slo>@tenant:<label>`` while the merged
  fleet-wide gate stays green (the per-worker unmerged-series posture
  applied to tenant labels);
* the shared web query-param validators (web.py) and the
  ``/debug/tenants`` route contract built on them (400 on caller
  errors, clamp on absurd sizes, sort whitelist).
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import web
from geomesa_tpu.index.planner import Query
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import TpuDataStore
from geomesa_tpu.utils import slo, tenants, timeline
from geomesa_tpu.utils.audit import MetricsRegistry
from geomesa_tpu.utils.config import properties

T0 = 1483228800000  # 2017-01-01T00:00:00Z
DAY = 86400000
SPEC = "actor:String,dtg:Date,*geom:Point:srid=4326"
CQL = "bbox(geom, -50, -50, 50, 50)"


@pytest.fixture(autouse=True)
def _reset_flags():
    tenants.set_enabled(None)
    yield
    tenants.set_enabled(None)


def _fill(store, name="gdelt", n=500, seed=3):
    ft = parse_spec(name, SPEC)
    store.create_schema(ft)
    rng = np.random.default_rng(seed)
    store._insert_columns(ft, {
        "__fid__": np.array([f"f{i}" for i in range(n)], dtype=object),
        "geom__x": rng.uniform(-80, 80, n),
        "geom__y": rng.uniform(-80, 80, n),
        "dtg": T0 + rng.integers(0, 30 * DAY, n),
        "actor": np.array([["USA", "FRA", "CHN"][i % 3] for i in range(n)],
                          dtype=object),
    })
    return store


# -- attribution --------------------------------------------------------------


def test_hint_attribution_and_anon_default():
    store = _fill(TpuDataStore())
    store.query("gdelt", Query.cql(CQL, hints={"tenant": "acme"}))
    store.query("gdelt", Query.cql(CQL))
    rows = {r["tenant"]: r for r in store._tenants_obj().rows(n=10)}
    assert rows["acme"]["calls"] == 1
    assert rows["anon"]["calls"] == 1
    assert rows["acme"]["classes"]["query"]["calls"] == 1


def test_label_cleaning_truncates_and_strips():
    assert tenants.clean_label("  acme  ") == "acme"
    assert tenants.clean_label("") == tenants.ANON
    assert tenants.clean_label(None) == tenants.ANON
    assert len(tenants.clean_label("x" * 500)) == 64


def test_header_fills_hint_and_hint_wins():
    store = _fill(TpuDataStore())
    with web.GeoMesaServer(store) as url:
        req = urllib.request.Request(
            url + "/query?name=gdelt&cql=INCLUDE&max=5",
            headers={"X-Geomesa-Tenant": "hdr-co"},
        )
        urllib.request.urlopen(req).read()
        body = json.loads(
            urllib.request.urlopen(url + "/debug/tenants").read()
        )
    got = {r["tenant"]: r["calls"] for r in body["tenants"]}
    assert got.get("hdr-co") == 1
    # the hint wins when both are present (the header only fills an
    # ABSENT hint — setdefault semantics): an application-set tenant
    # hint survives a proxy stamping its own header
    q = Query.cql(CQL, hints={"tenant": "app-co"})
    q.hints.setdefault("tenant", "hdr-co")  # what _apply_tenant does
    assert tenants.tenant_of(q) == "app-co"


def test_disabled_costs_nothing_and_reports_disabled():
    store = _fill(TpuDataStore())
    with properties(geomesa_tenants_enabled="false"):
        tenants.set_enabled(None)
        store.query("gdelt", Query.cql(CQL, hints={"tenant": "acme"}))
        assert getattr(store, "_tenants", None) is None
        payload = web.debug_tenants_payload(store)
    assert payload["enabled"] is False
    assert payload["tenants"] == []


# -- conservation -------------------------------------------------------------


def test_per_tenant_sums_equal_store_counters():
    """The accounting is conservative AND exact: per-tenant per-class
    call/bad sums equal the store-level counters, ok and failed
    outcomes included."""
    reg = MetricsRegistry()
    store = _fill(TpuDataStore(metrics=reg))
    store.query("gdelt", Query.cql(CQL, hints={"tenant": "acme"}))
    store.query("gdelt", Query.cql("actor = 'USA'", hints={"tenant": "beta"}))
    store.query("gdelt", Query.cql("INCLUDE"))
    store.aggregate("gdelt", Query.cql(CQL, hints={"tenant": "acme"}))
    for _ in store.query_stream(
        "gdelt", Query.cql(CQL, hints={"tenant": "beta"})
    ):
        pass
    # a failed query meters too (timeout after zero budget)
    from geomesa_tpu.utils.audit import QueryTimeout

    slow = _fill(TpuDataStore(metrics=reg, query_timeout_s=0.0), name="g2")
    slow.__dict__["_tenants"] = store._tenants_obj()  # shared registry
    with pytest.raises(QueryTimeout):
        slow.query("g2", Query.cql(CQL, hints={"tenant": "acme"}))

    by_class: dict = {}
    bad = 0
    for r in store._tenants_obj().rows(n=100):
        for cls, c in r["classes"].items():
            by_class[cls] = by_class.get(cls, 0) + c["calls"]
            bad += c["bad"]
    # streams audit through the same "queries" counter as plain queries
    # (the store's counter naming); the tenant table keeps them as
    # their own class, so conservation sums the two
    assert by_class["query"] + by_class.get("stream", 0) == reg.counter(
        "queries")
    assert by_class["aggregate"] == reg.counter("queries.aggregate")
    assert bad == reg.counter("queries.timeout")


def test_sharded_rollup_conserves_calls():
    """Fan the same tagged traffic through a sharded store: the merged
    cross-shard tenant table's call sums equal the per-shard sums —
    nothing lost or double-counted in the rollup."""
    from geomesa_tpu.parallel.shards import ShardedDataStore

    store = ShardedDataStore(num_shards=3, replicas=1)
    _fill(store)
    for i in range(6):
        store.query("gdelt", Query.cql(
            CQL, hints={"tenant": ["acme", "beta"][i % 2]}
        ))
    shards, merged = store.tenants_rollup()
    per_shard = sum(
        r["calls"] for rows_ in shards.values() for r in rows_
    )
    per_merged = sum(r["calls"] for r in merged)
    assert per_merged == per_shard
    labels = {r["tenant"] for r in merged}
    assert {"acme", "beta"} <= labels


# -- the per-tenant SLO fold --------------------------------------------------


def _slo_props(**extra):
    base = dict(
        geomesa_slo_min_events="5",
        geomesa_slo_window_fast="1 second",
        geomesa_slo_window_slow="3 seconds",
    )
    base.update(extra)
    return properties(**base)


def test_sick_tenant_burns_named_while_fleet_green():
    """One tenant at 90% timeouts inside healthy merged traffic: the
    merged availability gate stays quiet, the per-tenant fold names
    ``query-availability@tenant:acme`` — the per-worker unmerged-series
    posture (PR 15) applied to tenant labels."""
    reg = MetricsRegistry()
    store = _fill(TpuDataStore(metrics=reg))
    treg = store._tenants_obj()
    s = timeline.TimelineSampler(
        store=store, registries=[reg], interval_s=0.1, window_s=10
    )
    s.tick()
    # merged traffic healthy on average: 1009 calls, 9 bad
    reg.inc("queries", 1000)
    reg.inc("queries.timeout", 9)
    for _ in range(9):
        treg.observe("acme", "query", outcome="timeout", duration_s=0.01)
    treg.observe("acme", "query", outcome="ok", duration_s=0.01)
    s.tick()
    with _slo_props():
        ev = slo.SloEngine(s).evaluate()
    row = next(r for r in ev["slos"] if r["name"] == "query-availability")
    assert row["fast"]["burn_rate"] < 14.4  # merged gate quiet
    assert row["violating_tenants"] == ["acme"]
    assert row["tenants"]["acme"]["violating"]
    assert row["violating"]
    assert "query-availability@tenant:acme" in ev["violating"]


def test_healthy_tenants_do_not_burn():
    reg = MetricsRegistry()
    store = _fill(TpuDataStore(metrics=reg))
    treg = store._tenants_obj()
    s = timeline.TimelineSampler(
        store=store, registries=[reg], interval_s=0.1, window_s=10
    )
    s.tick()
    reg.inc("queries", 100)
    for _ in range(20):
        treg.observe("acme", "query", outcome="ok", duration_s=0.01)
    s.tick()
    with _slo_props():
        ev = slo.SloEngine(s).evaluate()
    assert not any("@tenant:" in v for v in ev["violating"])


# -- registry mechanics -------------------------------------------------------


def test_registry_caps_and_evicts_lru():
    with properties(geomesa_tenants_max="2"):
        r = tenants.TenantRegistry()
    for label in ("a", "b", "c"):
        r.observe(label, "query", outcome="ok", duration_s=0.01)
    rows = {row["tenant"] for row in r.rows(n=10)}
    assert len(rows) == 2 and "c" in rows  # oldest evicted, newest kept


def test_rows_rejects_unknown_sort():
    r = tenants.TenantRegistry()
    with pytest.raises(ValueError):
        r.rows(sort="bogus")


def test_timeline_deltas_are_deltas():
    r = tenants.TenantRegistry()
    r.observe("acme", "query", outcome="ok", duration_s=0.1)
    prev, rows1 = tenants.timeline_deltas(r, {})
    assert rows1 and rows1[0]["calls"] == 1
    _, rows2 = tenants.timeline_deltas(r, prev)
    assert rows2 == []  # no new traffic, no delta rows


# -- the shared web param validators ------------------------------------------


def test_parse_count_param_contract():
    assert web.parse_count_param({"n": "5"}, cap=10) == (5, None)
    assert web.parse_count_param({}, cap=10, default_n=7) == (7, None)
    assert web.parse_count_param({"n": "99"}, cap=10) == (10, None)  # clamp
    assert web.parse_count_param({"n": "x"}, cap=10) == (
        None, "n must be an integer")
    assert web.parse_count_param({"n": "-1"}, cap=10) == (
        None, "n must be >= 0")


def test_parse_window_param_contract():
    assert web.parse_window_param({"s": "5"}, default_s=60.0) == (5.0, None)
    assert web.parse_window_param({}, default_s=60.0) == (60.0, None)
    got, err = web.parse_window_param({"s": "1e12"}, default_s=60.0)
    assert err is None and got == web.MAX_TIMELINE_S  # clamp
    assert web.parse_window_param({"s": "x"}, default_s=60.0) == (
        None, "s must be a number of seconds")
    assert web.parse_window_param({"s": "-2"}, default_s=60.0) == (
        None, "s must be >= 0")
    assert web.parse_window_param({"s": "nan"}, default_s=60.0)[1] is not None


def test_parse_sort_param_contract():
    assert web.parse_sort_param({}, tenants.SORTS) == ("time", None)
    assert web.parse_sort_param({"sort": "calls"}, tenants.SORTS) == (
        "calls", None)
    got, err = web.parse_sort_param({"sort": "bogus"}, tenants.SORTS)
    assert got is None and "sort must be one of" in err


def test_debug_tenants_route_contract():
    store = _fill(TpuDataStore())
    store.query("gdelt", Query.cql(CQL, hints={"tenant": "acme"}))
    with web.GeoMesaServer(store) as url:
        body = json.loads(
            urllib.request.urlopen(url + "/debug/tenants?sort=calls").read()
        )
        assert body["enabled"] and body["tenants"]
        for bad in ("?n=x", "?n=-1", "?sort=bogus"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url + "/debug/tenants" + bad)
            assert ei.value.code == 400
        # absurd n clamps instead of erroring
        ok = urllib.request.urlopen(url + "/debug/tenants?n=999999")
        assert ok.status == 200
        rep = json.loads(
            urllib.request.urlopen(url + "/debug/report").read()
        )
    assert "tenants" in rep["sections"]
