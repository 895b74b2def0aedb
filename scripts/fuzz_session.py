"""Extended fuzz-parity session: many random store pairs x many random
queries across every executor mode, with deletes, sorts, limits,
projections and compaction — the long-running version of
tests/test_fuzz_parity.py, covering the round-3 paths (record-table
joins, dictionary-encoded strings, device-assisted seek).

Usage: python scripts/fuzz_session.py [minutes] (default 30). Prints a
running tally; any parity failure prints the repro (seed, mode, query)
and exits non-zero.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from geomesa_tpu.parallel.mesh import force_cpu_platform  # noqa: E402

force_cpu_platform()

from geomesa_tpu.geom.base import Point  # noqa: E402
from geomesa_tpu.index.planner import Query  # noqa: E402
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh  # noqa: E402
from geomesa_tpu.schema.featuretype import parse_spec  # noqa: E402
from geomesa_tpu.store.datastore import HostScanExecutor, TpuDataStore  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))
from test_fuzz_parity import _data, _rand_query  # noqa: E402

SPEC = "name:String:index=true,tag:String,age:Int,dtg:Date,*geom:Point:srid=4326"

MODES = [
    {"GEOMESA_SEEK": "auto"},
    {"GEOMESA_SEEK": "0"},
    {"GEOMESA_SEEK": "1"},
    {"GEOMESA_SEEK": "auto", "GEOMESA_TPU_NO_NATIVE": "1"},
    {"GEOMESA_SEEK": "auto", "GEOMESA_DEVSEEK": "1"},
    {"GEOMESA_SEEK": "auto", "GEOMESA_EXACT_DEVICE": "1"},
    # batched exact device scans (query_many fuses exact-shape plans into
    # the span-framed bitmaps the mesh picks)
    {"GEOMESA_SEEK": "0", "GEOMESA_EXACT_DEVICE": "1", "GEOMESA_DEVBATCH": "1"},
    # device mask-sum counts alongside the batched scans
    {"GEOMESA_SEEK": "0", "GEOMESA_EXACT_DEVICE": "1", "GEOMESA_DEVBATCH": "1",
     "GEOMESA_COUNT_DEVICE": "1"},
]
_MODE_KEYS = (
    "GEOMESA_SEEK", "GEOMESA_TPU_NO_NATIVE", "GEOMESA_DEVSEEK",
    "GEOMESA_EXACT_DEVICE", "GEOMESA_DEVBATCH", "GEOMESA_COUNT_DEVICE",
)


def build_pair(rng, n):
    host = TpuDataStore(executor=HostScanExecutor())
    tpu = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
    rows = _data(rng, n)
    for s in (host, tpu):
        s.create_schema(parse_spec("t", SPEC))
        with s.writer("t") as w:
            for fid, name, age, t, x, y in rows:
                tag = None if int(fid[1:]) % 13 == 0 else f"tag-{int(fid[1:]) % 7}"
                w.write([name, tag, age, t, Point(x, y)], fid=fid)
    return host, tpu


def one_round(seed: int) -> int:
    rng = np.random.default_rng(seed)
    n = int(rng.integers(400, 2500))
    mode = MODES[seed % len(MODES)]
    old = {k: os.environ.get(k) for k in _MODE_KEYS}
    for k in old:
        os.environ.pop(k, None)
    os.environ.update(mode)
    try:
        host, tpu = build_pair(rng, n)
        checked = 0
        queries = [_rand_query(rng) for _ in range(12)] + [
            "tag IS NULL",
            "tag = 'tag-3' AND bbox(geom, -50, -40, 40, 40)",
            "name LIKE 'n%' AND age BETWEEN 10 AND 50",
            # attr-equality device plane shapes (batch modes route these
            # through the dictionary-code compare): z3 window edition,
            # absent literal, and two batchable partners on one attr
            "tag = 'tag-1' AND bbox(geom, -60, -50, 50, 50) AND "
            "dtg DURING 2026-01-02T00:00:00Z/2026-01-20T00:00:00Z",
            "tag = 'no-such-tag' AND bbox(geom, -50, -40, 40, 40)",
            "tag = 'tag-5' AND bbox(geom, -20, -30, 60, 45)",
            "tag IN ('tag-0', 'tag-4', 'missing') AND "
            "bbox(geom, -55, -45, 45, 45)",
            "tag IN ('tag-2', 'tag-6') AND bbox(geom, -40, -35, 50, 40) AND "
            "dtg DURING 2026-01-03T00:00:00Z/2026-01-18T00:00:00Z",
            # range-kind attr plane shapes (round 4): numeric + string
            # code-interval tests, incl. the z3 window edition, numeric
            # equality (membership edition on raw ranks), and an empty
            # interval
            "age > 20 AND age <= 60 AND bbox(geom, -55, -45, 45, 45)",
            "age BETWEEN 15 AND 40 AND bbox(geom, -60, -50, 50, 50) AND "
            "dtg DURING 2026-01-02T00:00:00Z/2026-01-20T00:00:00Z",
            "tag >= 'tag-2' AND tag < 'tag-5' AND bbox(geom, -50, -40, 40, 40)",
            "age = 33 AND bbox(geom, -45, -40, 45, 40)",
            "age IN (12, 34, 56) AND bbox(geom, -55, -40, 50, 42)",
            "age > 64 AND age < 12 AND bbox(geom, -50, -40, 40, 40)",
            # round-5 plane editions: complement membership ('<>'
            # chains), wide IN (K in (8, 32]), and the vocab-mask plane
            # (ILIKE / '_' / interior '%' via the oracle-regex mask)
            "tag <> 'tag-3' AND bbox(geom, -50, -40, 40, 40)",
            "tag <> 'tag-0' AND tag <> 'tag-5' AND "
            "bbox(geom, -55, -45, 45, 45) AND "
            "dtg DURING 2026-01-02T00:00:00Z/2026-01-20T00:00:00Z",
            "age <> 33 AND bbox(geom, -45, -40, 45, 40)",
            "age IN (" + ", ".join(str(v) for v in range(10, 34)) + ") "
            "AND bbox(geom, -55, -40, 50, 42)",
            "tag ILIKE 'TAG-2' AND bbox(geom, -50, -40, 40, 40)",
            "tag ILIKE 'TaG-%' AND bbox(geom, -40, -35, 50, 40)",
            "tag LIKE 'tag-_' AND bbox(geom, -55, -45, 45, 45)",
            "tag LIKE '%g-4%' AND bbox(geom, -50, -40, 40, 40) AND "
            "dtg DURING 2026-01-03T00:00:00Z/2026-01-18T00:00:00Z",
        ]
        wants = {}
        for q in queries:
            got = sorted(map(str, tpu.query("t", q).fids))
            wants[q] = sorted(map(str, host.query("t", q).fids))
            assert got == wants[q], ("plain", seed, mode, q)
            checked += 1
        # filtered counts (device mask-sum when the mode enables it,
        # host len() otherwise) must match the materialized result size
        for q in queries[:6]:
            assert tpu.count("t", q) == len(wants[q]), ("count", seed, mode, q)
            checked += 1
        # banded-polygon count on the point table (round-5): |decided
        # ray-cast hits| + host-certified band
        pq = ("intersects(geom, POLYGON ((-40 -38, 32 -30, 12 28, "
              "-34 18, -40 -38)))")
        assert tpu.count("t", pq) == len(host.query("t", pq)), (
            "poly-count", seed, mode)
        checked += 1
        # query_many: the pipelined/batched dispatch (exact-shape plans
        # fuse into one device execution under GEOMESA_DEVBATCH) must be
        # positionally identical to per-query execution
        for q, r in zip(queries, tpu.query_many("t", queries)):
            assert sorted(map(str, r.fids)) == wants[q], ("many", seed, mode, q)
            checked += 1
        # options: sort / limit / projection
        q = queries[0]
        for opts in (
            dict(sort_by=[("age", False)]),
            dict(max_features=7),
            dict(properties=["name", "geom"]),
            dict(sort_by=[("name", True)], max_features=11),
        ):
            a = tpu.query("t", Query.cql(q, **opts))
            b = host.query("t", Query.cql(q, **opts))
            assert len(a) == len(b), ("opts-len", seed, mode, q, opts)
            if "sort_by" in opts and "max_features" not in opts:
                key = opts["sort_by"][0][0]
                av = a.columns.get(key)
                bv = b.columns.get(key)
                if av is not None and bv is not None:
                    assert list(map(str, av)) == list(map(str, bv)), (
                        "opts-order", seed, mode, q, opts)
            checked += 1
        # deletes then requery, then compact then requery
        dead = [f"f{i}" for i in range(0, n, int(rng.integers(5, 11)))]
        for s in (host, tpu):
            s.delete_features("t", dead)
        for q in queries[:5]:
            got = sorted(map(str, tpu.query("t", q).fids))
            want = sorted(map(str, host.query("t", q).fids))
            assert got == want, ("post-delete", seed, mode, q)
            checked += 1
        tpu.compact("t")
        for q in queries[:5]:
            got = sorted(map(str, tpu.query("t", q).fids))
            want = sorted(map(str, host.query("t", q).fids))
            assert got == want, ("post-compact", seed, mode, q)
            checked += 1
        return checked
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def one_extent_round(seed: int) -> int:
    """Extent store (mixed rects/triangles/lines/null geoms, with dates):
    exercises xz2/xz3 incl. the device-assisted extent seek modes."""
    from geomesa_tpu.geom.base import LineString, Polygon

    rng = np.random.default_rng(seed)
    n = int(rng.integers(300, 1500))
    mode = MODES[seed % len(MODES)]
    old = {k: os.environ.get(k) for k in _MODE_KEYS}
    for k in old:
        os.environ.pop(k, None)
    os.environ.update(mode)
    try:
        host = TpuDataStore(executor=HostScanExecutor())
        tpu = TpuDataStore(executor=TpuScanExecutor(default_mesh()))
        base = 1767225600000  # 2026-01-01
        rows = []
        for i in range(n):
            x0 = float(rng.uniform(-170, 160))
            y0 = float(rng.uniform(-80, 70))
            k = i % 5
            if k == 0:
                g = Polygon([[x0, y0], [x0 + 1, y0], [x0 + 1, y0 + 1],
                             [x0, y0 + 1], [x0, y0]])
            elif k == 1:
                g = Polygon([[x0, y0], [x0 + 2, y0], [x0 + 1, y0 + 2], [x0, y0]])
            elif k == 2:
                g = LineString([(x0, y0), (x0 + 1.5, y0 + 0.7)])
            elif k == 3:
                g = LineString([(x0, y0), (x0 + 0.3, y0), (x0 + 0.3, y0 + 2.5)])
            else:
                g = None
            t = None if i % 41 == 0 else int(base + rng.integers(0, 15 * 86400_000))
            cat = None if i % 23 == 0 else f"cat-{int(rng.integers(0, 5))}"
            rows.append((f"e{i}", t, cat, g))
        for s in (host, tpu):
            s.create_schema(
                parse_spec("e", "dtg:Date,cat:String,*geom:Geometry:srid=4326")
            )
            with s.writer("e") as w:
                for fid, t, cat, g in rows:
                    w.write([t, cat, g], fid=fid)
        checked = 0
        queries = []
        wants = {}
        for _ in range(10):
            x0 = float(rng.uniform(-60, 30))
            y0 = float(rng.uniform(-40, 20))
            w_ = float(rng.uniform(5, 50))
            parts = [f"bbox(geom, {x0!r}, {y0!r}, {x0 + w_!r}, {y0 + w_!r})"]
            if rng.random() < 0.6:
                d0 = int(rng.integers(1, 10))
                d1 = d0 + int(rng.integers(1, 5))
                parts.append(
                    f"dtg DURING 2026-01-{d0:02d}T00:00:00Z/2026-01-{d1:02d}T00:00:00Z"
                )
            if rng.random() < 0.3:
                parts = [
                    f"INTERSECTS(geom, POLYGON(({x0} {y0}, {x0+w_} {y0}, "
                    f"{x0+w_/2} {y0+w_}, {x0} {y0})))"
                ] + parts[1:]
            if rng.random() < 0.4:
                # xz attr plane shapes: member / range fused into the
                # dual hit/decided planes (batched via query_many below)
                parts.append(
                    rng.choice([
                        f"cat = 'cat-{int(rng.integers(0, 5))}'",
                        "cat >= 'cat-1' AND cat < 'cat-4'",
                        "cat IN ('cat-0', 'cat-2')",
                        "cat IS NOT NULL",
                    ])
                )
            q = " AND ".join(parts)
            queries.append(q)
            got = sorted(map(str, tpu.query("e", q).fids))
            wants[q] = sorted(map(str, host.query("e", q).fids))
            assert got == wants[q], ("extent", seed, mode, q)
            checked += 1
        # query_many: the batched dual-plane dispatch (incl. the attr
        # editions when >= 2 shapes share a group) must match the
        # singles' host results (cached above — no second oracle pass)
        for q, r in zip(queries, tpu.query_many("e", queries)):
            assert sorted(map(str, r.fids)) == wants[q], (
                "extent-many", seed, mode, q)
            checked += 1
        # extent counts: |device-decided| + certified ring (round-5)
        for q in queries[:4]:
            assert tpu.count("e", q) == len(wants[q]), (
                "extent-count", seed, mode, q)
            checked += 1
        dead = [f"e{i}" for i in range(0, n, 7)]
        for s in (host, tpu):
            s.delete_features("e", dead)
        q = "bbox(geom, -60, -40, 40, 30)"
        got = sorted(map(str, tpu.query("e", q).fids))
        want = sorted(map(str, host.query("e", q).fids))
        assert got == want, ("extent-post-delete", seed, mode)
        return checked + 1
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main():
    minutes = float(sys.argv[1]) if len(sys.argv) > 1 else 30.0
    deadline = time.monotonic() + minutes * 60
    seed = int(os.environ.get("FUZZ_SEED0", 10_000))
    stores = 0
    queries = 0
    t0 = time.monotonic()
    while time.monotonic() < deadline:
        queries += one_round(seed)
        queries += one_extent_round(seed + 500_000)
        stores += 2
        seed += 1
        if stores % 25 == 0 or stores % 25 == 1:
            dt = time.monotonic() - t0
            print(
                f"[fuzz] {stores} store pairs, {queries} checks, "
                f"{dt:.0f}s elapsed, 0 failures",
                flush=True,
            )
    print(f"[fuzz] DONE: {stores} store pairs, {queries} checks, 0 failures")


if __name__ == "__main__":
    main()
