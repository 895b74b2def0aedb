"""Chip smoke: the store's z3 query path on one TPU, end to end.

One process, no child that touches JAX, no fallback. Through the store's
own entry points (``TpuDataStore`` over ``TpuScanExecutor(default_mesh())``):

  1. ingest 20M GDELT-shaped rows (``bench.synthesize``, dtg + point, z3
     index) from ``--seed`` through ``create_schema`` and the writer;
  2. answer the jittered bbox+DURING stream of ``bench.make_queries(20)``
     twice with ``query_many``: cost-chosen, then with ``GEOMESA_SEEK=0``
     so the device scan answers — every result set equal to the NumPy
     brute force (``bench.brute_force``);
  3. answer one 512x512 density aggregate (auto kernel edition) over a
     second 8M-row type from the same seed, cell for cell equal to the
     host reducer.

It fails (non-zero exit, no result line) on any parity mismatch, on any
query the host answered in the device's place (``degrade.device_to_host``),
on an open device breaker, when the mask mode is not the Pallas one, when
a result of the device-forced leg has no ``device-*`` scan path or the
leg's devstats receipt shows no ``d2h_bytes``, or when the density grid
did not come from the edition the auto mode chose. The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

``--chips 4`` runs only the multi-chip phase: the same 20M-row stream with
``GEOMESA_SEEK=0`` on a 4-device ``default_mesh()`` (mode ``pallas_spmd``),
rows on all four devices, parity with the brute force.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402
from geomesa_tpu.index.planner import Query  # noqa: E402
from geomesa_tpu.parallel import TpuScanExecutor, default_mesh  # noqa: E402
from geomesa_tpu.parallel import executor as executor_mod  # noqa: E402
from geomesa_tpu.schema.featuretype import parse_spec  # noqa: E402
from geomesa_tpu.store.datastore import TpuDataStore  # noqa: E402
from geomesa_tpu.utils.audit import robustness_metrics  # noqa: E402
from geomesa_tpu.utils.config import properties  # noqa: E402
from geomesa_tpu.utils import devstats  # noqa: E402

SPEC = "dtg:Date,*geom:Point:srid=4326"
ROWS = 20_000_000  # the headline's size: ~0.6 GB of segment mirror
DENSITY_ROWS = 8_000_000  # the suite's density size
GRID = 512
QUERIES = 20
# a world heatmap of three days of events: the band of rows within f32
# error of a cell edge stays well inside the kernel's per-shard band
# buffer at 8M rows (ops/aggregations.DENSITY_BAND_CAP)
DENSITY_ENV = (-180.0, -90.0, 180.0, 90.0)
DENSITY_DURING = "dtg DURING 2026-01-05T00:00:00Z/2026-01-08T00:00:00Z"
DENSITY_T = (
    np.datetime64("2026-01-05T00:00:00", "ms").astype(np.int64),
    np.datetime64("2026-01-08T00:00:00", "ms").astype(np.int64),
)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


@contextlib.contextmanager
def env(**values):
    saved = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def ingest(store: TpuDataStore, name: str, x, y, t) -> np.ndarray:
    """create_schema + the writer's columnar path; returns the fids."""
    n = len(x)
    store.create_schema(parse_spec(name, SPEC))
    fids = np.char.add("f", np.arange(n).astype(f"<U{len(str(max(n - 1, 1)))}"))
    t0 = time.perf_counter()
    store.writer(name).write_columns(
        {"__fid__": fids, "geom__x": x, "geom__y": y, "dtg": t}
    )
    say(f"ingest_{name}", f"{n} rows in {time.perf_counter() - t0:.3f} s")
    return fids


def _segment_caps(store: TpuDataStore):
    return {
        id(s): (s._rcap, s._span_cap, s._shard_span_cap)
        for _ref, dev in store.executor._cache.values()
        for s in dev.segments
    }


def phase_stream(store: TpuDataStore, name: str, x, y, t, fids,
                 n_queries: int, legs=("cost_chosen", "device")) -> dict:
    """The jittered bbox+DURING stream through query_many, once per leg,
    every result set checked against the brute force."""
    boxes, cqls = bench.make_queries(n_queries)
    wants = [np.sort(fids[bench.brute_force(x, y, t, b)]) for b in boxes]
    queries = [Query.cql(c, properties=[]) for c in cqls]
    out = {}
    for leg in legs:
        device = leg == "device"
        with env(GEOMESA_SEEK="0" if device else "auto"):
            # warm until the adaptive capacities stop changing: a changed
            # capacity keys a fresh compile, which belongs to set-up
            t0 = time.perf_counter()
            prev = None
            for _ in range(4):
                store.query_many(name, queries)
                caps = _segment_caps(store)
                if caps == prev:
                    break
                prev = caps
            warm_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            with devstats.collecting() as receipt:
                results = store.query_many(name, queries)
            per_query_ms = (time.perf_counter() - t0) * 1000.0 / len(queries)
        for i, (res, want) in enumerate(zip(results, wants)):
            got = np.sort(np.asarray(res.fids, dtype=fids.dtype))
            check(np.array_equal(got, want),
                  f"{leg} leg: query {i} returned {len(got)} rows, "
                  f"brute force {len(want)}")
        paths = sorted({res.plan.scan_path for res in results})
        if device:
            check(all(p.startswith("device-") for p in paths),
                  f"device leg answered by {paths}")
            check(receipt.get("d2h_bytes", 0) > 0, "device leg read nothing back")
        out[leg] = {
            "warm_s": warm_s,
            "ms_per_query": per_query_ms,
            "scan_paths": paths,
            "d2h_bytes": receipt.get("d2h_bytes", 0),
            "hits_first_query": int(len(wants[0])),
        }
        say(f"stream_{leg}", json.dumps(out[leg]))
    return out


def phase_density(store: TpuDataStore, name: str, x, y, t, grid: int) -> dict:
    """One grid x grid density aggregate, auto kernel edition, checked
    cell for cell against the host reducer on the same store."""
    b = DENSITY_ENV
    cql = f"bbox(geom, {b[0]}, {b[1]}, {b[2]}, {b[3]}) AND {DENSITY_DURING}"
    q = Query.cql(cql, hints={"density": {"envelope": b, "width": grid, "height": grid}})
    executor = store.executor
    chosen = executor_mod._mask_mode(executor.mesh)
    # the aggregate cache would answer a repeat from memory: off, so the
    # device grid and the host grid are both computed here
    with properties(geomesa_agg_enabled="false"):
        t0 = time.perf_counter()
        res = store.query(name, q)
        first_s = time.perf_counter() - t0
        check(res.plan.scan_path == "device-density",
              f"density answered by {res.plan.scan_path!r}, not the device")
        ran = {key[2] for key in executor._density_fns}
        check(ran == {chosen},
              f"density ran edition(s) {sorted(ran)}, auto chose {chosen!r}")
        t0 = time.perf_counter()
        again = store.query(name, q)
        warm_ms = (time.perf_counter() - t0) * 1000.0
        with env(GEOMESA_DENSITY_DEVICE="0"):
            host = store.query(name, q)
        check(host.plan.scan_path != "device-density", "host reducer did not run")
    dev_grid = np.asarray(res.aggregate["density"])
    host_grid = np.asarray(host.aggregate["density"])
    check(dev_grid.shape == (grid, grid), f"density grid shape {dev_grid.shape}")
    check(np.array_equal(dev_grid, host_grid),
          f"density grid differs from the host reducer in "
          f"{int((dev_grid != host_grid).sum())} cells")
    check(np.array_equal(np.asarray(again.aggregate["density"]), dev_grid),
          "repeated density call returned another grid")
    rows = int(((t > DENSITY_T[0]) & (t < DENSITY_T[1])).sum())
    check(int(dev_grid.sum()) == rows,
          f"density grid counts {int(dev_grid.sum())} rows, brute force {rows}")
    out = {"edition": chosen, "first_call_s": first_s, "warm_ms": warm_ms,
           "rows_counted": rows}
    say("density", json.dumps(out))
    return out


def degraded() -> int:
    return robustness_metrics().counter("degrade.device_to_host")


def check_device_answered(store: TpuDataStore, want_mode: str,
                          degraded_before: int) -> None:
    executor = store.executor
    n = degraded() - degraded_before
    check(n == 0, f"degrade.device_to_host = {n}")
    check(executor.breaker.state == "closed",
          f"device breaker is {executor.breaker.state}")
    mode = executor_mod._mask_mode(executor.mesh)
    check(mode == want_mode, f"mask mode {mode!r}, want {want_mode!r}")


def rows_on_devices(store: TpuDataStore, name: str) -> int:
    """Distinct devices holding rows of the z3 segment mirror."""
    table = store._tables[name]["z3"]
    devices = set()
    for seg in store.executor.device_index(table).segments:
        for shard in seg.valid.addressable_shards:
            if shard.data.shape[0] > 0:
                devices.add(shard.device)
    return len(devices)


def native_kernels() -> dict:
    from geomesa_tpu import native

    return {
        name: getattr(native, f"load_{name}")() is not None
        for name in ("seek", "env_seek", "xz", "bitdecode")
    } | {"zranges": native.load() is not None}


def peak_hbm() -> dict:
    import jax

    return {
        str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    }


def run_one_chip(mesh, seed: int, rows: int = ROWS,
                 density_rows: int = DENSITY_ROWS, grid: int = GRID,
                 queries: int = QUERIES) -> None:
    before = degraded()
    store = TpuDataStore(executor=TpuScanExecutor(mesh))
    x, y, t = bench.synthesize(rows, seed)
    fids = ingest(store, "gdelt", x, y, t)
    phase_stream(store, "gdelt", x, y, t, fids, queries)
    check_device_answered(store, "pallas", before)
    dx, dy, dt = bench.synthesize(density_rows, seed)
    ingest(store, "gdelt_density", dx, dy, dt)
    phase_density(store, "gdelt_density", dx, dy, dt, grid)
    check_device_answered(store, "pallas", before)


def run_four_chips(mesh, seed: int, rows: int = ROWS,
                   queries: int = QUERIES) -> None:
    before = degraded()
    store = TpuDataStore(executor=TpuScanExecutor(mesh))
    x, y, t = bench.synthesize(rows, seed)
    fids = ingest(store, "gdelt", x, y, t)
    phase_stream(store, "gdelt", x, y, t, fids, queries, legs=("device",))
    check_device_answered(store, "pallas_spmd", before)
    on = rows_on_devices(store, "gdelt")
    say("devices_holding_rows", on)
    check(on == mesh.devices.size, f"rows on {on} of {mesh.devices.size} devices")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=13)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    import jax

    from geomesa_tpu.parallel.mesh import enable_compile_cache
    from geomesa_tpu.utils.malloc import retain_freed_memory

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax gives {devices[0].platform})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {len(devices)} device(s), --chips {args.chips}",
              file=sys.stderr)
        return 2
    say("compile_cache", enable_compile_cache())
    retain_freed_memory()
    mesh = default_mesh(devices[: args.chips])
    say("device_kind", devices[0].device_kind)
    say("devices", f"{len(devices)} present, mesh of {mesh.devices.size}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(mesh, args.seed)
        else:
            run_one_chip(mesh, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say("native_host_kernels", json.dumps(native_kernels()))
    say("peak_hbm_bytes", json.dumps(peak_hbm()))
    say("wall_s", f"{time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
