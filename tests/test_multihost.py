"""Multi-host (DCN) mesh: two OS processes form ONE global device mesh.

The reference scales batch compute by adding Spark executors over the
database's RPC fabric (AccumuloSpatialRDDProvider); here the fabric is
``jax.distributed`` — each process contributes 4 virtual CPU devices, the
global mesh spans all 8, and the sharded query step's collectives ride the
inter-process transport (Gloo on CPU; ICI/DCN on real pods). The worker runs
the SAME fused z3 query step the driver compile-checks (__graft_entry__):
rows sharded over the global 'data' axis, global hit count via psum.

Infrastructure failures (port clash, distributed init not available) skip;
a parity mismatch between the global count and the summed host-local
oracles FAILS.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import sys

import numpy as np

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
port = sys.argv[3]

from geomesa_tpu.parallel.mesh import DATA_AXIS, multihost_mesh

mesh = multihost_mesh(f"127.0.0.1:{port}", nproc, pid)

import jax
import jax.numpy as jnp
from jax.experimental.multihost_utils import host_local_array_to_global_array
from jax.sharding import PartitionSpec as P

assert len(jax.devices()) == 8, len(jax.devices())
print("INIT-OK", flush=True)

import __graft_entry__ as graft

n_local = 4096  # rows contributed by THIS process
xi, yi, bins, offs, valid, boxes, windows = graft._example_batch(
    n=n_local, seed=100 + pid
)
gargs = [
    host_local_array_to_global_array(a, mesh, P(DATA_AXIS))
    for a in (xi, yi, bins, offs, valid)
]

fwd = jax.jit(graft._forward)
mask, count, checksum = fwd(*gargs, boxes, windows)
# host-local oracle for THIS process' rows (numpy reference of the mask)
in_box = (
    (xi >= boxes[0, 0]) & (xi <= boxes[0, 2])
    & (yi >= boxes[0, 1]) & (yi <= boxes[0, 3])
)
in_win = np.zeros(n_local, dtype=bool)
for b, lo, hi in windows:
    in_win |= (bins == b) & (offs >= lo) & (offs <= hi)
local = int(np.sum(in_box & in_win & valid))
print(f"RESULT {pid} {int(count)} {local}", flush=True)
"""


NPROC = 2

# VERDICT r4 #5: the SHIPPED executor (TpuScanExecutor.query_many, bitmap
# proto, per-shard extraction) across the two-process global mesh — the
# DCN analog of dryrun_multichip's 8-device leg. Each process ingests the
# IDENTICAL store; rows shard over the global 'data' axis; each process
# extracts hits for ITS OWN shards (per-executor partials, the Spark
# partition contract of GeoMesaSpark.scala:38-50); the test unions the
# per-process fid sets against a host-oracle store.
_EXEC_WORKER = r"""
import os
import sys

import numpy as np

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
port = sys.argv[3]

from geomesa_tpu.parallel.mesh import multihost_mesh

mesh = multihost_mesh(f"127.0.0.1:{port}", nproc, pid)

import jax

assert len(jax.devices()) == 8, len(jax.devices())
print("INIT-OK", flush=True)

# DEFAULT multi-device dispatch path (no proto/extract overrides): the
# mesh-aware auto must pick bitmap + per-shard extraction by itself
os.environ.update({
    "GEOMESA_SEEK": "0", "GEOMESA_DEVBATCH": "1", "GEOMESA_EXACT_DEVICE": "1",
})

from geomesa_tpu.parallel import TpuScanExecutor
from geomesa_tpu.parallel.mesh import default_mesh
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.store.datastore import TpuDataStore

rng = np.random.default_rng(42)  # SAME data in every process
n = 20_000
x = rng.uniform(-80, 80, n)
y = rng.uniform(-70, 70, n)
base = np.datetime64("2026-05-01", "ms").astype(np.int64)
t = base + rng.integers(0, 10 * 86400_000, n)
store = TpuDataStore(
    executor=TpuScanExecutor(default_mesh(list(mesh.devices.ravel())))
)
ft = parse_spec("t", "dtg:Date,*geom:Point:srid=4326")
store.create_schema(ft)
fids = np.char.add("f", np.arange(n).astype("<U5"))
store._insert_columns(
    ft, {"__fid__": fids, "geom__x": x, "geom__y": y, "dtg": t}
)
cqls = [
    "bbox(geom, -30, -20, 20, 25)",
    "bbox(geom, 0, 0, 60, 50)",
    "bbox(geom, -10, -40, 45, 5) AND "
    "dtg DURING 2026-05-02T00:00:00Z/2026-05-08T00:00:00Z",
    "bbox(geom, -60, -30, 10, 40) AND "
    "dtg DURING 2026-05-03T00:00:00Z/2026-05-09T00:00:00Z",
]
results = store.query_many("t", cqls)
for qi, res in enumerate(results):
    print(f"RESULT {pid} {qi} " + ",".join(sorted(map(str, res.fids))),
          flush=True)

# round 2: crush every segment's learned span window so each shard's hit
# span overflows -> the single-query REFETCH fallback, whose replicated
# (global) rows each process must filter to ITS OWN shards (the
# overflow edition of the per-partition contract)
table = store._tables["t"]["z2"]
dev = store.executor.device_index(table)
for seg in dev.segments:
    seg._span_cap = 8
    seg._shard_span_cap = 8
results = store.query_many("t", cqls[:2])
for qi, res in enumerate(results):
    print(f"OVERFLOW {pid} {qi} " + ",".join(sorted(map(str, res.fids))),
          flush=True)
print("DONE", flush=True)
"""


def _run_workers(tmp_path, script, port_base):
    port = port_base + (os.getpid() % 400)
    worker = tmp_path / "worker.py"
    worker.write_text(script)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=REPO,
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), str(NPROC), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=REPO,
        )
        for pid in range(NPROC)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("distributed init timed out (infra)")
    return outs


def test_two_process_query_many_shipped_executor(tmp_path):
    outs = _run_workers(tmp_path, _EXEC_WORKER, 9100)
    done = [o for o in outs if "DONE" in o]
    if len(done) != NPROC:
        missing = [o for o in outs if "DONE" not in o]
        tails = "\n---\n".join(o[-1500:] for o in missing)
        if any("INIT-OK" in o for o in missing):
            pytest.fail(f"worker died after mesh init:\n{tails}")
        pytest.skip(f"distributed init failed (infra):\n{tails}")
    # reassemble per-process partials (normal + crushed-span overflow)
    per_query = {}
    overflow = {}
    for out in outs:
        for line in out.splitlines():
            for tag, dest in (("RESULT ", per_query), ("OVERFLOW ", overflow)):
                if line.startswith(tag):
                    _, pid, qi, fid_csv = (line.split(" ", 3) + [""])[:4]
                    fset = set(fid_csv.split(",")) - {""}
                    dest.setdefault(int(qi), {})[int(pid)] = fset
    assert len(per_query) == 4
    assert len(overflow) == 2

    # host oracle on the same synthetic data
    rng = np.random.default_rng(42)
    n = 20_000
    x = rng.uniform(-80, 80, n)
    y = rng.uniform(-70, 70, n)
    base = np.datetime64("2026-05-01", "ms").astype(np.int64)
    t = base + rng.integers(0, 10 * 86400_000, n)

    def want(b, t0=None, t1=None):
        m = (x >= b[0]) & (x <= b[2]) & (y >= b[1]) & (y <= b[3])
        if t0 is not None:
            lo = np.datetime64(t0, "ms").astype(np.int64)
            hi = np.datetime64(t1, "ms").astype(np.int64)
            m &= (t > lo) & (t < hi)
        return {f"f{i}" for i in np.flatnonzero(m)}

    oracles = [
        want((-30, -20, 20, 25)),
        want((0, 0, 60, 50)),
        want((-10, -40, 45, 5), "2026-05-02", "2026-05-08"),
        want((-60, -30, 10, 40), "2026-05-03", "2026-05-09"),
    ]
    for qi, oracle in enumerate(oracles):
        parts = per_query[qi]
        assert len(parts) == NPROC
        union = set().union(*parts.values())
        overlap = set.intersection(*parts.values())
        assert union == oracle, (
            f"query {qi}: union {len(union)} != oracle {len(oracle)}"
        )
        # every row lives on exactly one shard -> no cross-process overlap
        assert not overlap, f"query {qi}: {len(overlap)} dup fids"

    # crushed-span round: every shard window overflowed into the
    # replicated single-query refetch, which each process must filter to
    # its OWN shards — union still exact, still no double counting
    for qi in overflow:
        parts = overflow[qi]
        assert len(parts) == NPROC
        assert set().union(*parts.values()) == oracles[qi], f"overflow {qi}"
        assert not set.intersection(*parts.values()), f"overflow dup {qi}"


def test_two_process_global_mesh_query_step(tmp_path):
    nproc = NPROC
    port = 9500 + (os.getpid() % 400)
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=REPO,
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), str(nproc), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=REPO,
        )
        for pid in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("distributed init timed out (infra)")
    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                _, pid, g, loc = line.split()
                results[int(pid)] = (int(g), int(loc))
    if len(results) != nproc:
        # Only INIT-phase failures (coordinator bind/connect, gloo missing)
        # may skip — the worker prints INIT-OK once the mesh is wired, so a
        # crash after that point is a product bug and must FAIL.
        missing = [outs[i] for i in range(nproc) if i not in results]
        tails = "\n---\n".join(o[-600:] for o in missing)
        if any("INIT-OK" in o for o in missing):
            pytest.fail(f"worker died after mesh init:\n{tails}")
        pytest.skip(f"distributed init failed (infra):\n{tails}")
    global_counts = {g for g, _ in results.values()}
    assert len(global_counts) == 1, results  # every process sees ONE answer
    want = sum(loc for _, loc in results.values())
    assert global_counts.pop() == want, results
