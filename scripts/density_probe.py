"""One-off TPU measurement of the density kernel editions at suite shape
(N=8M, 256x128 grid): scatter-XLA vs matmul (bf16 MXU) vs sort vs pallas.
Prints one JSON line per edition. Run it on the chip, alone in its
process (a chip belongs to one process at a time); it refuses to run
without a TPU.

Usage: python scripts/density_probe.py [N]
"""

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import jax

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"error": "no TPU: density_probe measures the chip only"}))
        return 1
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 8_000_000
    backend = jax.default_backend()
    print(json.dumps({"backend": backend, "n": n}), flush=True)

    from geomesa_tpu.ops.aggregations import make_sharded_density
    from geomesa_tpu.parallel.mesh import default_mesh
    from geomesa_tpu.parallel.executor import _pow2_at_least

    mesh = default_mesh()
    rng = np.random.default_rng(12)
    npad = _pow2_at_least(n, 1 << 13)
    x = np.zeros(npad, np.float32)
    y = np.zeros(npad, np.float32)
    x[:n] = rng.uniform(-180, 180, n)
    y[:n] = rng.uniform(-85, 85, n)
    valid = np.zeros(npad, bool)
    valid[:n] = True
    boxes = np.array([[-60, -30, 60, 40]], np.float32)
    env = np.array([-60, -30, 60, 40], np.float32)

    from geomesa_tpu.parallel.mesh import shard_array, replicate

    xd = shard_array(mesh, x)
    yd = shard_array(mesh, y)
    vd = shard_array(mesh, valid)
    bd = replicate(mesh, boxes)
    ed = replicate(mesh, env)

    want = None
    for mode in ("xla", "xla_matmul", "xla_sort", "pallas"):
        try:
            fns = make_sharded_density(mesh, 256, 128, mode)
            t0 = time.perf_counter()
            g = np.asarray(fns[1](xd, yd, vd, bd, ed))
            compile_s = time.perf_counter() - t0
            reps = 5
            t0 = time.perf_counter()
            for _ in range(reps):
                g = fns[1](xd, yd, vd, bd, ed)
            g.block_until_ready()
            dt = (time.perf_counter() - t0) / reps
            gn = np.asarray(g)
            ok = want is None or np.abs(gn - want).sum() <= 64
            if mode == "xla":
                want = gn
            print(json.dumps({
                "mode": mode, "ms": round(dt * 1000, 2),
                "compile_s": round(compile_s, 1),
                "sum": float(gn.sum()), "parity": bool(ok),
            }), flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps(
                {"mode": mode, "error": f"{type(e).__name__}: {str(e)[:160]}"}
            ), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
