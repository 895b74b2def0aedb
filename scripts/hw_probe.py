"""One-shot primitive timings on the chip (run alone in its process).

Measures the building blocks the batched-scan protocols choose between,
at headline scale (20M rows), so protocol decisions ride measurements
instead of guesses:

  mask         streaming exact limb mask (the lower bound)
  nonzero      size-bounded jnp.nonzero at rcap=131072 (runs extraction)
  sort         lax.sort of 20M i32 (sort-based compaction alternative)
  span_bounds  fused iota min/max framing (executor._span_bounds)
  packbits     bitmap pack (bitmap protocol device side)
  cumsum       prefix sum (scatter-compaction alternative)
  d2h_4m/h2d_4m  link bandwidth on a 4 MB buffer
  exec_floor   empty-ish execution round trip
  batch_bitmap end-to-end _exact_bitmap_batch_fn, q=20

Writes chiprun_out/HW_PRIMS.json and prints one JSON line. Refuses to
run without a TPU.
Timings are medians of 3 after a warmup run; each fn is jitted first.
"""

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N = int(os.environ.get("HW_PROBE_N", 20_000_000))
Q = 20
RCAP = 131072


def median3(f):
    f()  # warm (compile + first run)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        f()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[1]


def flush(out: dict) -> None:
    """Persist after EVERY measurement: a run cut at its time limit still
    leaves the numbers gathered so far on disk."""
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "HW_PRIMS.json"), "w") as f:
        json.dump(out, f, indent=1)


def main():
    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    dev = jax.devices()[0].platform
    if dev != "tpu":
        raise SystemExit(f"{dev} backend; hw_probe times the chip only")
    out = {"backend": backend, "platform": dev, "n": N}

    rng = np.random.default_rng(0)
    m_host = rng.random(N) < 0.05
    m = jax.device_put(m_host)
    x = jax.device_put(rng.integers(0, 2**31, N).astype(np.int32))

    cmp_fn = jax.jit(lambda a: (a < 12345).sum())
    out["mask_ms"] = median3(lambda: cmp_fn(x).block_until_ready()) * 1e3
    flush(out)

    nz = jax.jit(lambda a: jnp.nonzero(a, size=RCAP, fill_value=N)[0])
    out["nonzero_ms"] = median3(lambda: nz(m).block_until_ready()) * 1e3
    flush(out)

    srt = jax.jit(lambda a: jax.lax.sort(a))
    out["sort_ms"] = median3(lambda: srt(x).block_until_ready()) * 1e3
    flush(out)

    # the ACTUAL span framing (executor._span_bounds): fused iota-select
    # min/max — measured instead of the argmax pair it replaced
    def spanb(a):
        idx = jnp.arange(a.shape[0], dtype=jnp.int32)
        return (
            jnp.min(jnp.where(a, idx, jnp.int32(a.shape[0]))),
            jnp.max(jnp.where(a, idx, jnp.int32(-1))),
        )

    sb = jax.jit(spanb)
    out["span_bounds_ms"] = median3(
        lambda: jax.block_until_ready(sb(m))
    ) * 1e3
    flush(out)

    pb = jax.jit(lambda a: jnp.packbits(a))
    out["packbits_ms"] = median3(lambda: pb(m).block_until_ready()) * 1e3
    flush(out)

    cs = jax.jit(lambda a: jnp.cumsum(a.astype(jnp.int32)))
    out["cumsum_ms"] = median3(lambda: cs(m).block_until_ready()) * 1e3
    flush(out)

    big = jax.device_put(np.zeros(1 << 20, np.int32))  # 4 MB
    idn = jax.jit(lambda a: a + 1)
    idn(big).block_until_ready()
    # fresh output per call: jax.Array caches its host value after the
    # first np.asarray, which would turn repeats into cache hits
    out["d2h_4m_ms"] = median3(lambda: np.asarray(idn(big))) * 1e3
    flush(out)
    host4 = np.zeros(1 << 20, np.int32)
    out["h2d_4m_ms"] = median3(
        lambda: jax.device_put(host4).block_until_ready()
    ) * 1e3
    flush(out)
    tiny = jax.device_put(np.zeros(8, np.int32))
    out["exec_floor_ms"] = median3(
        lambda: np.asarray(idn(tiny))
    ) * 1e3
    flush(out)

    # end-to-end batch kernels on a realistic z3 segment
    from geomesa_tpu.parallel import executor as ex
    from geomesa_tpu.parallel.mesh import default_mesh, replicate

    mesh = default_mesh()
    mode = "spmd" if ex._mask_mode(mesh) == "pallas_spmd" else "local"

    def limb(hi):
        # hi limbs carry the sign-clear top bit pattern real sort keys
        # have; lo limbs span the full u32 range
        bound = 2**31 if hi else 2**32
        return jax.device_put(
            rng.integers(0, bound, N, dtype=np.uint64).astype(np.uint32)
        )

    xh, xl, yh, yl = limb(1), limb(0), limb(1), limb(0)
    valid = jax.device_put(np.ones(N, bool))
    boxes = replicate(mesh, rng.integers(0, 2**31, (Q, 8)).astype(np.uint32))

    span = 1 << 23  # 8M-row window (1 MB bitmap/query)
    bm_fn = ex._exact_bitmap_batch_fn(False, min(span, N - N % 8), Q, mode, mesh)
    def run_bm():
        h, b = bm_fn(xh, xl, yh, yl, valid, boxes)
        np.asarray(h)
        np.asarray(b)
    out["batch_bitmap_ms"] = median3(run_bm) * 1e3
    flush(out)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
