"""Run one benchmark cell once on the chip(s) this machine holds.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic and metrics are found by name through
``BENCHMARK.json``. Set-up makes the deployment's rows from the seed,
ingests them through ``create_schema`` + ``writer(name).write_columns``
into a ``TpuDataStore`` over ``TpuScanExecutor(default_mesh(devices))``,
and warms up the cell's own request shapes. The window then drives
``TpuDataStore.query`` from the traffic's closed-loop clients for
``--seconds``. After it, the store is freed and a sample of the finished
requests, drawn from the seed and holding the longest answer, is compared
with the plain reference (``benchmark/lib/reference.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the span exporter and the JAX profiler and prints
its per-layer metrics (``benchmark/metrics/<name>.py``). The last stdout
line is the result; the compared numbers and their limits are the last
lines on stderr. Without a TPU, or with fewer chips than the cell needs,
it exits 2 and prints no result.

``--control 1`` (never passed by the driver) runs the control instead:
the reference computed in float32 answers the window's requests in the
program's place (no store is built), and the same check judges them.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import spec as spec_mod  # noqa: E402


def log(msg: str) -> None:
    sys.stderr.write(f"[bench] {msg}\n")
    sys.stderr.flush()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = spec_mod.load(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: jax gives {devices[0].platform}; no result")
        return 2
    if len(devices) < cell.chips:
        log(f"{len(devices)} device(s), the cell needs {cell.chips}; no result")
        return 2
    from benchmark.lib.cell import run_cell

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[: cell.chips], t_start=T_START,
                      control=bool(args.control), log=log)
    checks = result["checks"]
    for name, c in checks.items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
