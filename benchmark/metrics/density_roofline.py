"""density_roofline: the density kernel's share of its roofline, in percent.

least time / kernel time, where the kernel time is the summed device
duration of the density programs in the traced window, and the least time
is the least bytes of every device-answered density request of the window
(``benchmark/counts/density.py``, counted from the query and the data)
over the chip's HBM bandwidth (``benchmark/peaks.json``). Bandwidth-bound
by definition: a density request needs about one operation per row.
"""

import importlib

# the density programs as the trace names them: the jitted shard_map
# ``step`` and ``step_no_time`` of ops/aggregations.make_sharded_density_dual
# (band, time mask and the Pallas ``_density_run`` kernel in one XLA module
# each). A named scope in the program (a later tracing PR) would give them
# names of their own.
PREFIXES = ("jit_step(", "jit_step_no_time(")


def kernel_seconds(module_seconds):
    return sum(s for name, s in module_seconds.items() if name.startswith(PREFIXES))


def read(ctx):
    if ctx.device is None or ctx.peaks is None or ctx.reference is None:
        return None
    t = kernel_seconds(ctx.device.module_seconds)
    if t <= 0:
        return None
    counts = importlib.import_module("benchmark.counts.density")
    total = 0
    for r in ctx.records:
        if r.scan_path != "device-density":
            continue
        # the rows inside the request's envelope and window, by the reference
        rows = ctx.reference.count(r.req)
        total += counts.least_bytes(rows, r.req.params["width"], r.req.params["height"])
    if total <= 0:
        return None
    return 100.0 * (total / ctx.peaks["hbm_bytes_per_s"]) / t
