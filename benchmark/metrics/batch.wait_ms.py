"""batch.wait_ms: the coalescer's queue time, ms per finished query of the
traced window: self time of ``batch.gather`` (a leader's window wait for
joiners) and ``batch.wait`` (a member blocked until the leader hands back
its outcome) in parallel/batch.py."""

NAMES = ("batch.gather", "batch.wait")


def read(ctx):
    n = len(ctx.records)
    got = [ctx.self_ms[k] for k in NAMES if k in ctx.self_ms]
    if not n or not got:
        return None
    return sum(got) / n
