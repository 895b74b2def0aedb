"""planner.plan_ms: self time of the planner's spans (``plan`` and
``plan.range_decomposition`` in index/planner.py, ``query.plan`` in
store/datastore.py), ms per finished query of the traced window."""

NAMES = ("plan", "plan.range_decomposition", "query.plan")


def read(ctx):
    n = len(ctx.records)
    got = [ctx.self_ms[k] for k in NAMES if k in ctx.self_ms]
    if not n or not got:
        return None
    return sum(got) / n
