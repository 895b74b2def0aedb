"""hostagg.reduce_ms: the host reducer, ms per finished query of the
traced window: self time of ``agg.reduce`` (store/datastore.py ``_finish``,
``run_aggregation`` over the scanned columns, e.g. the NumPy GridSnap of a
density the device did not answer). The column gather before it stays in
``query.assemble``."""


def read(ctx):
    n = len(ctx.records)
    if not n or "agg.reduce" not in ctx.self_ms:
        return None
    return ctx.self_ms["agg.reduce"] / n
