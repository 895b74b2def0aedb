"""density.certify_ms: the host half of the density push-down, ms per
finished query of the traced window: self time of ``density.certify``
(parallel/executor.py, the f64 decision and binning of the band rows the
device could not certify in f32)."""


def read(ctx):
    n = len(ctx.records)
    if not n or "density.certify" not in ctx.self_ms:
        return None
    return ctx.self_ms["density.certify"] / n
