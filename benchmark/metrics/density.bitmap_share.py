"""density.bitmap_share: the share of the traced window's density
push-downs whose band outgrew its per-shard index buffer and came back as a
packed bitmap, in percent: 100 x ``density`` spans (parallel/executor.py
``density_scan``) whose ``band`` is ``bitmap``, over all ``density`` spans.
A program whose ``density`` spans carry no ``band`` gives nothing."""


def read(ctx):
    spans = [sp for root in ctx.spans for sp in root.walk() if sp.name == "density"]
    if not any("band" in sp.attributes for sp in spans):
        return None
    return 100.0 * sum(1 for sp in spans if sp.attributes.get("band") == "bitmap") / len(spans)
