"""density.overflow_share: the share of the traced window's density
push-downs whose band overflowed its per-shard buffer, in percent: 100 x
``density`` spans (parallel/executor.py ``density_scan``) whose ``outcome``
is ``band_overflow``, over all ``density`` spans. Each overflow throws the
device's grid away and the host answers the request again."""


def read(ctx):
    outcomes = [sp.attributes.get("outcome")
                for root in ctx.spans for sp in root.walk() if sp.name == "density"]
    if not outcomes:
        return None
    return 100.0 * sum(1 for o in outcomes if o == "band_overflow") / len(outcomes)
