"""executor.device_share: the share of the traced window's finished
queries whose ``plan.scan_path`` names a device path (``device-...``),
in percent: the executor's cost chooser and push-downs, counted."""


def read(ctx):
    n = len(ctx.records)
    if not n:
        return None
    dev = sum(1 for r in ctx.records if r.scan_path.startswith("device-"))
    return 100.0 * dev / n
