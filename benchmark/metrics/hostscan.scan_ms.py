"""hostscan.scan_ms: self time of the scan spans (``scan``, ``scan.block``,
``scan.post_filter`` in store/datastore.py, around the host seek of
native/ and ``_HostSeekScan``), ms per finished query of the traced
window."""

NAMES = ("scan", "scan.block", "scan.post_filter")


def read(ctx):
    n = len(ctx.records)
    got = [ctx.self_ms[k] for k in NAMES if k in ctx.self_ms]
    if not n or not got:
        return None
    return sum(got) / n
