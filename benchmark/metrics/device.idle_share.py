"""device.idle_share: 100 x (1 - busy / window), where busy is the union
of the intervals in which an XLA op ran on the device in the profiler's
trace of the window (averaged over the chips used)."""


def read(ctx):
    dev = ctx.device
    if dev is None or dev.window_s <= 0:
        return None
    return 100.0 * (1.0 - dev.busy_s / dev.window_s)
