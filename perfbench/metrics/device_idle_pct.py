"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, averaged over the chips."""
from perfbench.trace import busy_s


def read(ctx):
    if ctx.trace is None or ctx.trace.window is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - busy_s(ctx.trace) / ctx.trace.window_s)
