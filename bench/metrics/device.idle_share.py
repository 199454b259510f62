"""Share of the traced window in which no op ran on a device, averaged
over the cell's chips: 1 - (union of op intervals) / window, in
percent."""
from harness import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    busy = tr.device_busy_s(ctx.trace)
    w = ctx.trace.window_s
    return 100.0 * (1.0 - sum(busy) / len(busy) / w)
