"""Median wait of a request in its lane's queue, from its arrival to the
moment the lane worker popped it (``ServerStats.p50_queue_wait_s``),
over the window, in ms."""


def read(ctx):
    wait = getattr(ctx.stats, "p50_queue_wait_s", None)
    if wait is None or not ctx.stats.batches:
        return None
    return wait * 1e3
