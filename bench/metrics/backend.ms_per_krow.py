"""Host-clock milliseconds inside the lane backend's ``run_infer`` (pad,
host to device, trunk, device to host), summed over the window, per
1000 rows the trunk computed (``ServerStats.embed_rows``)."""


def read(ctx):
    rows = ctx.stats.embed_rows
    t = ctx.spans.seconds["run_infer"]
    if not rows or not t:
        return None
    return sum(t) * 1e3 / (rows / 1e3)
