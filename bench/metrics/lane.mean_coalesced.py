"""Requests fused per executed lane batch over the window
(``ServerStats.mean_coalesced``, telemetry reset at the window's
open)."""


def read(ctx):
    return float(ctx.stats.mean_coalesced) if ctx.stats.batches else None
