"""Median host-clock time of ``MorphingServer.submit`` (parse, plan, the
row filter and the row snapshot), over the window's requests."""
import statistics


def read(ctx):
    s = ctx.spans.seconds["submit"]
    return statistics.median(s) * 1e3 if s else None
