"""The whole trunk step's share of the chips' peak: trunk operations per
computed row (from the configuration's shapes) times the rows the trunk
computed in the window, over window seconds x chips x peak, in
percent."""


def read(ctx):
    rows = ctx.stats.embed_rows
    if not rows:
        return None
    flops = ctx.trunk.flops_per_row(ctx.config) * rows
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["flops_per_s"])
