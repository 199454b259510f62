"""Share of the window's trunk-lane rows served from the share cache
(``ServerStats.share_hit_rate``), in percent."""


def read(ctx):
    st = ctx.stats
    if st.share_hits + st.share_misses + st.approx_hits == 0:
        return None
    return 100.0 * float(st.share_hit_rate)
