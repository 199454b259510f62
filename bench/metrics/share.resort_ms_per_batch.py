"""Host ms a lane batch spends re-sorting the share cache's fingerprints
(the program's ``share.resort`` span) over the window's lane steps. The
run's notes get the cache's state over the window: rows held at the
close, re-sorts and rows re-sorted, buffer grows and bytes copied,
evictions and rows evicted."""


def read(ctx):
    st = ctx.stats
    sec = getattr(st, "span_seconds", None)
    calls = getattr(st, "span_calls", None) or {}
    steps = calls.get("lane.step")
    if not sec or not steps:
        return None
    counts = st.counts
    ctx.notes.append(
        f"share cache in the window: {st.share_rows_held} rows held at the "
        f"close; {calls.get('share.resort', 0)} re-sorts of "
        f"{counts.get('share.resort_rows', 0)} rows; "
        f"{calls.get('share.grow', 0)} grows copying "
        f"{counts.get('share.grow_bytes', 0)} bytes; "
        f"{calls.get('share.evict', 0)} evictions of "
        f"{counts.get('share.evicted_rows', 0)} rows")
    return sec.get("share.resort", 0.0) * 1e3 / steps
