"""Host ms of the share cache's lookup (fingerprints, binary search,
gather; the program's ``share.lookup`` span less its ``share.resort``)
per 1000 rows looked up (``share.lookup_rows``), over the window."""


def read(ctx):
    sec = getattr(ctx.stats, "span_seconds", None)
    rows = (getattr(ctx.stats, "counts", None) or {}).get("share.lookup_rows")
    if not sec or not rows:
        return None
    own = sec.get("share.lookup", 0.0) - sec.get("share.resort", 0.0)
    return own * 1e6 / rows
