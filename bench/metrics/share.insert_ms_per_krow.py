"""Host ms of the share cache's insert (the program's ``share.insert``
span: the lookup of what is present, buffer growth, eviction) per 1000
rows handed to it (``share.insert_rows``), over the window."""


def read(ctx):
    sec = getattr(ctx.stats, "span_seconds", None)
    rows = (getattr(ctx.stats, "counts", None) or {}).get("share.insert_rows")
    if not sec or not rows:
        return None
    return sec.get("share.insert", 0.0) * 1e6 / rows
