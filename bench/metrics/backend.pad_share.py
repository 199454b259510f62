"""Share of the trunk's padded rows that are padding: the program's
counters ``backend.bucket_rows`` (rows of each call's power-of-two
bucket) and ``backend.rows`` (the rows asked for), over the window, in
percent."""


def read(ctx):
    counts = getattr(ctx.stats, "counts", None) or {}
    padded = counts.get("backend.bucket_rows")
    if not padded:
        return None
    return 100.0 * (padded - counts.get("backend.rows", 0)) / padded
