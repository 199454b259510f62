"""The lane step's own host time a batch: the program's ``lane.step``
span less its direct children (stack, share lookup, dedup, the trunk
call, scatter, share insert, head), over the window's steps, in ms."""

CHILDREN = ("lane.stack", "share.lookup", "lane.dedup", "backend.run_infer",
            "lane.scatter", "share.insert", "lane.head")


def read(ctx):
    sec = getattr(ctx.stats, "span_seconds", None)
    steps = (getattr(ctx.stats, "span_calls", None) or {}).get("lane.step")
    if not sec or not steps:
        return None
    own = sec["lane.step"] - sum(sec.get(c, 0.0) for c in CHILDREN)
    return own * 1e3 / steps
