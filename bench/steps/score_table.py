"""``score_table``: score the whole of ``table`` once, in requests of
the largest warmed trunk size from ``clients`` threads, so that the
window's re-reads of it hit the share cache."""
from __future__ import annotations

from harness.serving import Setup, serve_all
from traffic.generator import PHASE_SETUP, Request


def chunks(total: int, largest: int):
    """Split ``total`` rows into near-equal parts of at most ``largest``."""
    q = -(-total // largest)
    base, extra = divmod(total, q)
    return [base + (1 if i < extra else 0) for i in range(q)]


def run(setup: Setup, step: dict) -> str:
    t = step["table"]
    n = setup.traffic.rows[t]
    reqs, lo = [], 0
    for i, c in enumerate(chunks(n, setup.state["trunk_sizes"][-1])):
        reqs.append(Request(PHASE_SETUP, i, "window", t, lo, c))
        lo += c
    serve_all(setup.eng, setup.traffic, reqs, int(step.get("clients", 1)))
    return f"scored {t} ({n} rows) in {len(reqs)} requests"
