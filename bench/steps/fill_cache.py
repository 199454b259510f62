"""``fill_cache``: send new rows until the share cache has evicted rows
it held, so that the window starts from a cache at its capacity, in the
state that cold traffic keeps it in.

The cache's own behaviour says when. The fill goes in rounds of
``clients`` requests of new rows of the largest warmed trunk size,
written into the ring ``table``. The first rows of each round's first
request (as many as the smallest warmed trunk size) are that round's
marker. After each round marker 0 is read again, alone, and the
server's miss counter says whether the cache still holds it; the read
sorts what the round put in, as the next round's first lookup would.
When it missed, the cache has evicted, and the step looks for the
oldest marker the cache still holds (rounds go in in order, so those it
holds are the newest); that marker's rows are among the oldest the
cache holds when the window opens. ``after_window`` reads it again: a
miss there means the cache evicted inside the window.

A cache that evicts by recency rather than by age keeps marker 0, which
is read every round; the fill then stops after twice the rows that the
cache's capacity holds of the embeddings alone (``share_capacity_bytes``
over the float32 bytes of one), when any cache that keeps them has
evicted, and eviction inside the window goes unobserved.
"""
from __future__ import annotations

from dataclasses import replace

from harness import spec as specs
from harness.serving import Setup, misses_of, serve_all
from traffic.generator import PHASE_SETUP, Request


def _marker(setup: Setup, rnd: int) -> Request:
    """The first rows of round ``rnd``'s first request: a ``fresh``
    request draws its rows in order, so a shorter request of the same
    index reads the same first rows."""
    return replace(setup.state["fill_requests"][rnd],
                   n=setup.state["trunk_sizes"][0])


def run(setup: Setup, step: dict) -> str:
    eng, traffic, st = setup.eng, setup.traffic, setup.state
    fresh = specs.kind("fresh")
    table, largest = step["table"], st["trunk_sizes"][-1]
    clients = min(int(step.get("clients", 1)),
                  fresh.ring_clients(traffic, table, largest))
    limit = 2 * eng.session.config.share_capacity_bytes // (
        4 * eng.trunk.width)
    st["fill_requests"] = firsts = []
    sent = 0
    while True:
        reqs = [fresh.new_rows(traffic, table, PHASE_SETUP,
                               len(firsts) * clients + i, largest)
                for i in range(clients)]
        firsts.append(reqs[0])
        serve_all(eng, traffic, reqs, clients)
        sent += clients * largest
        if len(firsts) > 1 and misses_of(eng, traffic, _marker(setup, 0)):
            break
        if sent >= limit:
            st["open_marker"] = None
            return (f"fill: {sent} new rows in {len(firsts)} rounds of "
                    f"{clients} x {largest}; the cache kept marker 0 (it "
                    "evicts by recency), so eviction inside the window is "
                    "not observed")
    # the last round's marker is held: find the oldest held one
    lo, hi = 1, len(firsts) - 1
    while lo < hi:
        mid = (lo + hi) // 2
        if misses_of(eng, traffic, _marker(setup, mid)):
            lo = mid + 1
        else:
            hi = mid
    st["open_marker"] = _marker(setup, lo)
    held = (len(firsts) - lo) * clients * largest
    return (f"fill: the cache evicted within {sent} new rows "
            f"({len(firsts)} rounds of {clients} x {largest}); the oldest "
            f"round it holds is round {lo}: it holds about {held} of the "
            "fill's rows when the window opens")


def after_window(setup: Setup, step: dict) -> str:
    m = setup.state.get("open_marker")
    if m is None:
        return "share cache evicted inside the window: not observed"
    evicted = misses_of(setup.eng, setup.traffic, m) > 0
    return ("share cache evicted inside the window: "
            + ("yes" if evicted else "no"))
