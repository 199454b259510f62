"""``warm_trunk``: one request of new rows for each trunk call size the
window can make, so that nothing compiles inside it.

The sizes are every power of two from the smallest distinct-row count of
a request of the mix to the largest coalesced batch: the lane's row
budget, less one, plus the largest request. New rows are written into
``table`` (a ring) by the ``fresh`` kind. Leaves ``trunk_sizes``
(smallest first) in the set-up's state.

A set-up step module defines ``run(setup, step) -> str`` (what it did,
for the log) and may define ``after_window(setup, step) -> str``.
"""
from __future__ import annotations

from harness import spec as specs
from harness.serving import Setup, pow2, serve_one
from traffic.generator import PHASE_WARM


def run(setup: Setup, step: dict) -> str:
    eng, traffic = setup.eng, setup.traffic
    fresh = specs.kind("fresh")
    table = step["table"]
    fewest, most = traffic.trunk_rows()
    sizes = [pow2(fewest)]
    serve_one(eng, traffic, fresh.new_rows(traffic, table, PHASE_WARM, 0,
                                           sizes[0]))
    lane_rows = max(h["batch_rows"] for h in eng.server.health().values())
    hi = pow2(lane_rows - 1 + most)
    while sizes[-1] * 2 <= hi:
        sizes.append(sizes[-1] * 2)
        serve_one(eng, traffic, fresh.new_rows(traffic, table, PHASE_WARM,
                                               len(sizes) - 1, sizes[-1]))
    setup.state["trunk_sizes"] = sizes
    return (f"warm-up: one request of each trunk size {sizes} (lane row "
            f"budget {lane_rows})")
