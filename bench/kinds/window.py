"""``window``: a request re-reads one of ``windows`` fixed ranges of a
static table, chosen by Zipf popularity (exponent ``zipf_s``).

Window sizes are log-uniform over ``rows.lo`` to ``rows.hi`` and the
same for every seed (the fixed ``layout_seed`` pairs popularity ranks
with sizes); the seed places the windows in the table. Each cycle holds
every rank in proportion to its Zipf weight. The window's rows reach the
trunk only until a set-up step (``score_table``) has scored the table,
so the kind reports none for the warm-up.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from traffic.generator import (PHASE_WINDOW, Request, Traffic,
                               largest_remainder, loguniform_quantiles)

KIND = "window"


def cycle(m: dict, count: int) -> List[int]:
    """The popularity ranks of one cycle's ``count`` requests."""
    nw = int(m["windows"])
    per_rank = largest_remainder(
        np.arange(1, nw + 1, dtype=np.float64) ** -float(m["zipf_s"]), count)
    return [r for r, k in enumerate(per_rank) for _ in range(k)]


def layout(traffic: Traffic, m: dict, rng) -> List[Tuple[int, int]]:
    """(first id, rows) of the window at each popularity rank."""
    nw = int(m["windows"])
    sizes = loguniform_quantiles(m["rows"]["lo"], m["rows"]["hi"], nw)
    order = np.random.default_rng(int(m["layout_seed"])).permutation(nw)
    n_tab = traffic.rows[m["table"]]
    return [(int(rng.integers(0, n_tab - sizes[j] + 1)), sizes[j])
            for j in order]


def request(traffic: Traffic, m: dict, state, rank: int,
            index: int) -> Request:
    lo, n = state[rank]
    return Request(PHASE_WINDOW, index, KIND, m["table"], lo, n)


def rows(traffic: Traffic, req: Request) -> np.ndarray:
    return traffic.static(req.table)[req.lo:req.lo + req.n]


def write(traffic: Traffic, tables, req: Request) -> None:
    return None


def trunk_rows(m: dict) -> Optional[Tuple[int, int]]:
    return None
