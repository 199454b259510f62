"""``fresh``: a request scores rows that no earlier request contained.

The rows are drawn anew from the seed and written over the range the
request reads just before it is sent; ranges walk the table as a ring,
so the table keeps its size. ``dup_share`` of a request's rows repeat
other rows of the same request (the same event delivered twice), which
single-flight dedup folds. Sizes are log-uniform over ``rows.lo`` to
``rows.hi``.

A kind module defines ``cycle``, ``layout``, ``request``, ``rows``,
``write`` and ``trunk_rows``; it may define ``reference`` (see
``traffic.generator.reference``). This one also makes the new-row
requests that set-up steps send (``new_rows``).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from traffic.generator import (PHASE_WINDOW, Request, Traffic,
                               loguniform_quantiles)

KIND = "fresh"


def cycle(m: dict, count: int) -> List[int]:
    """The sizes of one cycle's ``count`` requests of this entry."""
    return loguniform_quantiles(m["rows"]["lo"], m["rows"]["hi"], count)


def layout(traffic: Traffic, m: dict, rng) -> None:
    return None


def request(traffic: Traffic, m: dict, state, n: int,
            index: int) -> Request:
    dup = float(m["dup_share"])
    return Request(PHASE_WINDOW, index, KIND, m["table"],
                   traffic.claim(m["table"], n), n, dup)


def new_rows(traffic: Traffic, table: str, phase: int, index: int,
             n: int) -> Request:
    """A request of ``n`` distinct new rows outside the measured
    sequence (warm-up and set-up)."""
    return Request(phase, index, KIND, table, traffic.claim(table, n), n)


def rows(traffic: Traffic, req: Request) -> np.ndarray:
    """``req.n`` new rows, of which ``round(n * dup)`` repeat others."""
    rng = np.random.default_rng([traffic.seed, req.phase, req.index, 1])
    out = rng.standard_normal((req.n, traffic.width[req.table]),
                              dtype=np.float32)
    m = int(round(req.n * req.dup))
    if m:
        idx = rng.permutation(req.n)[:2 * m]
        out[idx[m:]] = out[idx[:m]]
    return out


def write(traffic: Traffic, tables, req: Request) -> None:
    tables[req.table]["emb"][req.lo:req.lo + req.n] = rows(traffic, req)


def trunk_rows(m: dict) -> Optional[Tuple[int, int]]:
    lo = int(m["rows"]["lo"])
    return lo - int(round(lo * float(m["dup_share"]))), int(m["rows"]["hi"])


def ring_clients(traffic: Traffic, table: str, n: int) -> int:
    """Concurrent requests of ``n`` new rows that the table's ring holds
    without a range being written over before it is read."""
    return max(1, traffic.rows[table] // max(n, 1) - 1)
