"""The one traffic generator: tables and a request sequence from data.

A workload file (``bench/workloads/<name>.json``) names its tables and a
mix of request kinds; this module turns it and ``--seed`` into the rows
of every table and into an endless, deterministic sequence of
``PREDICT emb USING TASK score FROM <table> WHERE id >= a AND id < b``
requests. What a kind of request reads, and how its rows are rebuilt
for the check, lives in ``bench/kinds/<kind>.py``, found by the name in
the mix entry (see ``fresh.py`` for the functions a kind defines).

Every seed sends the same work in another order: the requests come in
cycles of ``cycle``, and each cycle holds the same multiset of kinds and
of the values each kind draws for it (sizes, window ranks). The seed
shuffles each cycle, places what a kind places, and draws every row.

A request's rows are a function of the seed and the request alone, so
the correctness check rebuilds them here without reading any table the
program was given.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from harness import spec as specs

PHASE_WINDOW, PHASE_WARM, PHASE_SETUP = 0, 1, 2
TASK = "score"


@dataclass(frozen=True)
class Request:
    phase: int
    index: int
    kind: str                 # the module under bench/kinds
    table: str
    lo: int                   # first id read
    n: int                    # rows read
    dup: float = 0.0          # share of rows repeating another row
    task: str = TASK          # the task the PREDICT names


def loguniform_quantiles(lo: int, hi: int, count: int) -> List[int]:
    """``count`` sizes at the mid-quantiles of a log-uniform law."""
    q = (np.arange(count) + 0.5) / count
    return [int(round(float(np.exp(np.log(lo) + u * (np.log(hi)
                                                    - np.log(lo))))))
            for u in q]


def largest_remainder(weights, total: int) -> List[int]:
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * total
    counts = np.floor(raw).astype(int)
    short = total - int(counts.sum())
    if short:
        counts[np.argsort(-(raw - counts), kind="stable")[:short]] += 1
    return [int(c) for c in counts]


def static_rows(seed: int, table: str, n: int, width: int) -> np.ndarray:
    rng = np.random.default_rng([seed, _table_key(table)])
    return rng.standard_normal((n, width), dtype=np.float32)


def _table_key(name: str) -> int:
    return int.from_bytes(name.encode()[:8].ljust(8, b"\0"), "little")


class Traffic:
    """Tables and requests of one workload under one seed."""

    def __init__(self, spec: dict, seed: int):
        self.seed = int(seed)
        self.tables: Dict[str, dict] = spec["tables"]
        self.width = {t: int(v["width"]) for t, v in self.tables.items()}
        self.rows = {t: int(v["rows"]) for t, v in self.tables.items()}
        self.mix: List[dict] = spec["mix"]
        self.cycle = int(spec["cycle"])
        self.kinds = [specs.kind(m["kind"]) for m in self.mix]
        counts = largest_remainder([m["weight"] for m in self.mix],
                                   self.cycle)
        layout = np.random.default_rng([self.seed, 0x3A7])
        # what each kind placed (window ranges), and the fixed multiset
        # of one cycle: (mix entry, the value its kind drew)
        self.state = [k.layout(self, m, layout)
                      for k, m in zip(self.kinds, self.mix)]
        self._cycle: List[Tuple[int, int]] = [
            (i, v) for i, (k, m, c) in enumerate(
                zip(self.kinds, self.mix, counts)) for v in k.cycle(m, c)]
        self._cursor: Dict[str, int] = {}
        self._lock = threading.RLock()
        self._next = 0
        self._order: tuple = (-1, None)
        self._static: Dict[str, np.ndarray] = {}

    # -- tables -----------------------------------------------------------
    def make_tables(self) -> Dict[str, Dict[str, np.ndarray]]:
        """``{table: {"id": int64 ids, "emb": float32 rows}}``, every row
        drawn from the seed. A kind that writes its own rows before a
        request is sent (``fresh``) writes over them."""
        return {t: {"id": np.arange(self.rows[t], dtype=np.int64),
                    "emb": self.static(t).copy()} for t in self.tables}

    def static(self, table: str) -> np.ndarray:
        """A table's rows as ``make_tables`` draws them."""
        rows = self._static.get(table)
        if rows is None:
            rows = self._static[table] = static_rows(
                self.seed, table, self.rows[table], self.width[table])
        return rows

    def claim(self, table: str, n: int) -> int:
        """The next ``n`` ids of ``table`` taken as a ring: the first id."""
        with self._lock:
            lo = self._cursor.get(table, 0)
            if lo + n > self.rows[table]:
                lo = 0
            self._cursor[table] = lo + n
            return lo

    def trunk_rows(self) -> Tuple[int, int]:
        """The fewest distinct rows and the most rows one request of the
        mix can send to the trunk in the window, over the kinds that
        send any."""
        spans = [s for s in (k.trunk_rows(m)
                             for k, m in zip(self.kinds, self.mix)) if s]
        if not spans:
            raise ValueError("no request kind of the mix reaches the trunk")
        return min(a for a, _ in spans), max(b for _, b in spans)

    # -- the request sequence ---------------------------------------------
    def next_request(self) -> Request:
        """The next request of the measured sequence (thread-safe)."""
        with self._lock:
            k = self._next
            self._next = k + 1
            c, j = divmod(k, self.cycle)
            if self._order[0] != c:
                rng = np.random.default_rng([self.seed, 0xC1C, c])
                self._order = (c, rng.permutation(self.cycle))
            i, v = self._cycle[self._order[1][j]]
            return self.kinds[i].request(self, self.mix[i], self.state[i],
                                         v, k)

    def rows_of(self, req: Request) -> np.ndarray:
        """The rows a request reads, rebuilt from the seed."""
        return specs.kind(req.kind).rows(self, req)

    def write(self, tables, req: Request) -> None:
        """Put into the table what the request's kind writes before the
        request is sent (nothing for most kinds)."""
        specs.kind(req.kind).write(self, tables, req)

    @staticmethod
    def sql(req: Request) -> str:
        return (f"PREDICT emb USING TASK {req.task} FROM {req.table} "
                f"WHERE id >= {req.lo} AND id < {req.lo + req.n}")


def reference(traffic: Traffic, trunk_mod, trunk,
              reqs: List[Request]) -> List[np.ndarray]:
    """The plain reference's answer to each request: the trunk module's
    reference over the rows, unless the requests' kind gives its own
    (``reference(traffic, trunk_mod, trunk, reqs)``, as a kind that names
    other tasks than the trunk's own head would)."""
    out: Dict[int, np.ndarray] = {}
    for kind in sorted({r.kind for r in reqs}):
        part = [(i, r) for i, r in enumerate(reqs) if r.kind == kind]
        mod = specs.kind(kind)
        own = getattr(mod, "reference", None)
        if own is not None:
            answers = own(traffic, trunk_mod, trunk, [r for _, r in part])
        else:
            X = np.concatenate([traffic.rows_of(r) for _, r in part])
            scores = trunk_mod.reference_scores(trunk, X)
            bounds = np.cumsum([0] + [r.n for _, r in part])
            answers = [scores[a:b] for a, b in zip(bounds, bounds[1:])]
        out.update({i: a for (i, _), a in zip(part, answers)})
    return [out[i] for i in range(len(reqs))]

