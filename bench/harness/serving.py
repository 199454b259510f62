"""What the harness's modules share about the engine under test: the
engine a cell builds, the host spans it keeps, the answers it gets back,
and the calls that send set-up requests through ``MorphingServer``."""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from traffic.generator import Request, Traffic


def pow2(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


@dataclass
class Spans:
    """Host spans of the calls the harness makes or wraps, kept only
    while ``on`` (the window)."""
    on: bool = False
    lock: threading.Lock = field(default_factory=threading.Lock)
    seconds: Dict[str, List[float]] = field(
        default_factory=lambda: {"submit": [], "run_infer": [], "head": []})
    trunk_rows: List[int] = field(default_factory=list)

    def add(self, name: str, dt: float, rows: Optional[int] = None) -> None:
        if self.on:
            with self.lock:
                self.seconds[name].append(dt)
                if rows is not None:
                    self.trunk_rows.append(rows)


def wrap_backend(backend, spans: Spans) -> None:
    """Time the lane backend's trunk and head entry points."""
    import jax
    run_infer, run_head = backend.run_infer, backend.run_head

    def timed_infer(spec, batch):
        with jax.profiler.TraceAnnotation("run_infer"):
            t0 = time.perf_counter()
            out = run_infer(spec, batch)
            spans.add("run_infer", time.perf_counter() - t0,
                      len(batch[spec.col]))
        return out

    def timed_head(spec, F):
        with jax.profiler.TraceAnnotation("head"):
            t0 = time.perf_counter()
            out = run_head(spec, F)
            spans.add("head", time.perf_counter() - t0)
        return out

    backend.run_infer, backend.run_head = timed_infer, timed_head


@dataclass
class Done:
    req: Request
    t_submit: float
    t_done: float
    scores: Optional[np.ndarray]     # None: the request failed
    error: Optional[str] = None


@dataclass
class Engine:
    session: object
    server: object
    backend: object
    tables: dict
    trunk: object


@dataclass
class Setup:
    """What set-up steps and arrival drivers are handed: the engine, the
    traffic, and what earlier steps left for later ones (``state``)."""
    eng: Engine
    traffic: Traffic
    state: dict = field(default_factory=dict)


@dataclass
class Window:
    done: List[Done]
    t_open: float
    t_close: float
    stats: object                    # ServerStats at the close
    compiles: int
    lags: List[float]                # client gaps: result -> next submit
    in_flight_s: float               # drain after the close


def serve_one(eng: Engine, traffic: Traffic, req: Request) -> np.ndarray:
    traffic.write(eng.tables, req)
    return np.asarray(eng.server.predict(Traffic.sql(req),
                                         timeout=600.0).scores)


def misses_of(eng: Engine, traffic: Traffic, req: Request) -> int:
    """Serve one request alone; the rows the share cache did not hold."""
    before = eng.server.stats().share_misses
    serve_one(eng, traffic, req)
    return eng.server.stats().share_misses - before


def serve_all(eng: Engine, traffic: Traffic, reqs: List[Request],
              clients: int) -> None:
    """Serve ``reqs`` from ``clients`` threads (set-up passes)."""
    it = iter(reqs)
    lock = threading.Lock()
    errors: List[BaseException] = []

    def client():
        while not errors:
            with lock:
                req = next(it, None)
            if req is None:
                return
            try:
                serve_one(eng, traffic, req)
            except BaseException as e:          # re-raised below
                errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
