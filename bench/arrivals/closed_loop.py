"""``closed_loop``: ``clients`` threads, each sending its next request
when its last one came back, with no think time (callers that wait for
their answer). Each request is timed from ``submit`` to its result.

An arrival driver defines ``drive(setup, wl, seconds, spans, clock) ->
Window``: it opens the window, resets the server's telemetry and turns
the spans on at the open, and returns when every request it sent has
come back.
"""
from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

from harness import trace as tr
from harness.serving import Done, Setup, Spans, Window
from traffic.generator import Traffic


def drive(setup: Setup, wl: dict, seconds: float, spans: Spans,
          clock) -> Window:
    import jax
    eng, traffic = setup.eng, setup.traffic
    done: List[Done] = []
    lags: List[float] = []
    lock = threading.Lock()
    go = threading.Event()
    bounds = {}

    def client():
        go.wait()
        t_close = bounds["close"]
        last = bounds["open"]
        while time.perf_counter() < t_close:
            req = traffic.next_request()
            traffic.write(eng.tables, req)
            t0 = time.perf_counter()
            lag = t0 - last
            try:
                with jax.profiler.TraceAnnotation("submit"):
                    rid = eng.server.submit(Traffic.sql(req))
                spans.add("submit", time.perf_counter() - t0)
                res = eng.server.result(rid, timeout=600.0)
                d = Done(req, t0, time.perf_counter(),
                         np.asarray(res.scores))
            except Exception as e:       # counted as failed
                d = Done(req, t0, time.perf_counter(), None, repr(e))
            last = d.t_done
            with lock:
                done.append(d)
                lags.append(lag)

    threads = [threading.Thread(target=client)
               for _ in range(int(wl["clients"]))]
    for t in threads:
        t.start()
    eng.server.reset_telemetry()
    c0 = clock.count
    spans.on = True
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        t_open = time.perf_counter()
        bounds.update(open=t_open, close=t_open + seconds)
        go.set()
        time.sleep(max(0.0, bounds["close"] - time.perf_counter()))
        t_close = time.perf_counter()
    spans.on = False
    stats = eng.server.stats()
    compiles = clock.count - c0
    for t in threads:
        t.join(timeout=660.0)
        if t.is_alive():
            raise RuntimeError("a client did not finish within 660 s "
                               "of the close")
    drain = time.perf_counter() - t_close
    return Window(done, t_open, t_close, stats, compiles, lags, drain)
