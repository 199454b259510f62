"""One cell, once: build the engine, set up, measure, check.

The system under test is the engine's public serving surface: a
``MorphingSession`` on the decoupled store with the trunk pinned to the
accelerator (``EngineConfig(devices=("tpu",), device_count=chips)``), and
a ``MorphingServer`` whose ``submit`` every request goes through. The
harness makes the trunk and the tables from the seed, hands them to the
engine as a user would (``dstore.save`` + ``resolve_task(model_id=)``,
``register_table``), and reads only the server's public counters.

The workload names its set-up steps (``bench/steps``) and its arrival
driver (``bench/arrivals``); around the calls they make or wrap
(``submit``, the lane backend's ``run_infer`` and ``run_head``) the
harness keeps host spans on its own clock and as
``jax.profiler.TraceAnnotation`` for the traced run. After the window it
sends the last requests it answered once more, alone, so that the share
cache serves them, and compares those answers too.
"""
from __future__ import annotations

import gc
import statistics
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from harness import spec as specs
from harness import trace as tr
from harness.clock import CompileClock
from harness.serving import (Done, Engine, Setup, Spans, Window, serve_one,
                             wrap_backend)
from traffic.generator import TASK, Traffic, reference

MODEL_ID = "bench-trunk"


def build(cfg: dict, traffic: Traffic, chips: int, root: Path, seed: int,
          engine_overrides: Optional[dict] = None) -> Engine:
    from repro.engine import EngineConfig, MorphingServer, MorphingSession
    tm = specs.trunk_module(cfg)
    trunk, Xs, ys = tm.build(cfg, seed)
    ecfg = EngineConfig(model_store="decoupled", devices=("tpu",),
                        device_count=chips, **(engine_overrides or {}))
    sess = MorphingSession(root=root, config=ecfg)
    tables = traffic.make_tables()
    for name, tab in tables.items():
        sess.register_table(name, tab)
    arch, params = tm.store_layers(trunk, MODEL_ID)
    sess.dstore.save(MODEL_ID, arch, params, task_types=["classification"],
                     modality="series")
    sess.sql(f"CREATE TASK {TASK} (INPUT=Series, OUTPUT IN ('POS','NEG'), "
             "TYPE='Classification');")
    sess.resolve_task(TASK, Xs, ys, model_id=MODEL_ID)
    server = MorphingServer(session=sess)
    server.start()
    return Engine(sess, server, sess.backends["tpu"], tables, trunk)


def resend(eng: Engine, traffic: Traffic, done: List[Done],
           count: int) -> Tuple[List[Done], int]:
    """Send the last ``count`` answered requests again, one at a time,
    after the window: the share cache holds their rows, so these answers
    come from it. Returns them and the rows the cache missed."""
    answered = sorted((d for d in done if d.scores is not None),
                      key=lambda d: d.t_done)[-count:] if count else []
    out: List[Done] = []
    before = eng.server.stats().share_misses
    for d in answered:
        t0 = time.perf_counter()
        try:
            scores = serve_one(eng, traffic, d.req)
            out.append(Done(d.req, t0, time.perf_counter(), scores))
        except Exception as e:           # counted as failed
            out.append(Done(d.req, t0, time.perf_counter(), None, repr(e)))
    return out, eng.server.stats().share_misses - before


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


def sample(done: List[Done], seed: int, n_check: int) -> List[Done]:
    """``n_check`` answers drawn from the seed, and the largest."""
    answered = [d for d in done if d.scores is not None]
    if not answered:
        return []
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = {max(range(len(answered)), key=lambda i: answered[i].req.n)}
    k = min(n_check, len(answered))
    pick.update(int(i) for i in rng.choice(len(answered), size=k,
                                           replace=False))
    return [answered[i] for i in sorted(pick)]


def check(traffic: Traffic, trunk_mod, trunk, answers: List[Done],
          failed: int, limit: float, missed: int = 0):
    """Compare ``answers`` with the plain reference computed from rows
    rebuilt from the seed. Returns the numbers compared, each beside its
    limit, and how many rows were checked."""
    answers = [d for d in answers if d.scores is not None]
    want_all = reference(traffic, trunk_mod, trunk,
                         [d.req for d in answers]) if answers else []
    gap, mismatched, rows = 0.0, 0, 0
    for d, want in zip(answers, want_all):
        got = np.asarray(d.scores, np.float32).reshape(-1)
        if got.shape != want.shape or not np.isfinite(got).all():
            mismatched += 1
            continue
        rows += len(got)
        gap = max(gap, float(np.abs(got - want).max(initial=0.0)))
    # no answer checked at all is as wrong as a wrong answer
    compared = {"score_gap": {"value": gap if answers else 1.0e308,
                              "limit": limit},
                "wrong_row_count": {"value": mismatched, "limit": 0},
                "failed_requests": {"value": failed, "limit": 0},
                "resent_rows_missed": {"value": missed, "limit": 0}}
    return compared, rows


def passed(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def prepare(setup: Setup, wl: dict, clock: CompileClock,
            log: Callable[[str], None]) -> None:
    """The workload's set-up steps, each with its seconds."""
    for step in wl["setup"]:
        t0 = time.perf_counter()
        text = specs.step(step["step"]).run(setup, step)
        log(f"set-up {step['step']}: {time.perf_counter() - t0:.3f} s; "
            f"{text}")
    eng = setup.eng
    log(f"lane: backend={eng.backend.name} "
        f"interpret={getattr(eng.backend, 'interpret', None)} "
        f"devices={eng.session.device_count}; compile {clock.seconds:.3f} s "
        f"over {clock.count} executables, {clock.cache_hits} "
        "persistent-cache hits")


def after_window(setup: Setup, wl: dict) -> List[str]:
    """What the set-up steps read once the window has closed."""
    out = []
    for step in wl["setup"]:
        fn = getattr(specs.step(step["step"]), "after_window", None)
        if fn is not None:
            out.append(fn(setup, step))
    return out


def log_window(win: Window, in_window: List[Done],
               log: Callable[[str], None]) -> None:
    st = win.stats
    rows = sum(d.req.n for d in in_window)
    log(f"window: {win.t_close - win.t_open:.3f} s, {len(win.done)} "
        f"requests sent, {len(in_window)} completed inside it ({rows} "
        f"rows), {win.in_flight_s:.3f} s to drain the rest; share hit rate "
        f"{st.share_hit_rate:.6f} ({st.share_hits} hits, {st.share_misses} "
        f"misses, {st.dedup_rows} folded by dedup, {st.embed_rows} trunk "
        f"rows); compiles inside the window: {win.compiles}")
    if win.lags:
        log(f"clients: gap from a result to the next submit median "
            f"{statistics.median(win.lags) * 1e3:.3f} ms, max "
            f"{max(win.lags) * 1e3:.3f} ms")
    if in_window:
        lat = [d.t_done - d.t_submit for d in in_window]
        q = np.percentile(lat, [5, 25, 50, 75, 90, 95, 99, 100]) * 1e3
        slow = sorted(in_window, key=lambda d: d.t_submit - d.t_done)[:5]
        log("latency ms p5/25/50/75/90/95/99/max "
            + "/".join(f"{v:.1f}" for v in q) + "; slowest (sent at s, ms, "
            "rows): " + ", ".join(
                f"({d.t_submit - win.t_open:.2f}, "
                f"{(d.t_done - d.t_submit) * 1e3:.0f}, {d.req.n})"
                for d in slow))
    failed = [d.error for d in win.done if d.scores is None]
    if failed:
        log(f"failed: {len(failed)} requests; the first: {failed[0]}")


def end_to_end(in_window: List[Done], window_s: float,
               setup_s: float) -> Dict[str, float]:
    """The cell's end-to-end numbers, on the host clock."""
    out = {"setup_s": setup_s}
    if in_window:
        lat = np.array([d.t_done - d.t_submit for d in in_window])
        out.update(rows_per_s=sum(d.req.n for d in in_window) / window_s,
                   p50_ms=float(np.percentile(lat, 50)) * 1e3,
                   p95_ms=float(np.percentile(lat, 95)) * 1e3)
    return out


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             traced: bool, t_start: float, log: Callable[[str], None],
             peaks: dict, *,
             workload_overrides: Optional[dict] = None,
             engine_overrides: Optional[dict] = None,
             break_path: Optional[Callable[[Engine], None]] = None
             ) -> dict:
    """Run one cell once and return the result line's fields. Tests
    shrink the cell through the overrides and break the timed path with
    ``break_path``; the benchmark's runs pass none of them."""
    import jax
    c = specs.cell(bench, cell_name)
    cfg = specs.config(bench, c["config"])
    wl = {**specs.workload(c["traffic"]), **(workload_overrides or {})}
    chips = int(c["chips"])
    devices = jax.devices()[:chips]
    traffic = Traffic(wl, seed)
    clock = CompileClock()
    spans = Spans()
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        t0 = time.perf_counter()
        eng = build(cfg, traffic, chips, Path(tmp) / "engine", seed,
                    engine_overrides)
        wrap_backend(eng.backend, spans)
        log(f"engine: {time.perf_counter() - t0:.3f} s; trunk "
            f"{cfg['mode']} x{cfg['width']}")
        setup = Setup(eng, traffic)
        prepare(setup, wl, clock, log)
        if break_path is not None:
            break_path(eng)
        trace_dir = Path(tmp) / "trace"
        if traced:
            jax.profiler.start_trace(str(trace_dir))
        win = specs.arrivals(wl["arrivals"]).drive(setup, wl, seconds,
                                                   spans, clock)
        if traced:
            jax.profiler.stop_trace()
        peak = memory_peak(devices)
        for line in after_window(setup, wl):
            log(line)
        resent, missed = resend(eng, traffic, win.done,
                                int(wl["resend_requests"]))
        eng.server.stop()
        del eng, setup
        gc.collect()
        in_window = [d for d in win.done
                     if d.scores is not None and d.t_done <= win.t_close]
        log_window(win, in_window, log)
        log(f"resent: {len(resent)} requests after the window, "
            f"{sum(d.req.n for d in resent)} rows, {missed} missed by the "
            "share cache")
        t_check = time.perf_counter()
        # the reference rebuilds the trunk from the seed: it takes no
        # weight the engine was handed or made
        tm = specs.trunk_module(cfg)
        answers = sample(win.done, seed, int(wl["check_requests"])) + resent
        failed = sum(1 for d in win.done if d.scores is None)
        compared, n_rows = check(
            traffic, tm, tm.build(cfg, seed)[0], answers,
            failed + sum(1 for d in resent if d.scores is None),
            float(cfg["check"]["score_gap_limit"]), missed)
        log(f"check: {len(answers)} answers, {n_rows} rows against the "
            f"reference in {time.perf_counter() - t_check:.3f} s")
        result = {"correct": passed(compared),
                  "attempted": len(win.done),
                  "failed": failed,
                  "metrics": {},
                  "device": {"platform": devices[0].platform,
                             "kind": devices[0].device_kind,
                             "count": len(jax.devices()),
                             "memory_peak_bytes": peak}}
        window_s = win.t_close - win.t_open
        if traced:
            ctx = Context(cell=c, config=cfg, trunk=tm, stats=win.stats,
                          window_s=window_s, chips=chips, peaks=peaks,
                          spans=spans,
                          trace=tr.load(str(trace_dir), devices=chips))
            busy = tr.device_busy_s(ctx.trace)
            result["device"]["busy_s"] = sum(busy) / len(busy) if busy \
                else 0.0
            result["device"]["window_s"] = ctx.trace.window_s
            values = {m["name"]: specs.metric_reader(m["name"])(ctx)
                      for m in specs.metrics_for(bench, cell_name,
                                                 "per_layer")}
            result["breakdown"] = tr.breakdown(ctx.trace)
            for line in ctx.notes:
                log(line)
            kind = "per_layer"
        else:
            values = end_to_end(in_window, window_s, win.t_open - t_start)
            kind = "end_to_end"
        for m in specs.metrics_for(bench, cell_name, kind):
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
        result["compared"] = compared
    return result


@dataclass
class Context:
    """What a metric reader may read."""
    cell: dict
    config: dict
    trunk: object                    # the configuration's trunk module
    stats: object                    # ServerStats over the window
    window_s: float
    chips: int
    peaks: dict
    spans: Spans
    trace: Optional[tr.Trace]
    notes: List[str] = field(default_factory=list)
