"""Overload + chaos benchmark for the SLO-aware admission layer.

Three legs against the same mixed-tenant ``PREDICT`` workload
(interactive requests with deadlines, batch, and best-effort bulk):

1. **sustainable** — closed-loop: the server's sustainable request rate
   with the admission policy attached (this calibrates the overload leg,
   so the bench adapts to the machine instead of hardcoding a rate);
2. **overload** — open-loop submission at ``OVERLOAD_X`` (2x) the
   sustainable rate. Graceful degradation is the contract: interactive
   p95 must hold within its SLO bound while best-effort is the class
   that degrades (sheds via typed ``Rejected`` backpressure) — both
   asserted in-bench;
3. **chaos** — a ``FaultInjector`` kills >= ``CHAOS_ERROR_RATE`` (5%+)
   of trunk batches. Failed batches surface as ``RequestError`` on
   exactly their requests; every non-injected request must match the
   fault-free engine answer (parity), and the same server keeps serving
   afterwards — no restart.

The share cache is disabled for this bench: every request pays real
trunk work, so saturation (and therefore backpressure) is genuine
rather than an artifact of cache-hit traffic.

Run directly for machine-readable output::

    PYTHONPATH=src:. python benchmarks/bench_overload.py \
        --json BENCH_overload.json

``BENCH_overload.json`` is gated by ``scripts/check_bench.py``
(``docs/benchmarks.md`` documents the schema and baseline protocol:
median run for throughput floors, max-of-3 for the p95 ceiling).
``--smoke`` shrinks the workload for CI.
"""
from __future__ import annotations

import argparse
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from benchmarks.common import emit_value
from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.device import enable_compile_cache
from repro.engine import MorphingServer, MorphingSession
from repro.pipeline import AdmissionPolicy, Rejected, RequestError
from repro.training.fault import FaultInjector, InjectedFault

N_ROWS = 2000
TRUNK_WIDTH = 160                # heavy enough that trunk work is real
N_CALIBRATE = 48                 # closed-loop requests for leg 1
N_OVERLOAD = 96                  # open-loop requests for leg 2
N_CHAOS = 48                     # closed-loop requests for leg 3
CONCURRENCY = 8
OVERLOAD_X = 2.0                 # offered load vs sustainable
CHAOS_ERROR_RATE = 0.10          # >= 5% of batches killed
# interactive SLO: a multiple of the *unloaded* interactive p95 — the
# contract is "overload does not blow up the premium tail", not an
# absolute number that would flake across machines
SLO_FACTOR = 10.0
SLO_FLOOR_MS = 50.0
# below this the statistical asserts are recorded but not enforced
# (tiny smoke runs don't have enough samples for stable percentiles)
MIN_REQUESTS_FOR_ASSERT = 64


# -- workload ---------------------------------------------------------------

def _setup(n_rows: int):
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=160, dim=16, classes=3)
    zoo = [pretrain_model(src, width=TRUNK_WIDTH, seed=1, name="ovl-m0")]
    rng = np.random.default_rng(0)
    table = {"len": rng.integers(1, 200, n_rows),
             "emb": rng.standard_normal((n_rows, 16)).astype(np.float32)}
    sample = make_task(rng, "gauss", n=128, dim=16, classes=3)
    return zoo, table, sample


def _make_session(zoo, table, sample):
    # share cache off: every request pays trunk compute, so the
    # sustainable rate (and the overload above it) is real work
    sess = MorphingSession(zoo=zoo, model_store="decoupled",
                           backend="numpy", enable_share=False)
    sess.register_table("reviews",
                        {k: v.copy() for k, v in table.items()})
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0
    sess.resolve_task("sent", sample.X, sample.y)
    return sess


def _mixed_requests(n: int, slo_ms: float):
    """(sql, priority, deadline_ms) mix: 25% interactive over small row
    windows with the SLO deadline, 25% batch, 50% best-effort bulk."""
    reqs = []
    for i in range(n):
        r = i % 4
        if r == 0:
            reqs.append((f"PREDICT emb USING TASK sent FROM reviews "
                         f"WHERE len > {170 + (i % 8)}",
                         "interactive", slo_ms))
        elif r == 1:
            reqs.append((f"PREDICT emb USING TASK sent FROM reviews "
                         f"WHERE len > {100 + (i % 8)}", "batch", None))
        else:
            reqs.append((f"PREDICT emb USING TASK sent FROM reviews "
                         f"WHERE len > {20 + (i % 8)}",
                         "best_effort", None))
    return reqs


def _rows_of(sess, sql: str) -> int:
    thr = int(sql.rsplit(">", 1)[1])
    return int((sess.tables["reviews"]["len"] > thr).sum())


def _policy(rows_per_be_request: int) -> AdmissionPolicy:
    # best-effort may hold ~1.5 bulk requests of queued rows and batch
    # ~1.7, together below the total cap: interactive always has
    # admission headroom, so under overload best-effort is the class
    # that sheds (typed Rejected) while interactive keeps its SLO
    return AdmissionPolicy(
        max_queue_rows=rows_per_be_request * 4,
        per_priority_rows={
            "best_effort": int(rows_per_be_request * 1.5),
            "batch": int(rows_per_be_request * 1.7),
        },
        mode="reject", retry_limit=1, retry_backoff_s=0.005,
        breaker_threshold=50, min_batch_rows=64)


# -- legs -------------------------------------------------------------------

def leg_sustainable(server, reqs, concurrency):
    """Closed loop: measures what the server can actually sustain.
    Clients honor backpressure — a Rejected submit backs off and
    retries, as a well-behaved closed-loop client would."""
    def one(r):
        sql, prio, dl = r
        while True:
            try:
                return server.predict(sql, timeout=60.0, priority=prio,
                                      deadline_ms=dl)
            except Rejected:
                time.sleep(0.005)

    with ThreadPoolExecutor(concurrency) as pool:
        list(pool.map(one, reqs[:concurrency]))          # warm
        server.reset_telemetry()
        t0 = time.perf_counter()
        list(pool.map(one, reqs))
        wall = time.perf_counter() - t0
    st = server.stats()
    return wall, st


def leg_overload(server, reqs, offered_rps: float, concurrency):
    """Open loop at ``offered_rps``: a pacer thread submits on schedule
    regardless of completions (rejections don't slow the offered load);
    a collector pool blocks on results."""
    outcomes = {"ok": [], "rejected": [], "failed": []}
    lock = threading.Lock()
    rows_ok = 0
    interval = 1.0 / max(offered_rps, 1e-6)

    def collect(rid, r):
        nonlocal rows_ok
        sql, prio, _ = r
        try:
            out = server.result(rid, timeout=120.0)
            with lock:
                outcomes["ok"].append((prio, sql))
                rows_ok += out.rows
        except RequestError:
            with lock:
                outcomes["failed"].append((prio, sql))

    t0 = time.perf_counter()
    with ThreadPoolExecutor(concurrency) as pool:
        for i, r in enumerate(reqs):
            target = t0 + i * interval
            delay = target - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            sql, prio, dl = r
            try:
                rid = server.submit(sql, priority=prio, deadline_ms=dl)
            except Rejected:
                with lock:
                    outcomes["rejected"].append((prio, sql))
                continue
            pool.submit(collect, rid, r)
    wall = time.perf_counter() - t0
    st = server.stats()
    return wall, rows_ok, outcomes, st


def leg_chaos(server, sess, reqs, ref, error_rate: float, concurrency):
    """Closed loop with a FaultInjector killing batches. Returns
    (ok, failed, injector). Scripted kills on trunk calls 1 and 2
    guarantee at least one batch exhausts its retry (the lane serializes
    batches, so the call-1 batch retries *as* call 2) on top of the
    probabilistic error_rate."""
    fi = FaultInjector(error_rate=error_rate, scripted_errors={1, 2},
                       seed=11)
    sess.backends.set_fault_injector(fi)
    ok, failed = [], []
    lock = threading.Lock()

    def one(r):
        sql, prio, dl = r
        try:
            while True:
                try:
                    out = server.predict(sql, timeout=60.0,
                                         priority=prio, deadline_ms=dl)
                    break
                except Rejected:
                    time.sleep(0.005)    # closed loop: honor backpressure
            with lock:
                ok.append((sql, out))
        except RequestError as e:
            assert isinstance(e.__cause__, InjectedFault), (
                f"chaos leg saw a non-injected failure: {e.__cause__!r}")
            with lock:
                failed.append(sql)

    with ThreadPoolExecutor(concurrency) as pool:
        list(pool.map(one, reqs))
    sess.backends.set_fault_injector(None)
    # parity: every surviving request equals the fault-free answer
    for sql, out in ok:
        np.testing.assert_allclose(out.scores, ref[sql], atol=1e-5)
    # no restart: the SAME server object still serves
    post = server.predict(reqs[0][0], timeout=60.0)
    np.testing.assert_allclose(post.scores, ref[reqs[0][0]], atol=1e-5)
    return ok, failed, fi


# -- driver -----------------------------------------------------------------

def run(n_rows: int = N_ROWS, n_calibrate: int = N_CALIBRATE,
        n_overload: int = N_OVERLOAD, n_chaos: int = N_CHAOS,
        concurrency: int = CONCURRENCY,
        json_path: str = "BENCH_overload.json") -> dict:
    zoo, table, sample = _setup(n_rows)

    # -- leg 0: unloaded interactive latency defines the SLO bound ------
    sess = _make_session(zoo, table, sample)
    be_rows = _rows_of(sess, "x > 20")
    policy = _policy(be_rows)
    server = MorphingServer(session=sess, policy=policy, max_wait_s=0.002)
    server.start()
    ia_reqs = [r for r in _mixed_requests(32, None)
               if r[1] == "interactive"]
    for sql, prio, _ in ia_reqs:
        server.predict(sql, timeout=60.0, priority=prio)
    base_p95 = server.stats().p95_latency_s_by_priority.get(
        "interactive", 0.01)
    slo_ms = max(base_p95 * 1e3 * SLO_FACTOR, SLO_FLOOR_MS)
    emit_value("overload.interactive_slo_ms", slo_ms,
               f"{SLO_FACTOR:.0f}x unloaded p95 (floor {SLO_FLOOR_MS})")

    # -- leg 1: sustainable closed-loop rate ----------------------------
    cal_reqs = _mixed_requests(n_calibrate, slo_ms)
    server.reset_telemetry()
    wall_cal, st_cal = leg_sustainable(server, cal_reqs, concurrency)
    sustainable_rps = n_calibrate / wall_cal
    rows_cal = sum(_rows_of(sess, sql) for sql, _, _ in cal_reqs)
    emit_value("overload.sustainable_rows_per_s", rows_cal / wall_cal,
               f"{sustainable_rps:.1f} req/s closed loop")

    # -- leg 2: open loop at OVERLOAD_X the sustainable rate ------------
    ovl_reqs = _mixed_requests(n_overload, slo_ms)
    server.reset_telemetry()
    wall_ovl, rows_ok, outcomes, st_ovl = leg_overload(
        server, ovl_reqs, sustainable_rps * OVERLOAD_X, concurrency)
    n_by = {p: sum(1 for q, _ in outcomes["ok"] if q == p)
            for p in ("interactive", "batch", "best_effort")}
    rej_by = dict(st_ovl.rejected_by_priority)
    ia_p95_ms = st_ovl.p95_latency_s_by_priority.get(
        "interactive", 0.0) * 1e3
    emit_value("overload.served_rows_per_s", rows_ok / wall_ovl,
               f"{OVERLOAD_X:.0f}x offered load")
    emit_value("overload.interactive_p95_ms", ia_p95_ms,
               f"SLO {slo_ms:.0f}ms")
    emit_value("overload.best_effort_rejected",
               rej_by.get("best_effort", 0),
               f"{len(outcomes['rejected'])} total rejections")
    emit_value("overload.deadline_misses", st_ovl.deadline_misses,
               f"{st_ovl.deadlines_admitted} admitted with deadlines")
    emit_value("overload.budget_shrinks", st_ovl.budget_shrinks,
               "dynamic Eq.11 shrink events")
    server.stop()

    if n_overload >= MIN_REQUESTS_FOR_ASSERT:
        # graceful degradation contract, asserted in-bench:
        assert ia_p95_ms <= slo_ms, (
            f"interactive p95 {ia_p95_ms:.1f}ms blew the "
            f"{slo_ms:.0f}ms SLO under {OVERLOAD_X:.0f}x overload")
        assert rej_by.get("best_effort", 0) > 0, (
            "2x overload must shed best-effort traffic via Rejected "
            f"backpressure (rejections by class: {rej_by})")
        assert rej_by.get("interactive", 0) == 0, (
            f"interactive traffic must not shed: {rej_by}")

    # -- leg 3: chaos — injected batch kills, parity on survivors -------
    sess_c = _make_session(zoo, table, sample)
    chaos_reqs = _mixed_requests(n_chaos, slo_ms)
    ref = {sql: sess_c.sql(sql).rows["_score"]
           for sql, _, _ in chaos_reqs}         # fault-free answers
    srv_c = MorphingServer(session=sess_c, policy=_policy(be_rows),
                           max_wait_s=0.002)
    with srv_c:
        srv_c.predict(chaos_reqs[0][0], timeout=60.0)     # warm/stage
        ok, failed, fi = leg_chaos(srv_c, sess_c, chaos_reqs, ref,
                                   CHAOS_ERROR_RATE, concurrency)
        st_chaos = srv_c.stats()
    kill_rate = fi.injected_errors / max(fi.calls, 1)
    emit_value("chaos.injected_batch_kill_rate", kill_rate,
               f"{fi.injected_errors}/{fi.calls} trunk batches")
    emit_value("chaos.failed_requests", len(failed),
               f"{len(ok)} survivors, parity checked")
    emit_value("chaos.retries", st_chaos.retries, "transient recoveries")
    assert len(ok) + len(failed) == n_chaos, "requests lost, not failed"
    assert fi.injected_errors > 0, (
        "chaos leg injected nothing — raise CHAOS_ERROR_RATE or n_chaos")
    # survivors' parity + post-chaos serve were asserted inside leg_chaos

    result = {
        "rows_table": n_rows, "concurrency": concurrency,
        "overload_x": OVERLOAD_X,
        "sustainable": {
            "requests": n_calibrate, "wall_s": wall_cal,
            "rows_per_s": rows_cal / wall_cal,
            "requests_per_s": sustainable_rps,
        },
        "overload": {
            "requests": n_overload,
            "interactive_slo_ms": slo_ms,
            "served_rows_per_s": rows_ok / wall_ovl,
            "interactive": {
                "p95_latency_ms": ia_p95_ms,
                "completed": n_by["interactive"],
                "rejected": rej_by.get("interactive", 0),
            },
            "batch": {"completed": n_by["batch"],
                      "rejected": rej_by.get("batch", 0)},
            "best_effort": {"completed": n_by["best_effort"],
                            "rejected": rej_by.get("best_effort", 0)},
            "failed": len(outcomes["failed"]),
            "deadline_misses": st_ovl.deadline_misses,
            "deadlines_admitted": st_ovl.deadlines_admitted,
            "budget_shrinks": st_ovl.budget_shrinks,
            "budget_grows": st_ovl.budget_grows,
        },
        "chaos": {
            "requests": n_chaos,
            "error_rate": CHAOS_ERROR_RATE,
            "injected_batch_kill_rate": kill_rate,
            "injected_errors": int(fi.injected_errors),
            "trunk_calls": int(fi.calls),
            "failed_requests": len(failed),
            "ok_requests": len(ok),
            "retries": st_chaos.retries,
            "failed_batches": st_chaos.failed_batches,
            "breaker_trips": st_chaos.breaker_trips,
        },
    }
    if json_path:
        Path(json_path).write_text(json.dumps(result, indent=2,
                                              sort_keys=True))
        print(f"# wrote {json_path}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=N_ROWS)
    ap.add_argument("--requests", type=int, default=N_OVERLOAD,
                    help="open-loop overload request count")
    ap.add_argument("--concurrency", type=int, default=CONCURRENCY)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run (keeps the chaos parity asserts; "
                         "skips the percentile asserts)")
    ap.add_argument("--json", default="BENCH_overload.json",
                    help="output path ('' disables)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    if args.smoke:
        run(n_rows=600, n_calibrate=16, n_overload=32, n_chaos=16,
            concurrency=4, json_path=args.json)
    else:
        run(n_rows=args.rows, n_overload=args.requests,
            concurrency=args.concurrency, json_path=args.json)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
