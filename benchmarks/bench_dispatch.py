"""Dispatch tier: front-door routing to worker processes vs worker count.

One ``DispatchServer`` front door, same concurrent ``PREDICT`` workload,
two tiers: ``workers=1`` (every lease lands on one process) and
``workers=4`` (the trunk prestaged on all four, coalesced batches
row-balanced across them). Workers run the numpy backend — real
multi-core parallelism with no per-process jax import — and the share
cache is disabled so the timed window measures trunk compute plus the
process-boundary transport, not cache hits. "Warm" means post-placement:
the warmup pass stages the trunk and visits every statement once.

A failover leg runs 2 workers, slows one down, hard-kills it mid-stream
(``Process.terminate``), and requires the survivor to complete the full
request set with fault-free parity — the re-dispatch and duplicate
counters land in the JSON.

Run directly for machine-readable output::

    PYTHONPATH=src:. python benchmarks/bench_dispatch.py \
        --json BENCH_dispatch.json

The >=1.5x speedup target is asserted only where it is physically
meaningful: ``os.cpu_count() >= 4`` (four worker processes on one core
time-slice a single ALU). ``speedup_asserted`` in the JSON records
whether the gate was armed.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from benchmarks.common import emit_value
from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.device import enable_compile_cache
from repro.engine import DispatchServer, MorphingSession, PlacementPolicy

N_ROWS = 1500
N_REQUESTS = 16
CONCURRENCY = 8
DIM = 32
# wide trunk: worker compute must dominate the queue transport
TRUNK_WIDTH = 256
WORKER_COUNTS = (1, 4)
TARGET_SPEEDUP = 1.5
MIN_WORKERS_FOR_ASSERT = 4
REPEATS = 3
N_FAILOVER = 10


def _setup(n_rows: int, dim: int = DIM):
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=160, dim=dim, classes=3)
    zoo = [pretrain_model(src, width=TRUNK_WIDTH, seed=1,
                          name="dispatch-m0")]
    rng = np.random.default_rng(0)
    table = {"len": rng.integers(1, 200, n_rows),
             "emb": rng.standard_normal((n_rows, dim)).astype(np.float32)}
    sample = make_task(rng, "gauss", n=128, dim=dim, classes=3)
    return zoo, table, sample


def _make_server(zoo, table, sample, workers: int) -> DispatchServer:
    # numpy front + workers: the front door never runs trunk compute,
    # and share is off so leases measure real worker forwards
    sess = MorphingSession(zoo=zoo, model_store="decoupled",
                           backend="numpy", enable_share=False)
    sess.register_table("reviews", {k: v.copy() for k, v in table.items()})
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0   # single-model zoo: no selector
    sess.resolve_task("sent", sample.X, sample.y)
    return DispatchServer(session=sess, workers=workers,
                          worker_backend="numpy",
                          placement=PlacementPolicy(watermark_rows=1 << 20),
                          max_wait_s=0.002)


def _statements(n_requests: int):
    # varied predicates: each request selects a different row window, as
    # concurrent clients would
    return [f"PREDICT emb USING TASK sent FROM reviews WHERE len > "
            f"{20 + (i % 16)}" for i in range(n_requests)]


def _rows_served(sess, stmts) -> int:
    lens = {s: int((sess.tables["reviews"]["len"]
                    > int(s.rsplit(">", 1)[1])).sum()) for s in set(stmts)}
    return sum(lens[s] for s in stmts)


def _bench(server: DispatchServer, stmts, concurrency: int):
    """Best-of-REPEATS wall over the statement set; the warmup pass
    places + stages the trunk on every worker and visits each statement
    once, and telemetry is re-based per repeat."""
    def one(stmt):
        return server.predict(stmt, timeout=120.0)

    server.prestage("sent")          # steady-state: all workers serve
    with ThreadPoolExecutor(concurrency) as pool:
        list(pool.map(one, stmts))               # warm
        best, p95s, outs = float("inf"), [], None
        for _ in range(REPEATS):
            server.reset_telemetry()
            t0 = time.perf_counter()
            got = list(pool.map(one, stmts))
            wall = time.perf_counter() - t0
            p95s.append(server.stats().p95_latency_s)
            if wall < best:
                best, outs = wall, got
    return best, outs, float(np.median(p95s))


def _failover_leg(zoo, table, sample, n_requests: int) -> dict:
    """2 workers, victim slowed then hard-killed mid-stream: the full
    request set must complete on the survivor with fault-free parity."""
    server = _make_server(zoo, table, sample, workers=2)
    sess = server.session
    thrs = [10 + 7 * i for i in range(n_requests)]
    refs = {thr: np.asarray(sess.sql(
        "PREDICT emb USING TASK sent FROM reviews "
        f"WHERE len > {thr}").rows["_score"]) for thr in thrs}
    with server:
        warm = server.predict("PREDICT emb USING TASK sent FROM reviews "
                              "WHERE len > 190", timeout=120.0)
        assert warm.rows >= 0
        st0 = server.stats()
        victim = [w for w, b in st0.staged_bytes_by_worker.items()
                  if b > 0][0]
        server.inject_fault(victim, {"slow_rate": 1.0, "slow_s": 0.4})
        ids = {thr: server.submit("PREDICT emb USING TASK sent FROM "
                                  f"reviews WHERE len > {thr}")
               for thr in thrs}
        time.sleep(0.3)              # leases in flight on the victim
        server.kill_worker(victim)
        completed = 0
        for thr, rid in ids.items():
            out = server.result(rid, timeout=120.0)
            np.testing.assert_allclose(out.scores, refs[thr], atol=1e-5)
            completed += 1
        st = server.stats()
    assert completed == n_requests, "failover must complete the full set"
    assert st.worker_deaths == 1 and st.redispatches >= 1
    emit_value("dispatch.failover_redispatches", st.redispatches,
               f"completed={completed}/{n_requests} "
               f"dup_dropped={st.duplicates_dropped}")
    return {
        "requests": n_requests,
        "completed": completed,
        "worker_deaths": st.worker_deaths,
        "redispatches": st.redispatches,
        "duplicates_dropped": st.duplicates_dropped,
        "survivor_parity": True,
    }


def run(n_rows: int = N_ROWS, n_requests: int = N_REQUESTS,
        concurrency: int = CONCURRENCY,
        worker_counts=WORKER_COUNTS,
        n_failover: int = N_FAILOVER,
        json_path: str = "BENCH_dispatch.json") -> dict:
    zoo, table, sample = _setup(n_rows)
    stmts = _statements(n_requests)
    cpus = os.cpu_count() or 1

    per_workers = {}
    outs_by_workers = {}
    for workers in worker_counts:
        server = _make_server(zoo, table, sample, workers)
        rows_total = _rows_served(server.session, stmts)
        with server:
            wall, outs, p95 = _bench(server, stmts, concurrency)
            st = server.stats()
        per_workers[workers] = {
            "workers": workers,
            "wall_s": wall,
            "rows_per_s_warm": rows_total / wall,
            "p95_latency_ms": p95 * 1e3,
            "leases": st.leases,
            "worker_deaths": st.worker_deaths,
        }
        outs_by_workers[workers] = outs
        emit_value(f"dispatch.workers{workers}_rows_per_s",
                   rows_total / wall, f"leases={st.leases}")
        emit_value(f"dispatch.workers{workers}_p95_latency_ms", p95 * 1e3,
                   "post-warmup window")

    # answers are worker-count invariant (pool.map keeps order)
    lo, hi = min(worker_counts), max(worker_counts)
    for a, b in zip(outs_by_workers[lo], outs_by_workers[hi]):
        np.testing.assert_allclose(a.scores, b.scores, atol=1e-5)

    speedup = (per_workers[hi]["rows_per_s_warm"]
               / per_workers[lo]["rows_per_s_warm"])
    asserted = (cpus >= MIN_WORKERS_FOR_ASSERT
                and hi >= MIN_WORKERS_FOR_ASSERT)
    emit_value("dispatch.speedup_multi_vs_single", speedup,
               f"x warm {hi}w vs {lo}w, asserted={asserted} (cpus={cpus})")

    failover = _failover_leg(zoo, table, sample, n_failover)

    result = {
        "rows_table": n_rows, "requests": n_requests,
        "concurrency": concurrency, "trunk_width": TRUNK_WIDTH,
        "host_cpu_count": cpus,
        **{f"workers_{w}": per_workers[w] for w in worker_counts},
        "speedup_multi_vs_single": speedup,
        "target_speedup": TARGET_SPEEDUP,
        "speedup_asserted": asserted,
        "failover": failover,
    }
    if asserted:
        assert speedup >= TARGET_SPEEDUP, (
            f"dispatch tier {speedup:.2f}x < {TARGET_SPEEDUP}x target at "
            f"{hi} workers vs {lo} ({cpus} cpus)")
    if json_path:
        Path(json_path).write_text(json.dumps(result, indent=2,
                                              sort_keys=True))
        print(f"# wrote {json_path}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=N_ROWS)
    ap.add_argument("--requests", type=int, default=N_REQUESTS)
    ap.add_argument("--concurrency", type=int, default=CONCURRENCY)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run (2-worker tier, keeps the "
                         "failover parity asserts)")
    ap.add_argument("--json", default="BENCH_dispatch.json",
                    help="output path ('' disables)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    if args.smoke:
        run(n_rows=600, n_requests=8, concurrency=4,
            worker_counts=(1, 2), n_failover=6, json_path=args.json)
    else:
        run(n_rows=args.rows, n_requests=args.requests,
            concurrency=args.concurrency, json_path=args.json)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
