"""Serving-path ablation: per-request query execution vs the
continuous-batching ``MorphingServer`` on the same concurrent
``PREDICT ... USING TASK`` workload; the share-aware trunk-lane server
vs per-task full-predict lanes on an *overlapping-request* workload
(where warm rows should cost head-only work); the fine-tune
*delta-fleet* workload (K fine-tunes of one base serve through a single
shared embed lane at base + K·delta loaded bytes, vs K per-task lanes
re-running the trunk); plus the partial-load resolution story
(loaded-vs-stored bytes on the decoupled store).

Run directly for machine-readable output::

    PYTHONPATH=src:. python benchmarks/bench_serving.py \
        --requests 64 --rows 2000 --json BENCH_serving.json

``BENCH_serving.json`` records warm rows/s for all paths, the server's
p50/p95 latency (measured over a post-warmup telemetry window: the
server is ``reset_telemetry()``-ed after warmup so percentiles never mix
pre- and post-warmup samples), share-hit/dedup rates, coalescing factor,
and the partial-load byte accounting, so the serving perf trajectory is
tracked per PR (gated by ``scripts/check_bench.py`` in CI, including a
p95 tail-latency ceiling).
"""
from __future__ import annotations

import argparse
import json
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from benchmarks.common import emit_value
from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.device import enable_compile_cache
from repro.engine import MorphingServer, MorphingSession

N_ROWS = 2000
N_REQUESTS = 64
CONCURRENCY = 8
# below this the speedup targets are recorded but not asserted (thread
# startup and compile overheads dominate tiny request counts)
MIN_REQUESTS_FOR_ASSERT = 32
TARGET_SPEEDUP = 2.0
# share-aware trunk lanes vs the per-task full-predict lanes on the
# overlapping workload: warm rows approach head-only cost
TARGET_SHARE_SPEEDUP = 1.5
# the overlap ablation runs a wider trunk so the embed stage carries the
# cost the share cache is supposed to remove
OVERLAP_TRUNK_WIDTH = 160
# fine-tune fleet: K delta variants of one base, served through one
# shared embed lane; the ablation gives each task its own full-predict
# lane (K trunk recomputations). Loaded bytes must stay near the
# marginal cost base + K·delta, not K·full.
DELTA_FLEET_K = 4
TARGET_DELTA_SPEEDUP = 1.5
DELTA_BYTES_FACTOR = 1.5


def _setup(n_rows: int, dim: int = 16, width: int = 24,
           name: str = "serve-m0"):
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=160, dim=dim, classes=3)
    zoo = [pretrain_model(src, width=width, seed=1, name=name)]
    rng = np.random.default_rng(0)
    table = {"gender": rng.integers(0, 2, n_rows),
             "len": rng.integers(1, 200, n_rows),
             "emb": rng.standard_normal((n_rows, dim)).astype(np.float32)}
    sample = make_task(rng, "gauss", n=128, dim=dim, classes=3)
    return zoo, table, sample


def _make_session(zoo, table, sample, **kw):
    sess = MorphingSession(zoo=zoo, model_store="decoupled",
                           backend="numpy", **kw)
    sess.register_table("reviews", {k: v.copy() for k, v in table.items()})
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0   # single-model zoo: no selector
    sess.resolve_task("sent", sample.X, sample.y)
    return sess


def _statements(n_requests: int):
    # varied predicates: each request selects a different row window, as
    # concurrent clients would
    return [f"PREDICT emb USING TASK sent FROM reviews WHERE len > "
            f"{20 + (i % 16)}" for i in range(n_requests)]


def _make_fleet_session(zoo, table, sample, k: int):
    """Base session + K registered fine-tunes (head deltas of the base),
    each bound to its own task via resolve_task(model_id=)."""
    sess = _make_session(zoo, table, sample)   # resolves 'sent' -> base
    rng = np.random.default_rng(7)
    base = zoo[0]
    width = int(base.W.shape[1])
    for i in range(k):
        w = np.abs(rng.standard_normal(width)).astype(np.float32)
        w /= w.sum()
        sess.register_finetune(f"{base.name}-ft{i}", base.name,
                               {"head/w": w})
        sess.create_task(TaskSpec(f"sent_ft{i}", "series", ("P", "N")))
        sess.resolve_task(f"sent_ft{i}", sample.X, sample.y,
                          model_id=f"{base.name}-ft{i}")
    return sess


def _fleet_statements(n_requests: int, k: int):
    return [f"PREDICT emb USING TASK sent_ft{i % k} FROM reviews "
            f"WHERE len > {20 + (i % 16)}" for i in range(n_requests)]


def _rows_served(sess, stmts) -> int:
    lens = {s: int((sess.tables["reviews"]["len"]
                    > int(s.rsplit(">", 1)[1])).sum()) for s in set(stmts)}
    return sum(lens[s] for s in stmts)


REPEATS = 3      # best-of: the warm walls are ~100ms, noise-prone


def bench_per_request(sess, stmts, concurrency: int) -> float:
    """Each request is its own full query: parse -> plan -> chunked
    executor, from ``concurrency`` client threads."""
    with ThreadPoolExecutor(concurrency) as pool:
        list(pool.map(sess.sql, stmts[:concurrency]))        # warm
        best = float("inf")
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            list(pool.map(sess.sql, stmts))
            best = min(best, time.perf_counter() - t0)
        return best


def bench_server(server, stmts, concurrency: int, warm_all: bool = False):
    """Same statements through the continuous-batching server. After the
    warmup pass the telemetry window is re-based, so the stats (latency
    percentiles, share/dedup rates) describe only the timed traffic."""
    def one(stmt):
        return server.predict(stmt, timeout=60.0)

    with ThreadPoolExecutor(concurrency) as pool:
        # warm_all runs every statement once so a share-aware server
        # enters the timed window with the full working set cached
        list(pool.map(one, stmts if warm_all else stmts[:concurrency]))
        warm_stats = server.stats()      # cold-phase counters (dedup)
        # each repeat gets its own telemetry window: percentiles never
        # mix warmup samples, counters come from the best-wall repeat
        # (matching the best-of timing convention) and the reported tail
        # latency is the *median* of the per-repeat p95s — one straggler
        # repeat on a loaded box must not define the latency contract
        best, best_stats, p95s = float("inf"), None, []
        for _ in range(REPEATS):
            server.reset_telemetry()
            t0 = time.perf_counter()
            outs = list(pool.map(one, stmts))
            wall = time.perf_counter() - t0
            rep = server.stats()
            p95s.append(rep.p95_latency_s)
            if wall < best:
                best, best_stats = wall, rep
        best_stats.p95_latency_s = float(np.median(p95s))
    return best, outs, warm_stats, best_stats


def run(n_rows: int = N_ROWS, n_requests: int = N_REQUESTS,
        concurrency: int = CONCURRENCY,
        json_path: str = "BENCH_serving.json") -> dict:
    zoo, table, sample = _setup(n_rows)
    stmts = _statements(n_requests)

    # -- baseline: every PREDICT is its own full query -------------------
    sess_base = _make_session(zoo, table, sample)
    t_per_req = bench_per_request(sess_base, stmts, concurrency)
    rows_total = _rows_served(sess_base, stmts)

    # -- server: continuous batching over shared trunk embed lanes -------
    sess_srv = _make_session(zoo, table, sample)
    server = MorphingServer(session=sess_srv, max_wait_s=0.002)
    with server:
        t_server, outs, _, st = bench_server(server, stmts, concurrency)

    # parity: a served request matches the engine answer
    ref = sess_base.sql(stmts[0]).rows["_score"]
    got = outs[0].scores                 # pool.map preserves order
    np.testing.assert_allclose(np.sort(got), np.sort(ref), atol=1e-5)

    speedup = t_per_req / t_server
    emit_value("serving.per_request_rows_per_s", rows_total / t_per_req,
               f"{concurrency} clients")
    emit_value("serving.server_rows_per_s", rows_total / t_server,
               f"coalesced x{st.mean_coalesced:.1f}")
    emit_value("serving.speedup_server_vs_per_request", speedup, "x warm")
    emit_value("serving.p50_latency_ms", st.p50_latency_s * 1e3,
               "post-warmup window")
    emit_value("serving.p95_latency_ms", st.p95_latency_s * 1e3,
               "post-warmup window")
    emit_value("serving.share_hit_rate", st.share_hit_rate, "warm rows")

    # -- overlap ablation: share-aware trunk lanes vs per-task lanes -----
    # concurrent requests select overlapping row windows; the share-aware
    # server embeds each distinct row once (cache + in-flight dedup) and
    # warm traffic pays head-only cost, while per-task full-predict lanes
    # recompute every window end to end
    zoo_o, table_o, sample_o = _setup(n_rows, width=OVERLAP_TRUNK_WIDTH,
                                      name="serve-share")
    sess_task = _make_session(zoo_o, table_o, sample_o)
    srv_task = MorphingServer(session=sess_task, max_wait_s=0.002,
                              share_lanes=False)
    with srv_task:
        t_task, _, _, _ = bench_server(srv_task, stmts, concurrency,
                                       warm_all=True)
    sess_share = _make_session(zoo_o, table_o, sample_o)
    srv_share = MorphingServer(session=sess_share, max_wait_s=0.002)
    with srv_share:
        t_share, outs_share, cold_share, st_share = bench_server(
            srv_share, stmts, concurrency, warm_all=True)

    # deterministic in-flight-dedup exercise: identical concurrent
    # requests against a cold cache under a generous coalescing window
    # (the 2ms production window makes batch composition — and thus the
    # dedup counter — scheduler-timing dependent; asserting on it would
    # flake on loaded runners)
    sess_probe = _make_session(zoo_o, table_o, sample_o)
    srv_probe = MorphingServer(session=sess_probe, max_wait_s=0.2)
    with srv_probe:
        with ThreadPoolExecutor(concurrency) as pool:
            list(pool.map(lambda s: srv_probe.predict(s, timeout=60.0),
                          [stmts[0]] * concurrency))
    dedup_probe = srv_probe.stats()
    ref_o = sess_task.sql(stmts[0]).rows["_score"]
    got_o = outs_share[0].scores         # pool.map preserves order
    np.testing.assert_allclose(np.sort(got_o), np.sort(ref_o), atol=1e-5)
    share_speedup = t_task / t_share
    emit_value("serving.overlap_task_lane_rows_per_s",
               rows_total / t_task, "full predict per lane")
    emit_value("serving.overlap_share_rows_per_s",
               rows_total / t_share,
               f"hit_rate={st_share.share_hit_rate:.2f} "
               f"cold_dedup={cold_share.dedup_rate:.2f}")
    emit_value("serving.speedup_share_vs_task_lanes", share_speedup,
               "x warm overlapping rows")
    emit_value("serving.dedup_probe_rate", dedup_probe.dedup_rate,
               f"{dedup_probe.dedup_rows} in-flight rows folded")
    assert st_share.share_hit_rate > 0.0, (
        "overlapping warm traffic must hit the share cache")
    assert dedup_probe.dedup_rows > 0, (
        "identical concurrent requests must exercise in-flight dedup")

    # -- delta fleet: K fine-tunes of one base share one embed lane -----
    # the heavy trunk runs once per distinct row window regardless of
    # which fine-tune asked; per-task full-predict lanes (the ablation)
    # recompute it K times and stage K trunk copies
    zoo_d, table_d, sample_d = _setup(n_rows, width=OVERLAP_TRUNK_WIDTH,
                                      name="serve-delta")
    fleet_stmts = _fleet_statements(n_requests, DELTA_FLEET_K)
    sess_dtask = _make_fleet_session(zoo_d, table_d, sample_d,
                                     DELTA_FLEET_K)
    srv_dtask = MorphingServer(session=sess_dtask, max_wait_s=0.002,
                               share_lanes=False)
    with srv_dtask:
        t_dtask, _, _, _ = bench_server(srv_dtask, fleet_stmts,
                                        concurrency, warm_all=True)
    sess_fleet = _make_fleet_session(zoo_d, table_d, sample_d,
                                     DELTA_FLEET_K)
    srv_fleet = MorphingServer(session=sess_fleet, max_wait_s=0.002)
    with srv_fleet:
        t_fleet, outs_fleet, _, st_fleet = bench_server(
            srv_fleet, fleet_stmts, concurrency, warm_all=True)
    rows_fleet = _rows_served(sess_fleet, fleet_stmts)

    # parity: a served fine-tune matches its analytics answer
    ref_d = sess_dtask.sql(fleet_stmts[0]).rows["_score"]
    np.testing.assert_allclose(np.sort(outs_fleet[0].scores),
                               np.sort(ref_d), atol=1e-5)
    # the whole fleet rides ONE embed lane (shared base trunk identity)
    assert st_fleet.lanes == 1 and st_fleet.delta_tasks == DELTA_FLEET_K, (
        f"expected one shared embed lane for {DELTA_FLEET_K} fine-tunes, "
        f"got lanes={st_fleet.lanes} delta_tasks={st_fleet.delta_tasks}")
    # loaded bytes stay at marginal cost: base once + K small deltas
    base_rm = sess_fleet.models["sent"]
    fleet_loaded = base_rm.loaded_bytes + st_fleet.delta_loaded_bytes
    fleet_budget = DELTA_BYTES_FACTOR * (base_rm.stored_bytes
                                         + st_fleet.delta_stored_bytes)
    assert fleet_loaded < fleet_budget, (
        f"delta fleet loaded {fleet_loaded}B >= {fleet_budget:.0f}B "
        f"(base {base_rm.stored_bytes}B + "
        f"{DELTA_FLEET_K}·delta {st_fleet.delta_stored_bytes}B)")
    delta_speedup = t_dtask / t_fleet
    emit_value("serving.delta_fleet_task_lane_rows_per_s",
               rows_fleet / t_dtask, f"{DELTA_FLEET_K} full lanes")
    emit_value("serving.delta_fleet_share_rows_per_s",
               rows_fleet / t_fleet,
               f"1 embed lane, {DELTA_FLEET_K} heads, "
               f"hit_rate={st_fleet.share_hit_rate:.2f}")
    emit_value("serving.speedup_delta_fleet_vs_task_lanes", delta_speedup,
               "x warm fleet rows")
    emit_value("serving.delta_fleet_loaded_bytes", fleet_loaded,
               f"budget {fleet_budget:.0f}")

    # -- partial load: a head-only predict loads head bytes, not trunk --
    sess_head = _make_session(zoo, table, sample)
    sess_head.sql(stmts[0])               # warms the share cache
    # count true disk bytes (the in-memory layer cache would serve the
    # head layer for free after the first resolution)
    sess_head.dstore.cache_layers = False
    sess_head.create_task(TaskSpec("sent2", "series", ("P", "N")))
    sess_head.registry._resolution["sent2"] = 0
    rm2 = sess_head.resolve_task("sent2", sample.X, sample.y, mode="head")
    sess_head.sql("PREDICT emb USING TASK sent2 FROM reviews "
                  "WHERE len > 20")       # embeds come from the share
    head_loaded = rm2.loaded_bytes
    emit_value("serving.head_only_loaded_bytes", head_loaded,
               f"of {rm2.stored_bytes} stored")
    assert head_loaded < rm2.stored_bytes, (
        "head-only predict must load less than the stored model")
    assert not rm2.zoo_model.materialized, (
        "share-cache hits must keep the trunk on disk")

    result = {
        "rows_table": n_rows, "requests": n_requests,
        "concurrency": concurrency, "rows_served": rows_total,
        "per_request": {"wall_s": t_per_req,
                        "rows_per_s_warm": rows_total / t_per_req},
        "server": {"wall_s": t_server,
                   "rows_per_s_warm": rows_total / t_server,
                   "p50_latency_ms": st.p50_latency_s * 1e3,
                   "p95_latency_ms": st.p95_latency_s * 1e3,
                   "batches": st.batches,
                   "mean_coalesced": st.mean_coalesced,
                   "share_hit_rate": st.share_hit_rate},
        "speedup_server_vs_per_request": speedup,
        "overlap": {
            "trunk_width": OVERLAP_TRUNK_WIDTH,
            "task_lanes": {"wall_s": t_task,
                           "rows_per_s_warm": rows_total / t_task},
            "share_lanes": {"wall_s": t_share,
                            "rows_per_s_warm": rows_total / t_share,
                            "p95_latency_ms":
                                st_share.p95_latency_s * 1e3,
                            "share_hit_rate": st_share.share_hit_rate,
                            "cold_dedup_rate": cold_share.dedup_rate,
                            "dedup_probe_rate": dedup_probe.dedup_rate,
                            "dedup_probe_rows": dedup_probe.dedup_rows,
                            "embed_rows": st_share.embed_rows,
                            "head_rows": st_share.head_rows},
            "speedup_share_vs_task_lanes": share_speedup,
        },
        "delta_fleet": {
            "k": DELTA_FLEET_K,
            "trunk_width": OVERLAP_TRUNK_WIDTH,
            "task_lanes": {"wall_s": t_dtask,
                           "rows_per_s_warm": rows_fleet / t_dtask},
            "share_lanes": {"wall_s": t_fleet,
                            "rows_per_s_warm": rows_fleet / t_fleet,
                            "p95_latency_ms":
                                st_fleet.p95_latency_s * 1e3,
                            "share_hit_rate": st_fleet.share_hit_rate,
                            "lanes": st_fleet.lanes,
                            "delta_tasks": st_fleet.delta_tasks},
            "speedup_share_vs_task_lanes": delta_speedup,
            "base_stored_bytes": int(base_rm.stored_bytes),
            "delta_stored_bytes": int(st_fleet.delta_stored_bytes),
            "loaded_bytes": int(fleet_loaded),
            "loaded_budget_bytes": int(fleet_budget),
        },
        "partial_load": {"head_only_loaded_bytes": int(head_loaded),
                         "stored_bytes": int(rm2.stored_bytes),
                         "loaded_fraction": head_loaded
                         / max(rm2.stored_bytes, 1)},
    }
    if n_requests >= MIN_REQUESTS_FOR_ASSERT:
        assert speedup >= TARGET_SPEEDUP, (
            f"server {speedup:.2f}x < {TARGET_SPEEDUP}x target over "
            f"per-request execution at concurrency {concurrency}")
        assert share_speedup >= TARGET_SHARE_SPEEDUP, (
            f"share-aware lanes {share_speedup:.2f}x < "
            f"{TARGET_SHARE_SPEEDUP}x target over per-task lanes on the "
            f"overlapping workload at concurrency {concurrency}")
        assert delta_speedup >= TARGET_DELTA_SPEEDUP, (
            f"delta fleet through the shared embed lane "
            f"{delta_speedup:.2f}x < {TARGET_DELTA_SPEEDUP}x target over "
            f"{DELTA_FLEET_K} per-task lanes at concurrency {concurrency}")
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
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="output path ('' disables)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run(n_rows=args.rows, n_requests=args.requests,
        concurrency=args.concurrency, json_path=args.json)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
