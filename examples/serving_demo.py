"""Serving demo: concurrent PREDICT requests through ``MorphingServer``,
next to the batch-analytics surface of ``examples/task_centric_sql.py``.

Eight client threads fire ``PREDICT ... USING TASK`` statements at the
server; requests whose tasks resolve to the same *trunk* are coalesced
into one cost-model-sized embed lane (warm rows come from the share
cache, in-flight duplicates compute once) and scored by cheap per-task
head stages, while resolution rides the decoupled store's partial-load
path (only the layers a request needs leave the disk). Run:
  PYTHONPATH=src python examples/serving_demo.py

With ``--delta`` the workload becomes a fine-tune fleet: one base model
plus three head-delta variants registered via
``MorphingSession.register_finetune`` and bound with
``resolve_task(model_id=)``. All four tasks share the base trunk's
embed lane — the trunk is staged once and only the small per-head delta
bytes are read from disk (see docs/serving.md):
  PYTHONPATH=src python examples/serving_demo.py --delta

With ``--workers N`` the same traffic runs through the multi-process
dispatch tier instead: a ``DispatchServer`` front door spawns N numpy
worker processes over the shared store, routes coalesced batches to
them as leases, and keeps each trunk on as few workers as its load needs
(``--delta --workers 2`` shows the whole fleet staged on one worker's
shared embed lane). The stats dump covers placement, leases, and the
per-worker aggregates (see docs/serving.md "Dispatch tier"):
  PYTHONPATH=src python examples/serving_demo.py --workers 2 --delta
"""
import argparse
import threading

import numpy as np

from repro.core import (ModelSelector, TaskFeaturizer, build_tasks,
                        build_zoo, make_task, transfer_matrix)
from repro.device import enable_compile_cache
from repro.engine import (DispatchServer, EngineConfig, MorphingServer,
                          MorphingSession)

N_FINETUNES = 3


def main(delta: bool = False, workers: int = 0) -> None:
    zoo = build_zoo(16, seed=0)
    history = build_tasks(32, seed=1)
    V = transfer_matrix(zoo, history)
    fz = TaskFeaturizer()
    feats = np.stack([fz.features(t.X, t.y) for t in history])
    sel = ModelSelector(k=6, n_anchors=3).fit_offline(V, feats, zoo=zoo)

    # the dispatch tier's front door runs no inference, and its workers
    # are numpy processes: a chip belongs to one process, so neither the
    # front door nor N workers may each take it
    sess = MorphingSession(selector=sel, zoo=zoo, config=EngineConfig(
        model_store="decoupled", backend="numpy" if workers else "auto"))
    rng = np.random.default_rng(0)
    n = 3000
    sess.register_table("reviews", {
        "gender": rng.integers(0, 2, n),
        "len": rng.integers(1, 200, n),
        "emb": rng.standard_normal((n, 16)).astype(np.float32)})
    print(sess.sql(
        "CREATE TASK sentiment (INPUT=Series, OUTPUT IN ('POS','NEG'), "
        "TYPE='Classification');"))
    sample = make_task(rng, "gauss", n=128, dim=16, classes=3)

    if workers:
        # front door + N worker processes over the shared store root
        server = DispatchServer(session=sess, workers=workers,
                                max_wait_s=0.005)
    else:
        server = MorphingServer(session=sess, max_wait_s=0.005)
    # partial-load resolution ahead of traffic: the slice is keyed to
    # the sample's width, which matches the reviews.emb schema here
    server.resolve_task("sentiment", sample.X, sample.y, mode="partial")
    tasks = ["sentiment"]
    if delta:
        # fine-tune fleet: the system-resolved model becomes the base;
        # each variant stores only a new head (delta layers) and rides
        # the base trunk's embed lane when served
        base_id = sess.models["sentiment"].model_id
        base_dim = sess.models["sentiment"].head_dim
        for i in range(N_FINETUNES):
            w = np.abs(rng.standard_normal(base_dim)).astype(np.float32)
            w /= w.sum()
            ft_id = f"{base_id}-ft{i}"
            sess.register_finetune(ft_id, base_id, {"head/w": w})
            name = f"sentiment_ft{i}"
            sess.sql(f"CREATE TASK {name} (INPUT=Series, "
                     "OUTPUT IN ('POS','NEG'), TYPE='Classification');")
            sess.resolve_task(name, sample.X, sample.y, model_id=ft_id)
            tasks.append(name)

    with server:
        results = {}

        def client(cid: int) -> None:
            for i in range(6):
                task = tasks[(cid + i) % len(tasks)]
                out = server.predict(
                    f"PREDICT emb USING TASK {task} FROM reviews "
                    f"WHERE len > {20 + 10 * (i % 4)}",
                    sample=(sample.X, sample.y), timeout=30.0)
                results[(cid, i)] = out

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = server.stats()          # workers answer while still alive

    rm = sess.models["sentiment"]
    print(f"(system resolved sentiment -> {rm.model_id}, "
          f"{rm.store} store, mode={rm.load_mode})")
    if workers:
        print(f"dispatch tier: {st.alive_workers}/{st.workers} workers, "
              f"{st.requests} requests / {st.rows} rows over "
              f"{st.leases} leases "
              f"(redispatches={st.redispatches}, "
              f"scale out/in={st.scale_outs}/{st.scale_ins})")
        print(f"placement: replicas {st.replicas_by_trunk}; "
              f"staged bytes by worker {st.staged_bytes_by_worker}")
        print(f"front latency p50={st.p50_latency_s * 1e3:.1f}ms "
              f"p95={st.p95_latency_s * 1e3:.1f}ms; "
              f"{st.rows_per_second:.0f} rows/s worker inference; "
              f"share hit rate {st.share_hit_rate:.2f}")
    else:
        print(f"served {st.requests} requests / {st.rows} rows in "
              f"{st.batches} batches (x{st.mean_coalesced:.1f} coalesced)")
        print(f"latency p50={st.p50_latency_s * 1e3:.1f}ms "
              f"p95={st.p95_latency_s * 1e3:.1f}ms; "
              f"{st.rows_per_second:.0f} rows/s inference")
        print(f"partial load: {st.loaded_bytes}B read of "
              f"{st.stored_bytes}B stored")
        if delta:
            print(f"delta fleet: {len(tasks)} tasks over {st.lanes} embed "
                  f"lane(s) {st.tasks_by_lane}; {st.delta_tasks} "
                  f"fine-tunes read {st.delta_loaded_bytes}B "
                  f"({st.delta_stored_bytes}B of deltas on disk); "
                  f"share hit rate {st.share_hit_rate:.2f}")
    one = results[(0, 0)]
    print(f"(request {one.req_id}: {one.rows} rows, "
          f"mean score {one.scores.mean():+.4f})")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--delta", action="store_true",
                    help="serve a fine-tune fleet (base + "
                         f"{N_FINETUNES} head-delta variants) through "
                         "one shared embed lane")
    ap.add_argument("--workers", type=int, default=0,
                    help="route through the multi-process dispatch tier "
                         "with N worker processes (0 = in-process "
                         "MorphingServer)")
    args = ap.parse_args()
    main(delta=args.delta, workers=args.workers)
