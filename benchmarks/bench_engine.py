"""Engine ablation: naive per-row inference vs the full task-centric
engine (pre-embedding share cache + window batching + chunked stage
overlap) on the same task-centric query over a >=5k-row table, plus the
execution-backend ablation (numpy host path vs jax-jitted path with
shape-bucketed compilation) that the backend registry makes switchable.

Run directly for machine-readable output::

    PYTHONPATH=src python benchmarks/bench_engine.py --backend both \
        --rows 6000 --json BENCH_engine.json

``BENCH_engine.json`` records rows/s per backend, the share hit rate,
compile/stage counts for the jitted path, and the jax-vs-numpy speedup so
the perf trajectory is tracked per PR.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import emit, emit_value, timeit
from repro.core import (ModelSelector, TaskFeaturizer, build_tasks,
                        build_zoo, make_task, transfer_matrix)
from repro.device import default_interpret, enable_compile_cache
from repro.engine import MorphingSession
from repro.pipeline.backend import JaxBackend
from repro.pipeline.operators import groupby_agg

N_ROWS = 6000
QUERY = ("SELECT gender, AVG(sent(emb)) FROM reviews "
         "WHERE len > 20 GROUP BY gender")
# below this the backend ablation is recorded but not asserted (compile
# and fixed overheads dominate tiny tables)
MIN_ROWS_FOR_SPEEDUP_ASSERT = 4000
# the jit win is a device claim: on TPU (native Pallas) the jitted path
# must beat the vectorized numpy host path; on CPU the linear-mode kernel
# runs in *interpret* mode, so parity (not speedup) is the honest gate
TARGET_SPEEDUP = 1.3
INTERPRET_SANITY_SPEEDUP = 0.7


def _setup(n_rows: int):
    zoo = build_zoo(16, seed=0)
    history = build_tasks(32, seed=1)
    V = transfer_matrix(zoo, history)
    fz = TaskFeaturizer()
    feats = np.stack([fz.features(t.X, t.y) for t in history])
    sel = ModelSelector(k=6, n_anchors=3).fit_offline(V, feats, zoo=zoo)
    rng = np.random.default_rng(0)
    table = {"gender": rng.integers(0, 2, n_rows),
             "len": rng.integers(1, 200, n_rows),
             "emb": rng.standard_normal((n_rows, 16)).astype(np.float32)}
    sample = make_task(rng, "gauss", n=128, dim=16, classes=3)
    return sel, zoo, table, sample


def _make_session(sel, zoo, table, sample, *, backend="auto",
                  enable_share=True):
    sess = MorphingSession(selector=sel, zoo=zoo, backend=backend,
                           enable_share=enable_share)
    sess.register_table("reviews",
                        {k: v.copy() for k, v in table.items()})
    sess.sql("CREATE TASK sent (INPUT=Series, OUTPUT IN ('P','N'), "
             "TYPE='Classification')")
    model = sess.resolve_task("sent", sample.X, sample.y)
    return sess, model


def _bench_backend(sel, zoo, table, sample, backend: str, n_scored: int):
    """Steady-state rows/s of one execution backend with the share cache
    disabled, so the timed runs exercise the actual inference hot path
    (jit stays warm after the first run; weights staged at resolve)."""
    sess, _ = _make_session(sel, zoo, table, sample, backend=backend,
                            enable_share=False)
    t0 = time.perf_counter()
    cold = sess.sql(QUERY)                       # first run: compiles
    t_cold = time.perf_counter() - t0
    # best-of-5 with warmup: the warm wall is ~10ms at smoke sizes, so
    # scheduler jitter needs several samples to shake out (CI gates on
    # this number)
    t_warm = timeit(lambda: sess.sql(QUERY), repeats=5, warmup=1)
    rec = {"t_cold_s": t_cold, "t_warm_s": t_warm,
           "rows_per_s_cold": n_scored / t_cold,
           "rows_per_s_warm": n_scored / t_warm}
    jaxish = {id(b): b for b in sess.backends.values()
              if isinstance(b, JaxBackend)}
    if jaxish:
        rec["compile_count"] = sum(b.compile_count
                                   for b in jaxish.values())
        rec["stage_count"] = sum(b.stage_count for b in jaxish.values())
    return rec, cold.rows["mean__score"]


def run(n_rows: int = N_ROWS, backends=("numpy", "jax"),
        json_path: str = "BENCH_engine.json") -> dict:
    sel, zoo, table, sample = _setup(n_rows)
    n_scored = int((table["len"] > 20).sum())

    # -- naive: per-row model call, no sharing/batching/overlap ----------
    sess, model = _make_session(sel, zoo, table, sample, backend="numpy")

    def naive():
        mask = table["len"] > 20
        emb = table["emb"][mask]
        scores = np.empty(len(emb), np.float32)
        for i in range(len(emb)):
            scores[i] = model.head(model.features(emb[i:i + 1]))[0]
        return groupby_agg({"gender": table["gender"][mask],
                            "_score": scores}, "gender", "_score")

    # -- engine: shared pre-embedding + window batching + chunk overlap --
    def engine():
        return sess.sql(QUERY)

    ref = naive()
    t_naive = timeit(naive, repeats=2, warmup=0)

    def cold_once():
        """First-ever run on a fresh session: empty share cache."""
        s2, _ = _make_session(sel, zoo, table, sample, backend="numpy")
        t0 = time.perf_counter()
        s2.sql(QUERY)
        return time.perf_counter() - t0

    t_cold = min(cold_once() for _ in range(2))    # best-of-2: less noisy
    res = engine()                                 # cache now filled
    np.testing.assert_allclose(ref["mean__score"],
                               res.rows["mean__score"], rtol=1e-4)
    t_warm = timeit(engine, repeats=2, warmup=0)
    warm = engine()

    emit("engine.naive_per_row", t_naive,
         f"{n_scored / t_naive:.0f} rows/s")
    emit("engine.full_cold", t_cold, f"{n_scored / t_cold:.0f} rows/s")
    emit("engine.full_warm", t_warm,
         f"{n_scored / t_warm:.0f} rows/s "
         f"hit_rate={warm.report.share_hit_rate:.2f}")
    emit_value("engine.speedup_cold", t_naive / t_cold, "x vs per-row")
    emit_value("engine.speedup_warm", t_naive / t_warm, "x vs per-row")
    emit_value("engine.warm_share_hit_rate", warm.report.share_hit_rate,
               "second-run cache hits")
    # cold sits within measurement noise of the naive loop on a loaded
    # machine (share cache is empty; the engine's wins are warm) — gate
    # on "not materially slower" and keep the warm asserts strict
    assert t_naive / t_cold > 0.75, "cold engine materially slower than per-row"
    assert t_naive / t_warm > 1.0, "warm engine must beat per-row inference"
    assert warm.report.share_hit_rate > 0.0, "warm run must hit the cache"

    # -- share-cache fingerprinting: per-row hashing vs vectorized -------
    # the serving row tier fingerprints whole chunks in one numpy pass;
    # this micro-bench records the per-row hashlib overhead it removes
    from repro.pipeline.share import fingerprint, fingerprint_rows

    X_fp = table["emb"]
    t_row_hash = timeit(
        lambda: [fingerprint(X_fp[i:i + 1]) for i in range(len(X_fp))],
        repeats=2, warmup=1)
    t_vec_hash = timeit(lambda: fingerprint_rows(X_fp),
                        repeats=5, warmup=1)
    fp_speedup = t_row_hash / t_vec_hash
    emit("engine.fingerprint_per_row", t_row_hash,
         f"{t_row_hash / len(X_fp) * 1e6:.2f} us/row hashlib")
    emit("engine.fingerprint_vectorized", t_vec_hash,
         f"{t_vec_hash / len(X_fp) * 1e6:.3f} us/row one-pass")
    emit_value("engine.speedup_fingerprint_vectorized", fp_speedup,
               "x vs per-row hashing")
    if n_rows >= MIN_ROWS_FOR_SPEEDUP_ASSERT:
        assert fp_speedup > 5.0, (
            f"vectorized fingerprinting {fp_speedup:.1f}x <= 5x over "
            "per-row hashing — the serving hot path regressed to "
            "per-row Python cost")

    # -- backend ablation: numpy host path vs jax-jitted path ------------
    result = {"rows": n_rows, "scored_rows": n_scored,
              "query": QUERY,
              "naive_rows_per_s": n_scored / t_naive,
              "share_hit_rate_warm": warm.report.share_hit_rate,
              "share_fingerprint": {
                  "rows": len(X_fp),
                  "per_row_us_per_row": t_row_hash / len(X_fp) * 1e6,
                  "vectorized_us_per_row": t_vec_hash / len(X_fp) * 1e6,
                  "speedup_vectorized": fp_speedup},
              "backends": {}}
    parity = {}
    for backend in backends:
        rec, scores = _bench_backend(sel, zoo, table, sample, backend,
                                     n_scored)
        result["backends"][backend] = rec
        emit(f"engine.backend_{backend}_warm", rec["t_warm_s"],
             f"{rec['rows_per_s_warm']:.0f} rows/s")
        parity[backend] = scores
    if len(parity) > 1:
        vals = list(parity.values())
        for v in vals[1:]:
            np.testing.assert_allclose(vals[0], v, atol=1e-5)
    if "numpy" in result["backends"] and "jax" in result["backends"]:
        speedup = (result["backends"]["jax"]["rows_per_s_warm"]
                   / result["backends"]["numpy"]["rows_per_s_warm"])
        result["speedup_jax_vs_numpy"] = speedup
        emit_value("engine.speedup_jax_vs_numpy", speedup,
                   "warm rows/s ratio")
        if n_rows >= MIN_ROWS_FOR_SPEEDUP_ASSERT:
            interpret = default_interpret()
            target = (INTERPRET_SANITY_SPEEDUP if interpret
                      else TARGET_SPEEDUP)
            assert speedup >= target, (
                f"jitted backend {speedup:.2f}x < {target}x target over "
                f"numpy on the warm {n_rows}-row workload "
                f"(interpret={interpret})")
    if json_path:
        Path(json_path).write_text(json.dumps(result, indent=2,
                                              sort_keys=True))
        print(f"# wrote {json_path}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", choices=("numpy", "jax", "both"),
                    default="both",
                    help="execution backend(s) to ablate (default both)")
    ap.add_argument("--rows", type=int, default=N_ROWS)
    ap.add_argument("--json", default="BENCH_engine.json",
                    help="output path ('' disables)")
    args = ap.parse_args(argv)
    # --backend jax still runs numpy as the comparison baseline (the
    # speedup target is defined against it)
    backends = (("numpy",) if args.backend == "numpy"
                else ("numpy", "jax"))
    print("name,us_per_call,derived")
    run(n_rows=args.rows, backends=backends, json_path=args.json)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
