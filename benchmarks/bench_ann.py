"""Semantic share cache ablation: ANN-indexed embedding reuse on a
near-duplicate serving workload vs the exact-only share cache, plus
``ORDER BY SIMILARITY(...) LIMIT k`` top-k latency against a brute-force
trunk scan.

The serving workload models recurring near-duplicate traffic (retries,
lightly edited rows, sensor jitter): every timed pass perturbs the base
table within the ANN tier's *calibrated* reuse radius, so the exact
tier's fingerprints never match while the ANN tier serves the rows
within its error bound. The exact-only server pays the trunk for every
pass; the ANN chain pays one IVF probe.

Run directly for machine-readable output::

    PYTHONPATH=src:. python benchmarks/bench_ann.py \
        --rows 2000 --passes 5 --json BENCH_ann.json

``BENCH_ann.json`` records warm rows/s for both cache configurations,
the measured recall and max embedding error on the timed traffic
(asserted against the configured bound), and warm top-k latency for the
lowered index scan vs a brute-force trunk-and-sort baseline (gated by
``scripts/check_bench.py``: rows/s floors, p95 ceilings).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import emit_value
from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.device import enable_compile_cache
from repro.engine import AnnConfig, EngineConfig, MorphingServer, \
    MorphingSession
from repro.engine.serve import _SHARE_TABLE

N_ROWS = 2000
N_PASSES = 5
DIM = 64
# radial (RBF-to-centers) trunk: per-row cost scales with centers x dim
# and doesn't collapse into one BLAS call — the inference cost class
# ANN reuse is built to remove (a single-matmul toy trunk is cheaper
# than any index probe and would make the ablation meaningless)
TRUNK_WIDTH = 256
K_TOP = 10
TOPK_CALLS = 30
# below this the speedup target is recorded but not asserted (fixed
# overheads dominate tiny tables)
MIN_ROWS_FOR_ASSERT = 1000
TARGET_ANN_SPEEDUP = 1.3
TARGET_RECALL = 0.95
ANN_CFG = AnnConfig(error_bound=0.1, audit_rate=0.02, nlist=32, nprobe=4)


def _setup(n_rows: int):
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=800, dim=DIM, classes=3)
    zoo = [pretrain_model(src, width=TRUNK_WIDTH, seed=1, name="ann-m0",
                          mode="radial")]
    rng = np.random.default_rng(0)
    base = rng.standard_normal((n_rows, DIM)).astype(np.float32)
    sample = make_task(rng, "gauss", n=128, dim=DIM, classes=3)
    return zoo, base, sample


def _make_session(zoo, sample, tiers):
    cfg = EngineConfig(model_store="decoupled", backend="numpy",
                       cache_tiers=tiers,
                       ann=ANN_CFG if "ann" in tiers else None)
    sess = MorphingSession(zoo=zoo, config=cfg)
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0
    sess.resolve_task("sent", sample.X, sample.y)
    return sess


def _serve_pass(srv, rows):
    srv.session.register_table("reviews", {"emb": rows})
    return srv.predict("PREDICT emb USING TASK sent FROM reviews",
                       timeout=120.0)


def _perturb(rng, base, scale):
    noise = rng.standard_normal(base.shape).astype(np.float32)
    noise /= np.linalg.norm(noise, axis=1, keepdims=True)
    return base + noise * scale


def bench_serving(zoo, base, sample, tiers, passes):
    """Near-duplicate passes through the serving lanes: returns
    (wall_seconds, rows_served, server, perturbation_scale, last_rows).
    Pass 1 fills the cache, pass 2 calibrates the ANN radius (both
    untimed for either configuration); timed passes perturb within 30%
    of the calibrated radius so the workload is reuse-eligible by
    construction."""
    sess = _make_session(zoo, sample, tiers)
    rng = np.random.default_rng(7)
    n = len(base)
    srv = MorphingServer(session=sess, max_wait_s=0.002)
    with srv:
        _serve_pass(srv, base)                               # fill
        _serve_pass(srv, _perturb(rng, base, 1e-3))          # calibrate
        ann = sess.ann
        if ann is not None:
            with ann._lock:
                block = next(iter(ann._blocks.values()))
                scale = 0.3 * ann._radius_of(block)
            assert scale > 0, "ANN tier failed to calibrate"
        else:
            scale = 1e-3        # same row geometry for the ablation
        srv.reset_telemetry()
        t0 = time.perf_counter()
        for _ in range(passes):
            last = _perturb(rng, base, scale)
            _serve_pass(srv, last)
        wall = time.perf_counter() - t0
        st = srv.stats()
    return wall, passes * n, st, scale, last, sess


def bench_topk(zoo, base, sample):
    """Warm top-k: the lowered index scan (cache-chain gather + argsort,
    zero trunk rows) vs a brute-force baseline that runs the trunk over
    the whole table and sorts. Uses the chain configuration: the chain's
    row-granular blocks are what the index scan gathers from."""
    sess = _make_session(zoo, sample, ("exact", "ann"))
    sess.register_table("reviews", {"id": np.arange(len(base)),
                                    "emb": base})
    sess.sql("PREDICT emb USING TASK sent FROM reviews")       # warm
    q = base[len(base) // 2]
    vec = "[" + ", ".join(f"{x:.6f}" for x in q) + "]"
    stmt = (f"PREDICT emb USING TASK sent FROM reviews "
            f"ORDER BY SIMILARITY(emb, {vec}) LIMIT {K_TOP}")
    res = sess.sql(stmt)
    assert res.report.index_scan, "similarity query must lower"
    assert res.report.sim_trunk_rows == 0, (
        "warm top-k must not run the trunk")
    lat = []
    for _ in range(TOPK_CALLS):
        t0 = time.perf_counter()
        sess.sql(stmt)
        lat.append(time.perf_counter() - t0)

    rm = sess.models["sent"]
    table = sess.tables["reviews"]
    qE = np.asarray(rm.features(q[None]), np.float32)[0]

    def brute():
        E = np.asarray(rm.features(table["emb"]), np.float32)
        top = np.argsort(np.linalg.norm(E - qE[None], axis=1))[:K_TOP]
        return rm.head(E[top])

    blat = []
    for _ in range(TOPK_CALLS):
        t0 = time.perf_counter()
        brute()
        blat.append(time.perf_counter() - t0)
    return (float(np.percentile(lat, 95)),
            float(np.percentile(blat, 95)))


def run(n_rows: int = N_ROWS, passes: int = N_PASSES,
        json_path: str = "BENCH_ann.json") -> dict:
    zoo, base, sample = _setup(n_rows)

    t_exact, rows, st_ex, _, _, _ = bench_serving(
        zoo, base, sample, ("exact",), passes)
    t_ann, _, st_ann, scale, last, sess_ann = bench_serving(
        zoo, base, sample, ("exact", "ann"), passes)

    recall = st_ann.approx_hits / max(rows, 1)
    speedup = t_exact / t_ann

    # error audit on the actual serving block: every row the ANN tier
    # would serve for the final perturbed batch, compared to the trunk
    ann = sess_ann.ann
    rm = sess_ann.models["sent"]
    key = rm.trunk_fp or rm.version
    tl = ann.lookup_many(_SHARE_TABLE, key, last, version=key)
    hit = ~tl.miss
    assert hit.any(), "probe batch must hit the ANN tier"
    exact = np.asarray(rm.features(last[hit]), np.float32)
    max_err = float(np.linalg.norm(
        tl.found[hit].astype(np.float64) - exact, axis=1).max())

    p95_topk, p95_brute = bench_topk(zoo, base, sample)

    emit_value("ann.exact_rows_per_s_warm", rows / t_exact,
               "trunk every pass")
    emit_value("ann.ann_rows_per_s_warm", rows / t_ann,
               f"recall={recall:.3f} radius_frac=0.3")
    emit_value("ann.speedup_ann_vs_exact", speedup, "x near-dup passes")
    emit_value("ann.recall", recall, f"target {TARGET_RECALL}")
    emit_value("ann.max_embed_error", max_err,
               f"bound {ANN_CFG.error_bound}")
    emit_value("ann.false_accepts", st_ann.false_accepts,
               f"{st_ann.approx_hits} approx hits")
    emit_value("ann.topk_warm_p95_latency_ms", p95_topk * 1e3,
               f"index scan k={K_TOP}")
    emit_value("ann.topk_brute_p95_latency_ms", p95_brute * 1e3,
               "trunk + full sort")

    result = {
        "rows_table": n_rows,
        "passes": passes,
        "dim": DIM,
        "trunk_width": TRUNK_WIDTH,
        "error_bound": ANN_CFG.error_bound,
        "exact_only": {"rows_per_s_warm": rows / t_exact,
                       "wall_s": t_exact,
                       "share_hits": st_ex.share_hits,
                       "share_misses": st_ex.share_misses},
        "ann_chain": {"rows_per_s_warm": rows / t_ann,
                      "wall_s": t_ann,
                      "recall": recall,
                      "max_embed_error": max_err,
                      "approx_hits": st_ann.approx_hits,
                      "false_accepts": st_ann.false_accepts,
                      "perturbation_scale": float(scale)},
        "speedup_ann_vs_exact": speedup,
        "topk": {"k": K_TOP,
                 "warm_p95_latency_ms": p95_topk * 1e3,
                 "brute_p95_latency_ms": p95_brute * 1e3,
                 "speedup_vs_brute": p95_brute / p95_topk},
    }
    assert max_err <= ANN_CFG.error_bound, (
        f"served embedding error {max_err:.4f} exceeds the "
        f"{ANN_CFG.error_bound} bound")
    if n_rows >= MIN_ROWS_FOR_ASSERT:
        assert recall >= TARGET_RECALL, (
            f"ANN recall {recall:.3f} < {TARGET_RECALL} on the "
            f"in-radius near-duplicate workload")
        assert speedup >= TARGET_ANN_SPEEDUP, (
            f"ANN chain {speedup:.2f}x < {TARGET_ANN_SPEEDUP}x target "
            f"over exact-only on the near-duplicate workload")
    if json_path:
        Path(json_path).write_text(json.dumps(result, indent=2,
                                              sort_keys=True))
        print(f"# wrote {json_path}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=N_ROWS)
    ap.add_argument("--passes", type=int, default=N_PASSES)
    ap.add_argument("--json", default="BENCH_ann.json",
                    help="output path ('' disables)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run(n_rows=args.rows, passes=args.passes, json_path=args.json)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
