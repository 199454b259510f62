"""Table-1 demo: the task-centric SQL surface, as a thin client of the
query engine (`repro.engine.MorphingSession`).

The paper's two statements:

  CREATE TASK sentiment_classifier (INPUT=Series, OUTPUT IN ('POS','NEG'),
      TYPE='Classification');
  SELECT gender, AVG(sentiment_classifier(emb)) FROM reviews
      WHERE len > 20 GROUP BY gender;

vs. the model-centric equivalent where the user must pick
TextCNNForSentiAnalysisV_2_0 themselves. The session resolves the task to
a model through the transferability subspace, persists it through the
BLOB store + catalog, pre-embeds via the vector-share cache, window-
batches the inference, and streams chunks through the DAG runtime. Run:
  PYTHONPATH=src python examples/task_centric_sql.py

``--delta`` switches to the decoupled store and adds a fine-tune: a
head-delta variant of the system-resolved model is registered
(``register_finetune``), bound to its own task
(``resolve_task(model_id=)``), and queried — its embeddings come
straight from the share cache because fine-tunes of one base share
their trunk identity (docs/architecture.md):
  PYTHONPATH=src python examples/task_centric_sql.py --delta
"""
import argparse

import numpy as np

from repro.core import (ModelSelector, TaskFeaturizer, build_tasks,
                        build_zoo, make_task, transfer_matrix)
from repro.device import enable_compile_cache
from repro.engine import MorphingSession


def main(delta: bool = False) -> None:
    zoo = build_zoo(16, seed=0)
    history = build_tasks(32, seed=1)
    V = transfer_matrix(zoo, history)
    fz = TaskFeaturizer()
    feats = np.stack([fz.features(t.X, t.y) for t in history])
    sel = ModelSelector(k=6, n_anchors=3).fit_offline(V, feats, zoo=zoo)

    # fine-tune deltas live in decoupled layer tables; the default demo
    # keeps the BLOB store the paper's Table-1 flow uses
    db = MorphingSession(selector=sel, zoo=zoo,
                         model_store="decoupled" if delta else "blob")
    rng = np.random.default_rng(0)
    n = 600
    db.register_table("reviews", {
        "gender": rng.integers(0, 2, n),
        "len": rng.integers(1, 200, n),
        "emb": rng.standard_normal((n, 16)).astype(np.float32)})

    print(db.sql(
        "CREATE TASK sentiment_classifier (INPUT=Series, "
        "OUTPUT IN ('POS','NEG','NEU'), TYPE='Classification');"))

    sample = make_task(rng, "gauss", n=128, dim=16, classes=3)
    res = db.sql(
        "SELECT gender, AVG(sentiment_classifier(emb)) FROM reviews "
        "WHERE len > 20 GROUP BY gender;",
        sample=(sample.X, sample.y))
    rep = res.report
    print(f"(system resolved sentiment_classifier -> "
          f"{rep.resolution['sentiment_classifier']})")
    for g, s in zip(res.rows["gender"], res.rows["mean__score"]):
        print(f"  gender={g}: AVG(sentiment)={s:+.4f}")
    print(f"(plan: {rep.plan})")
    print(f"(rows {rep.rows_in} -> {rep.rows_out}, "
          f"batches={rep.batch_batches}, "
          f"share {rep.share_hits}h/{rep.share_misses}m)")

    # the same query again: pre-embeddings come back from the share cache
    res2 = db.sql(
        "SELECT gender, AVG(sentiment_classifier(emb)) FROM reviews "
        "WHERE len > 20 GROUP BY gender;")
    print(f"(second run share hit rate: "
          f"{res2.report.share_hit_rate:.2f})")

    if delta:
        # a head-only fine-tune of the resolved model: stored as deltas
        # (unchanged layers are references, the new head a delta file)
        # and served by base+delta composition — the trunk identity is
        # inherited, so even its *first* query hits the share cache
        base = db.models["sentiment_classifier"]
        w = np.abs(rng.standard_normal(base.head_dim)).astype(np.float32)
        w /= w.sum()
        ft_id = f"{base.model_id}-ft0"
        db.register_finetune(ft_id, base.model_id, {"head/w": w})
        print(db.sql(
            "CREATE TASK sentiment_ft (INPUT=Series, "
            "OUTPUT IN ('POS','NEG','NEU'), TYPE='Classification');"))
        rm = db.resolve_task("sentiment_ft", sample.X, sample.y,
                             model_id=ft_id)
        print(f"(fine-tune {ft_id}: {rm.delta_bytes}B of deltas on disk, "
              f"{rm.loaded_bytes}B read at resolve, shares trunk "
              f"{rm.trunk_fp == base.trunk_fp})")
        res3 = db.sql(
            "SELECT gender, AVG(sentiment_ft(emb)) FROM reviews "
            "WHERE len > 20 GROUP BY gender;")
        for g, s in zip(res3.rows["gender"], res3.rows["mean__score"]):
            print(f"  gender={g}: AVG(sentiment_ft)={s:+.4f}")
        print(f"(fine-tune first-query share hit rate: "
              f"{res3.report.share_hit_rate:.2f}, "
              f"delta bytes in report: {res3.report.delta_bytes})")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--delta", action="store_true",
                    help="add a fine-tune delta variant sharing the "
                         "base trunk's cached embeddings")
    main(delta=ap.parse_args().delta)
