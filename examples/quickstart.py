"""Quickstart: the MorphingDB task-centric flow in 60 lines.

  1. Build a model zoo + historical transfer matrix (offline).
  2. Fit the two-phase selector (NMF subspace + feature regressor).
  3. CREATE TASK, resolve it to a model for *your* data, run a query.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (ModelSelector, TaskFeaturizer, TaskRegistry,
                        TaskSpec, build_tasks, build_zoo, make_task,
                        transfer_matrix)
from repro.device import enable_compile_cache
from repro.pipeline import Dag, Node, PipelineExecutor, filter_op, groupby_agg


def main() -> None:
    # ---- offline phase (done once, per §4.2) --------------------------
    zoo = build_zoo(16, seed=0)
    history = build_tasks(32, seed=1)
    V = transfer_matrix(zoo, history)          # historical transfer matrix
    fz = TaskFeaturizer()
    feats = np.stack([fz.features(t.X, t.y) for t in history])
    selector = ModelSelector(k=6, n_anchors=3).fit_offline(V, feats, zoo=zoo)
    print(f"offline: |zoo|={len(zoo)} |history|={len(history)} "
          f"NMF recon err={selector.recon_error:.4f}")

    # ---- task-centric declaration (Table 1) ---------------------------
    registry = TaskRegistry(selector=selector, zoo=zoo)
    registry.create_task(TaskSpec(
        name="sentiment_classifier", input_type="series",
        output_labels=("POS", "NEG", "NEU"), kind="classification"))

    # a new, unseen task arrives with sample data
    rng = np.random.default_rng(42)
    task = make_task(rng, "ring", n=200, dim=16, classes=3)
    chosen = registry.resolve("sentiment_classifier", task.X, task.y)
    print(f"online: resolved to zoo model #{chosen} "
          f"({zoo[chosen].name}) in {selector.select(task.X, task.y).online_ms:.1f} ms")

    # ---- declarative query over the resolved task ---------------------
    predict = registry.predict_fn("sentiment_classifier")
    n = 500
    reviews = {"gender": rng.integers(0, 2, n),
               "len": rng.integers(1, 200, n),
               "emb": rng.standard_normal((n, 16)).astype(np.float32)}

    def predict_node(b):
        out = dict(b)
        out["sentiment"] = predict(b["emb"]).mean(axis=1)
        return out

    dag = Dag()
    dag.add(Node("reviews", "scan"))
    dag.add(Node("flt", "filter",
                 fn=lambda b: filter_op(b, lambda x: x["len"] > 20)),
            deps=("reviews",))
    dag.add(Node("pred", "predict", fn=predict_node, cost_hint=5),
            deps=("flt",))
    dag.add(Node("agg", "groupby",
                 fn=lambda b: groupby_agg(b, "gender", "sentiment")),
            deps=("pred",))
    res = PipelineExecutor(dag).execute({"reviews": reviews})
    for g, s in zip(res["agg"]["gender"], res["agg"]["mean_sentiment"]):
        print(f"  gender={g}: avg sentiment {s:+.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
