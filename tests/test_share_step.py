"""The one share step, ``CacheChain.step``, as each of its callers runs
it: the served lanes, similarity scoring and the analytics embed nodes,
over the exact tier alone and with the ANN tier behind it. Each case
meets a half-warm cache with a seeded batch holding planted duplicates,
so the step's counts are known before it runs."""
import numpy as np
import pytest

from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.engine import EngineConfig, MorphingServer, MorphingSession

DIM = 16
N_WARM, N_COLD = 24, 24              # distinct rows, cached / not cached
DUP_WARM, DUP_COLD = 4, 8            # planted repeats of each kind
TIERS = {"exact": ("exact",), "ann": ("exact", "ann")}


@pytest.fixture(scope="module")
def zoo():
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=120, dim=DIM, classes=3)
    return [pretrain_model(src, width=12, seed=1, name="m0")]


@pytest.fixture(scope="module")
def sample():
    return make_task(np.random.default_rng(1), "gauss", n=128, dim=DIM,
                     classes=3)


def session(tmp_path, zoo, sample, tiers):
    sess = MorphingSession(zoo=zoo, root=tmp_path, backend="numpy",
                           model_store="decoupled", auto_calibrate=False,
                           chunk_rows=256,
                           config=EngineConfig(cache_tiers=TIERS[tiers]))
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0
    sess.resolve_task("sent", sample.X, sample.y)
    return sess


def seeded_batch(seed):
    """``(warm, X)``: the rows to cache first, and a shuffled batch of
    the warm rows, as many cold ones and planted repeats of both."""
    rng = np.random.default_rng(seed)
    D = rng.standard_normal((N_WARM + N_COLD, DIM)).astype(np.float32)
    warm, cold = D[:N_WARM], D[N_WARM:]
    dups = np.concatenate([
        warm[rng.choice(N_WARM, DUP_WARM, replace=False)],
        cold[rng.choice(N_COLD, DUP_COLD, replace=False)]])
    X = np.concatenate([D, dups])
    return warm, X[rng.permutation(len(X))]


def record(chain):
    """Keep every ``(rows, ShareStep)`` the chain's step returns."""
    seen = []
    step = chain.step

    def recorded(table, column, rows, embed_fn, version="v1"):
        st = step(table, column, rows, embed_fn, version)
        seen.append((np.array(rows), st))
        return st
    chain.step = recorded
    return seen


def served(sess, warm, X):
    with MorphingServer(session=sess) as server:
        server.result(server.submit_rows("sent", warm), timeout=60.0)
        server.result(server.submit_rows("sent", X), timeout=60.0)


def similarity(sess, warm, X):
    rm = sess.models["sent"]
    sess._sim_embed("reviews", "emb", warm, rm)
    sess._sim_embed("reviews", "emb", X, rm)


def analytics(sess, warm, X):
    for rows in (warm, X):
        sess.register_table("reviews", {"emb": rows})
        sess.sql("PREDICT emb USING TASK sent FROM reviews")


@pytest.mark.parametrize("caller,tiers", [
    (served, "exact"), (served, "ann"), (similarity, "exact"),
    (similarity, "ann"), (analytics, "ann")],
    ids=["served-exact", "served-ann", "similarity-exact",
         "similarity-ann", "analytics-ann"])
def test_share_step_per_caller(tmp_path, zoo, sample, caller, tiers):
    sess = session(tmp_path, zoo, sample, tiers)
    warm, X = seeded_batch(7)
    seen = record(sess.cache_chain)
    caller(sess, warm, X)
    assert len(seen) == 2                    # the warm-up, then the batch
    rows, st = seen[-1]
    np.testing.assert_array_equal(rows, X)
    np.testing.assert_allclose(
        st.E, sess.models["sent"].features(X), atol=1e-5)
    assert (st.hits, st.misses, st.approx_hits) == (
        N_WARM + DUP_WARM, N_COLD + DUP_COLD, 0)
    assert (st.folded, st.computed) == (DUP_COLD, N_COLD)
    assert st.false_accepts == 0


def test_server_counters_are_the_share_step_sums(tmp_path, zoo, sample):
    sess = session(tmp_path, zoo, sample, "exact")
    seen = record(sess.cache_chain)
    warm, X = seeded_batch(11)
    _, Y = seeded_batch(12)
    with MorphingServer(session=sess) as server:
        for rows in (warm, X, X, Y, warm):
            server.result(server.submit_rows("sent", rows), timeout=60.0)
        stats = server.stats()
    steps = [st for _, st in seen]
    assert len(steps) == stats.batches == 5
    assert stats.share_hits == sum(st.hits for st in steps) > 0
    assert stats.share_misses == sum(st.misses for st in steps) > 0
    assert stats.dedup_rows == sum(st.folded for st in steps) > 0
    assert stats.approx_hits == sum(st.approx_hits for st in steps) == 0
    assert stats.embed_rows == sum(st.computed for st in steps)
