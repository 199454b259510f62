"""Serving path: ContinuousBatcher lifecycle, MorphingServer coalescing,
and partial-load resolution byte accounting on the DecoupledStore."""
import threading
import time

import numpy as np
import pytest

from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.engine import MorphingServer, MorphingSession
from repro.engine.config import EngineConfig
from repro.pipeline import ContinuousBatcher, OpProfile, Request
from repro.storage import Catalog, DecoupledStore

PROF = OpProfile(flops_per_row=1e5, bytes_per_row=128, model_bytes=1e6)


# -- fixtures --------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_zoo():
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=120, dim=16, classes=3)
    ring = make_task(rng, "ring", n=120, dim=16, classes=3)
    return [pretrain_model(src, width=12, seed=1, name="m0"),
            pretrain_model(ring, width=12, seed=2, name="m1",
                           mode="radial")]


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(0)
    n = 600
    return {"gender": rng.integers(0, 2, n),
            "len": rng.integers(1, 200, n),
            "emb": rng.standard_normal((n, 16)).astype(np.float32)}


@pytest.fixture(scope="module")
def sample():
    return make_task(np.random.default_rng(1), "gauss", n=128, dim=16,
                     classes=3)


def make_session(tmp_path, zoo, table, *, model_store="decoupled",
                 backend="numpy", resolution=0, **kw):
    sess = MorphingSession(zoo=zoo, root=tmp_path, model_store=model_store,
                          backend=backend, **kw)
    sess.register_table("reviews", {k: v.copy() for k, v in table.items()})
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = resolution
    return sess


# -- ContinuousBatcher lifecycle ------------------------------------------

def test_batcher_duplicate_req_id_raises():
    cb = ContinuousBatcher(lambda xs: xs, PROF, device="host")
    cb.submit(Request(1, 1.0))
    with pytest.raises(ValueError, match="duplicate"):
        cb.submit(Request(1, 2.0))


def test_batcher_run_returns_exactly_the_submitted_set():
    """total not a batch multiple: run() must not overcount past total."""
    calls = []

    def step(ps):
        calls.append(len(ps))
        return [p * 2 for p in ps]

    cb = ContinuousBatcher(step, PROF, device="host", max_wait_s=0.001)
    for i in range(10):
        cb.submit(Request(i, float(i)))
    res = cb.run(total=7)
    assert sum(calls) == 7          # exactly 7 served, 3 still queued
    assert len(res) == 7
    rest = cb.run(total=3)
    assert set(rest) == set(range(10))


def test_batcher_service_mode_concurrent_submitters():
    cb = ContinuousBatcher(lambda xs: [x + 1 for x in xs], PROF,
                           device="host", max_wait_s=0.002,
                           idle_wait_s=0.01).start()
    ids = list(range(40))

    def client(lo):
        for i in range(lo, lo + 10):
            cb.submit(Request(i, float(i)))

    threads = [threading.Thread(target=client, args=(lo,))
               for lo in range(0, 40, 10)]
    for t in threads:
        t.start()
    outs = {i: cb.result(i, timeout=5.0) for i in ids}
    for t in threads:
        t.join()
    cb.stop()
    assert outs == {i: i + 1.0 for i in ids}
    assert len(cb.latencies) == 40
    assert max(cb.batch_sizes) > 1      # actually coalesced


def test_batcher_stop_drains_queue():
    served = []

    def slow_step(ps):
        time.sleep(0.01)
        served.extend(ps)
        return ps

    cb = ContinuousBatcher(slow_step, PROF, device="host",
                           max_wait_s=0.001, idle_wait_s=0.01).start()
    for i in range(25):
        cb.submit(Request(i, i))
    cb.stop(drain=True)
    assert sorted(served) == list(range(25))
    with pytest.raises(RuntimeError, match="stopped"):
        cb.submit(Request(99, 1))


def test_batcher_stop_without_drain_fails_pending():
    release = threading.Event()

    def blocked_step(ps):
        release.wait(1.0)
        return ps

    cb = ContinuousBatcher(blocked_step, PROF, device="host",
                           batch_size=1, max_wait_s=0.0,
                           idle_wait_s=0.01).start()
    cb.submit(Request(0, 0))
    time.sleep(0.05)                 # worker is inside step 0
    for i in range(1, 8):
        cb.submit(Request(i, i))
    cb.stop(drain=False)
    release.set()
    dropped = 0
    for i in range(8):
        try:
            cb.result(i, timeout=1.0)
        except RuntimeError:
            dropped += 1
    assert dropped > 0               # queued requests were failed, not lost


def test_batcher_stop_join_timeout_raises_then_retries():
    """A worker wedged inside its step surfaces as TimeoutError from
    stop() instead of hanging the caller; once the step returns, a
    second stop() retries the join and succeeds."""
    release = threading.Event()
    entered = threading.Event()

    def wedged_step(ps):
        entered.set()
        release.wait(5.0)
        return ps

    cb = ContinuousBatcher(wedged_step, PROF, device="host",
                           batch_size=1, max_wait_s=0.0,
                           idle_wait_s=0.01).start()
    cb.submit(Request(0, 0))
    assert entered.wait(2.0)         # worker is inside the wedged step
    with pytest.raises(TimeoutError, match="did not join"):
        cb.stop(drain=False, timeout=0.2)
    release.set()
    cb.stop(drain=False, timeout=5.0)    # retry joins cleanly
    assert cb.result(0, timeout=1.0) == 0


def test_batcher_stop_drains_inline_when_never_started():
    """stop(drain=True) with no worker thread must serve the queue on
    the calling thread rather than orphan admitted requests."""
    cb = ContinuousBatcher(lambda xs: [x * 3 for x in xs], PROF,
                           device="host", max_wait_s=0.001,
                           idle_wait_s=0.01)
    for i in range(5):
        cb.submit(Request(i, float(i)))
    res = cb.stop(drain=True)
    assert res == {i: i * 3.0 for i in range(5)}


def test_batcher_step_error_propagates_to_result():
    def bad_step(ps):
        raise RuntimeError("boom")

    cb = ContinuousBatcher(bad_step, PROF, device="host",
                           idle_wait_s=0.01).start()
    cb.submit(Request(0, 1.0))
    with pytest.raises(RuntimeError, match="boom"):
        cb.result(0, timeout=5.0)
    cb.stop()


def test_batcher_run_raises_step_error():
    """One-shot mode has no result() call: run() must fail loudly, not
    hand back internal failure sentinels as model outputs."""
    cb = ContinuousBatcher(lambda ps: 1 / 0, PROF, device="host",
                           max_wait_s=0.001)
    cb.submit(Request(0, 1.0))
    with pytest.raises(ZeroDivisionError):
        cb.run(total=1)


def test_batcher_result_evicts_by_default():
    """Service mode must stay memory-bounded: a result is retrievable
    once, then its stored state is released."""
    cb = ContinuousBatcher(lambda xs: xs, PROF, device="host",
                           idle_wait_s=0.01).start()
    cb.submit(Request(0, 1.0))
    assert cb.result(0, timeout=5.0) == 1.0
    with pytest.raises(KeyError):
        cb.result(0, timeout=0.1)            # evicted
    cb.submit(Request(0, 2.0))               # req_id slot is reusable
    assert cb.result(0, timeout=5.0) == 2.0
    cb.stop()


def test_batcher_row_aware_sizing():
    """size_of counts payload rows: the row budget, not the request
    count, closes a batch."""
    sizes = []
    cb = ContinuousBatcher(lambda xs: xs, batch_size=100, size_of=len,
                           max_wait_s=0.05, idle_wait_s=0.01)
    for i in range(6):
        cb.submit(Request(i, list(range(40))))    # 40 rows each
    cb.run(total=6)
    # 100-row budget -> 3 requests (120 rows) per batch, not all 6
    assert max(cb.batch_sizes) <= 3


# -- MorphingServer --------------------------------------------------------

def test_server_concurrent_submitters_match_engine(tmp_path, serve_zoo,
                                                   table, sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    ref = {thr: sess.sql(f"PREDICT emb USING TASK sent FROM reviews "
                         f"WHERE len > {thr}").rows["_score"]
           for thr in (20, 60, 100)}
    server = MorphingServer(session=sess, max_wait_s=0.002)
    with server:
        ids = {}

        def client(thr):
            ids[thr] = [server.submit(
                "PREDICT emb USING TASK sent FROM reviews "
                f"WHERE len > {thr}") for _ in range(4)]

        threads = [threading.Thread(target=client, args=(t,))
                   for t in (20, 60, 100)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for thr, rids in ids.items():
            for rid in rids:
                out = server.result(rid, timeout=10.0)
                np.testing.assert_allclose(out.scores, ref[thr],
                                           atol=1e-5)
                assert out.latency_s >= 0.0


def test_server_coalesces_same_task_requests(tmp_path, serve_zoo, table,
                                             sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    server = MorphingServer(session=sess, max_wait_s=0.05)
    with server:
        rids = [server.submit("PREDICT emb USING TASK sent FROM reviews "
                              "WHERE len > 150") for _ in range(12)]
        for rid in rids:
            server.result(rid, timeout=10.0)
    st = server.stats()
    assert st.requests == 12
    assert st.batches < 12                   # requests shared batches
    assert st.mean_coalesced > 1.0
    assert st.rows > 0 and st.infer_seconds > 0.0


def test_server_stats_latency_percentiles(tmp_path, serve_zoo, table,
                                          sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    server = MorphingServer(session=sess)
    with server:
        for rid in [server.submit("PREDICT emb USING TASK sent "
                                  "FROM reviews") for _ in range(6)]:
            server.result(rid, timeout=10.0)
    st = server.stats()
    assert 0.0 < st.p50_latency_s <= st.p95_latency_s <= st.max_latency_s
    assert st.requests_by_task == {"sent": 6}
    assert st.stored_bytes > 0


def test_server_stop_drains_submitted_requests(tmp_path, serve_zoo, table,
                                               sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    server = MorphingServer(session=sess, max_wait_s=0.001).start()
    rids = [server.submit("PREDICT emb USING TASK sent FROM reviews")
            for _ in range(10)]
    server.stop(drain=True)                  # no result() calls yet
    for rid in rids:
        out = server.result(rid, timeout=0.1)   # already served
        assert out.rows == 600


def test_server_rejects_analytics_sql(tmp_path, serve_zoo, table, sample):
    sess = make_session(tmp_path, serve_zoo, table)
    server = MorphingServer(session=sess)
    with pytest.raises(ValueError, match="PREDICT"):
        server.submit("SELECT gender, AVG(sent(emb)) FROM reviews "
                      "GROUP BY gender")


def test_server_submit_before_start_raises(tmp_path, serve_zoo, table,
                                           sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    server = MorphingServer(session=sess)
    with pytest.raises(RuntimeError, match="not started"):
        server.submit("PREDICT emb USING TASK sent FROM reviews")


def test_server_resolves_on_first_request(tmp_path, serve_zoo, table,
                                          sample):
    sess = make_session(tmp_path, serve_zoo, table)
    server = MorphingServer(session=sess)
    with server:
        out = server.predict("PREDICT emb USING TASK sent FROM reviews",
                             sample=(sample.X, sample.y), timeout=10.0)
    assert out.rows == 600
    assert "sent" in sess.models


def test_server_jax_backend_parity(tmp_path, serve_zoo, table, sample):
    ref_sess = make_session(tmp_path / "np", serve_zoo, table)
    ref_sess.resolve_task("sent", sample.X, sample.y)
    ref = ref_sess.sql("PREDICT emb USING TASK sent FROM reviews "
                       "WHERE len > 50").rows["_score"]
    sess = make_session(tmp_path / "jax", serve_zoo, table, backend="jax")
    sess.resolve_task("sent", sample.X, sample.y)
    server = MorphingServer(session=sess)
    with server:
        out = server.predict("PREDICT emb USING TASK sent FROM reviews "
                             "WHERE len > 50", timeout=30.0)
    np.testing.assert_allclose(out.scores, ref, atol=1e-5)


# -- share-aware serving: trunk lanes, dedup, head stages ------------------

def _count_features(backend):
    """Instrument a backend instance: record rows per _features call."""
    calls = []
    orig = backend._features

    def counting(spec, X, _o=orig):
        calls.append(len(X))
        return _o(spec, X)

    backend._features = counting
    return calls


def test_concurrent_identical_requests_embed_once(tmp_path, serve_zoo,
                                                  table, sample):
    """N threads submitting identical PREDICT rows must produce exactly
    one embed computation: in-flight duplicates fold in-batch, and
    later batches hit the cache written back by earlier ones."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    calls = _count_features(sess.backends["host"])
    n_rows = int((table["len"] > 100).sum())
    n_clients = 8
    # generous coalescing window: the dedup assertion needs at least one
    # batch to carry two identical requests even on a loaded scheduler
    server = MorphingServer(session=sess, max_wait_s=0.2)
    with server:
        rids = []
        lock = threading.Lock()

        def client():
            rid = server.submit("PREDICT emb USING TASK sent FROM "
                                "reviews WHERE len > 100")
            with lock:
                rids.append(rid)

        threads = [threading.Thread(target=client)
                   for _ in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        outs = [server.result(r, timeout=10.0) for r in rids]
    assert all(o.rows == n_rows for o in outs)
    assert sum(calls) == n_rows              # the one and only trunk pass
    st = server.stats()
    assert st.embed_rows == n_rows
    assert st.head_rows == n_clients * n_rows
    assert st.dedup_rows + st.share_hits == (n_clients - 1) * n_rows
    assert st.dedup_rows > 0                 # in-flight dedup exercised
    assert st.dedup_rate > 0.0


def test_tasks_sharing_trunk_share_one_lane(tmp_path, serve_zoo, table,
                                            sample):
    """Two tasks resolving to the same stored model feed one embed lane
    and reuse each other's cached rows (cross-task trunk sharing)."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    sess.create_task(TaskSpec("sent2", "series", ("P", "N")))
    sess.registry._resolution["sent2"] = 0
    sess.resolve_task("sent2", sample.X, sample.y)
    assert (sess.models["sent"].trunk_fp
            == sess.models["sent2"].trunk_fp != "")
    ref = sess.sql("PREDICT emb USING TASK sent FROM reviews "
                   "WHERE len > 50").rows["_score"]
    server = MorphingServer(session=sess, max_wait_s=0.001)
    with server:
        out1 = server.predict("PREDICT emb USING TASK sent FROM reviews "
                              "WHERE len > 50", timeout=10.0)
        out2 = server.predict("PREDICT emb USING TASK sent2 FROM reviews "
                              "WHERE len > 50", timeout=10.0)
    np.testing.assert_allclose(out1.scores, ref, atol=1e-5)
    np.testing.assert_allclose(out2.scores, ref, atol=1e-5)
    assert len(server._lanes) == 1
    st = server.stats()
    assert st.requests_by_task == {"sent": 1, "sent2": 1}
    # the second task's rows were embedded by the first task's traffic
    assert st.share_hits >= out2.rows
    lane_key = sess.models["sent"].trunk_fp
    assert st.share_hit_rate_by_lane[lane_key] > 0.0


def test_distinct_trunks_get_distinct_lanes(tmp_path, serve_zoo, table,
                                            sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    sess.create_task(TaskSpec("ring", "series", ("P", "N")))
    sess.registry._resolution["ring"] = 1     # the radial model
    sess.resolve_task("ring", sample.X, sample.y)
    server = MorphingServer(session=sess)
    with server:
        server.predict("PREDICT emb USING TASK sent FROM reviews",
                       timeout=10.0)
        server.predict("PREDICT emb USING TASK ring FROM reviews",
                       timeout=10.0)
    assert len(server._lanes) == 2


def test_served_scores_match_session_sql(tmp_path, serve_zoo, table,
                                         sample):
    """The embed/head split must be invisible in the scores: a served
    PREDICT answers what ``session.sql`` answers over the same WHERE."""
    sql = "PREDICT emb USING TASK sent FROM reviews WHERE len > 30"
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    ref = sess.sql(sql).rows["_score"]
    with MorphingServer(session=sess) as server:
        got = server.predict(sql, timeout=10.0).scores
    assert len(got) == len(ref) == int((table["len"] > 30).sum())
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_embed_head_budgets_split(tmp_path, serve_zoo, table, sample):
    """Eq. 11 sizes the head stage independently of the embed lane: the
    head profile is orders cheaper per row, so its budget must be at
    least as large."""
    from repro.pipeline.cost import split_profile

    sess = make_session(tmp_path, serve_zoo, table)
    rm = sess.resolve_task("sent", sample.X, sample.y)
    embed_p, head_p = split_profile(rm.profile, rm.head_dim)
    assert head_p.flops_per_row < embed_p.flops_per_row
    assert head_p.model_bytes < embed_p.model_bytes
    server = MorphingServer(session=sess)
    with server:
        server.predict("PREDICT emb USING TASK sent FROM reviews",
                       timeout=10.0)
    (lane,) = server._lanes.values()
    assert lane.heads["sent"].batch_rows >= lane.batch_rows


def test_server_reset_telemetry_rebases_window(tmp_path, serve_zoo,
                                               table, sample):
    """Percentiles/counters must be computable over a consistent window:
    after reset, stats reflect only post-reset traffic (the warmup
    samples no longer skew p50/p95)."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    server = MorphingServer(session=sess)
    with server:
        for _ in range(4):                   # warmup traffic
            server.predict("PREDICT emb USING TASK sent FROM reviews",
                           timeout=10.0)
        assert server.stats().requests == 4
        server.reset_telemetry()
        st0 = server.stats()
        assert st0.requests == 0 and st0.rows == 0
        assert st0.p95_latency_s == 0.0 and st0.share_hits == 0
        server.predict("PREDICT emb USING TASK sent FROM reviews",
                       timeout=10.0)
        st = server.stats()
    assert st.requests == 1 and st.rows == 600
    assert st.batches == 1
    assert 0.0 < st.p50_latency_s <= st.p95_latency_s
    assert st.share_hits == 600              # warm rows survive the reset


def test_write_back_races_lane_shutdown(tmp_path, serve_zoo, table,
                                        sample):
    """stop(drain=True) racing concurrent submits: every admitted
    request is served, its scores correct, and the drained batches'
    cache write-backs land (a fresh server over the same session starts
    warm)."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    ref = sess.sql("PREDICT emb USING TASK sent FROM reviews "
                   "WHERE len > 50").rows["_score"]
    server = MorphingServer(session=sess, max_wait_s=0.005).start()
    admitted, rejected = [], []
    lock = threading.Lock()

    def client():
        for _ in range(5):
            try:
                rid = server.submit("PREDICT emb USING TASK sent FROM "
                                    "reviews WHERE len > 50")
                with lock:
                    admitted.append(rid)
            except RuntimeError:             # raced the stop: rejected
                with lock:
                    rejected.append(1)

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    server.stop(drain=True)                  # race the submitters
    for t in threads:
        t.join()
    for rid in admitted:
        out = server.result(rid, timeout=5.0)    # drained, never lost
        np.testing.assert_allclose(out.scores, ref, atol=1e-5)
    if admitted:                             # write-backs survived stop
        server2 = MorphingServer(session=sess, max_wait_s=0.001)
        with server2:
            server2.predict("PREDICT emb USING TASK sent FROM reviews "
                            "WHERE len > 50", timeout=10.0)
        st2 = server2.stats()
        assert st2.share_hits == len(ref) and st2.embed_rows == 0


def test_stop_without_drain_fails_pending_cleanly(tmp_path, serve_zoo,
                                                  table, sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    server = MorphingServer(session=sess, max_wait_s=0.05,
                            idle_wait_s=0.2).start()
    rids = [server.submit("PREDICT emb USING TASK sent FROM reviews")
            for _ in range(6)]
    server.stop(drain=False)
    outcomes = {"served": 0, "failed": 0}
    for rid in rids:
        try:
            server.result(rid, timeout=1.0)
            outcomes["served"] += 1
        except RuntimeError:
            outcomes["failed"] += 1
    assert outcomes["served"] + outcomes["failed"] == 6


def test_server_stop_surfaces_stuck_lane(tmp_path, serve_zoo, table,
                                         sample):
    """A lane worker wedged in a backend call must not hang stop():
    the join times out and the server raises RuntimeError naming the
    stuck lane, with pending results marked undeliverable."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    server = MorphingServer(session=sess, max_wait_s=0.01,
                            idle_wait_s=0.05).start()
    release = threading.Event()
    entered = threading.Event()
    backend = sess.backends["host"]        # numpy pool: every annotation
    orig = backend.run_infer               # shares this instance

    def wedged_run_infer(spec, batch):
        entered.set()
        release.wait(10.0)
        return orig(spec, batch)

    backend.run_infer = wedged_run_infer
    try:
        server.submit("PREDICT emb USING TASK sent FROM reviews")
        assert entered.wait(5.0)           # worker is inside the backend
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="did not join") as ei:
            server.stop(drain=False, timeout=0.2)
        assert time.perf_counter() - t0 < 5.0   # bounded, not hung
        # the error names which lane is wedged
        assert any(k in str(ei.value) for k in server._lanes)
    finally:
        release.set()
        backend.run_infer = orig


def test_head_mode_task_served_warm_keeps_trunk_on_disk(tmp_path,
                                                        serve_zoo, table,
                                                        sample):
    """The server-side embed split preserves the partial-load story: a
    head-mode task whose rows are already cached never materializes its
    trunk."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    sess.create_task(TaskSpec("sent2", "series", ("P", "N")))
    sess.registry._resolution["sent2"] = 0
    rm2 = sess.resolve_task("sent2", sample.X, sample.y, mode="head")
    ref = sess.sql("PREDICT emb USING TASK sent FROM reviews").rows["_score"]
    server = MorphingServer(session=sess)
    with server:
        server.predict("PREDICT emb USING TASK sent FROM reviews",
                       timeout=10.0)         # warms the lane's row cache
        out = server.predict("PREDICT emb USING TASK sent2 FROM reviews",
                             timeout=10.0)
    np.testing.assert_allclose(out.scores, ref, atol=1e-5)
    assert not rm2.zoo_model.materialized    # share hits: trunk on disk


# -- partial-load resolution ----------------------------------------------

def test_decoupled_loaded_bytes_accounting(tmp_path):
    cat = Catalog(tmp_path / "cat")
    ds = DecoupledStore(tmp_path / "dec", cat, cache_layers=False)
    W = np.arange(64, dtype=np.float32).reshape(16, 4)
    ds.save("m", {"v": 1}, {"trunk/W": W,
                            "head/w": np.ones(4, np.float32)})
    _, flat = ds.load("m")
    full_bytes = ds.stats.loaded_bytes
    assert full_bytes >= W.nbytes + 16      # payload + headers
    assert ds.stats.loads == 1 and ds.stats.partial_loads == 0

    _, head = ds.load("m", layer_filter=lambda n: n.startswith("head/"))
    head_bytes = ds.stats.loaded_bytes - full_bytes
    assert set(head) == {"head/w"}
    assert 0 < head_bytes < W.nbytes
    assert ds.stats.partial_loads == 1


def test_decoupled_load_layer_rows_counts_slice_bytes(tmp_path):
    ds = DecoupledStore(tmp_path / "dec", cache_layers=False)
    W = np.arange(128, dtype=np.float32).reshape(16, 8)
    ds.save("m", {"v": 1}, {"trunk/W": W})
    part = ds.load_layer_rows("m", "trunk/W", 0, 4)
    np.testing.assert_array_equal(part, W[:4])
    assert ds.stats.loaded_bytes == part.nbytes       # only the slice
    assert ds.stats.partial_loads == 1


def test_decoupled_layer_cache_shares_across_loads(tmp_path):
    ds = DecoupledStore(tmp_path / "dec")
    W = np.ones((8, 4), np.float32)
    ds.save("m", {"v": 1}, {"trunk/W": W})
    ds.load("m")
    first = ds.stats.loaded_bytes
    ds.load("m")                             # second load: cache tier
    assert ds.stats.loaded_bytes == first
    assert ds.stats.cache_hits == 1
    assert ds.stats.cache_hit_bytes == W.nbytes


def test_layer_cache_save_keeps_prefix_sibling_models(tmp_path):
    """Saving 'm1' must not evict cached layers of 'm10'."""
    ds = DecoupledStore(tmp_path / "dec")
    W = np.ones((8, 4), np.float32)
    ds.save("m10", {"v": 1}, {"trunk/W": W})
    ds.load("m10")
    ds.save("m1", {"v": 1}, {"trunk/W": 2 * W})
    ds.load("m10")                           # still cache-served
    assert ds.stats.cache_hits == 1


def test_partial_resolution_slices_trunk_width(tmp_path, serve_zoo):
    """A narrow table only pulls the trunk rows its width touches."""
    rng = np.random.default_rng(0)
    table8 = {"len": rng.integers(1, 200, 200),
              "emb": rng.standard_normal((200, 8)).astype(np.float32)}
    sess = make_session(tmp_path, serve_zoo, table8)
    sample8 = make_task(np.random.default_rng(2), "gauss", n=96, dim=8,
                        classes=3)
    rm = sess.resolve_task("sent", sample8.X, sample8.y, mode="partial")
    assert rm.loaded_bytes < rm.stored_bytes
    assert "+w8" in rm.version               # slice-tagged embedder
    res = sess.sql("PREDICT emb USING TASK sent FROM reviews "
                   "WHERE len > 50")
    assert res.report.loaded_bytes < res.report.stored_bytes
    # parity: zero-padded inputs through the full trunk give the same
    # scores as the sliced trunk
    full = make_session(tmp_path / "full", serve_zoo, table8)
    full.resolve_task("sent", sample8.X, sample8.y, mode="full")
    ref = full.sql("PREDICT emb USING TASK sent FROM reviews "
                   "WHERE len > 50")
    np.testing.assert_allclose(res.rows["_score"], ref.rows["_score"],
                               atol=1e-5)


def test_head_only_resolution_skips_trunk_on_share_hit(tmp_path,
                                                       serve_zoo, table,
                                                       sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y, mode="full")
    sess.sql("PREDICT emb USING TASK sent FROM reviews")  # warm share
    sess.dstore.cache_layers = False         # count true disk bytes
    sess.create_task(TaskSpec("sent2", "series", ("P", "N")))
    sess.registry._resolution["sent2"] = 0
    rm2 = sess.resolve_task("sent2", sample.X, sample.y, mode="head")
    res = sess.sql("PREDICT emb USING TASK sent2 FROM reviews")
    assert not rm2.zoo_model.materialized    # share hits: trunk on disk
    assert 0 < rm2.loaded_bytes < rm2.stored_bytes
    ref = sess.sql("PREDICT emb USING TASK sent FROM reviews")
    np.testing.assert_allclose(res.rows["_score"], ref.rows["_score"],
                               atol=1e-5)


def test_resolve_mode_conflict_with_cached_resolution(tmp_path,
                                                      serve_zoo, table,
                                                      sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)       # default: full
    with pytest.raises(ValueError, match="force=True"):
        sess.resolve_task("sent", sample.X, sample.y, mode="head")

    class _Pinned:                       # force re-runs selection
        def select(self, X, y):
            return type("R", (), {"chosen": 0})()

    sess.registry.selector = _Pinned()
    rm = sess.resolve_task("sent", sample.X, sample.y, mode="head",
                           force=True)
    assert rm.load_mode == "head"


def test_head_mode_lazily_loads_trunk_on_cold_embed(tmp_path, serve_zoo,
                                                    table, sample):
    sess = make_session(tmp_path, serve_zoo, table, enable_share=False)
    rm = sess.resolve_task("sent", sample.X, sample.y, mode="head")
    head_bytes = rm.loaded_bytes
    assert not rm.zoo_model.materialized
    sess.sql("PREDICT emb USING TASK sent FROM reviews")  # cold: needs it
    assert rm.zoo_model.materialized
    assert rm.loaded_bytes > head_bytes


def test_radial_partial_skips_projection(tmp_path, serve_zoo, table,
                                         sample):
    sess = make_session(tmp_path, serve_zoo, table, resolution=1)
    rm = sess.resolve_task("sent", sample.X, sample.y, mode="partial")
    assert rm.loaded_bytes < rm.stored_bytes     # identity W never read
    res = sess.sql("PREDICT emb USING TASK sent FROM reviews "
                   "WHERE len > 50")
    blob = make_session(tmp_path / "blob", serve_zoo, table,
                        model_store="blob", resolution=1)
    blob.resolve_task("sent", sample.X, sample.y)
    ref = blob.sql("PREDICT emb USING TASK sent FROM reviews "
                   "WHERE len > 50")
    np.testing.assert_allclose(res.rows["_score"], ref.rows["_score"],
                               atol=1e-5)


def test_auto_calibrate_populates_measured_hw(tmp_path, serve_zoo):
    sess = MorphingSession(zoo=serve_zoo, root=tmp_path, backend="numpy")
    assert sess.hw and all(p.measured for p in sess.hw.values())
    off = MorphingSession(zoo=serve_zoo, root=tmp_path / "off",
                          backend="numpy", auto_calibrate=False)
    assert off.hw is None


# -- fine-tune delta resolution & serving ---------------------------------

def _register_fleet(sess, sample, k, seed=11):
    """K head-delta fine-tunes of the already-resolved base model m0,
    each bound to task sent_ft{i}. Returns {task: head weights}."""
    rng = np.random.default_rng(seed)
    dim = sess.models["sent"].head_dim
    heads = {}
    for i in range(k):
        w = np.abs(rng.standard_normal(dim)).astype(np.float32)
        w /= w.sum()
        name, mid = f"sent_ft{i}", f"m0-ft{i}"
        sess.register_finetune(mid, "m0", {"head/w": w})
        sess.create_task(TaskSpec(name, "series", ("P", "N")))
        sess.resolve_task(name, sample.X, sample.y, model_id=mid)
        heads[name] = w
    return heads


def test_finetune_parity_vs_materialized_full_model(tmp_path, serve_zoo,
                                                    table, sample):
    """save(base_model=) -> resolve(model_id=) -> serve must match an
    eagerly-materialized full model stored without delta encoding."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    heads = _register_fleet(sess, sample, 1)
    # eagerly-materialized twin: same weights, no base_model lineage
    arch, flat = sess.dstore.load("m0")
    flat = dict(flat, **{"head/w": heads["sent_ft0"]})
    sess.dstore.save("m0-eager", arch, flat)
    sess.create_task(TaskSpec("sent_eager", "series", ("P", "N")))
    rme = sess.resolve_task("sent_eager", sample.X, sample.y,
                            model_id="m0-eager")
    assert not rme.is_delta and sess.models["sent_ft0"].is_delta
    got = sess.sql("PREDICT emb USING TASK sent_ft0 FROM reviews "
                   "WHERE len > 40").rows["_score"]
    ref = sess.sql("PREDICT emb USING TASK sent_eager FROM reviews "
                   "WHERE len > 40").rows["_score"]
    np.testing.assert_allclose(got, ref, atol=1e-5)
    # and against the raw math on the in-memory zoo weights
    X = table["emb"][table["len"] > 40]
    np.testing.assert_allclose(
        got, serve_zoo[0].features(X) @ heads["sent_ft0"], atol=1e-5)


def test_finetune_loaded_bytes_only_delta_on_warm_base(tmp_path,
                                                       serve_zoo, table,
                                                       sample):
    """A fine-tune resolved after its base reads only delta bytes: the
    base trunk is warm in the cross-model layer cache."""
    sess = make_session(tmp_path, serve_zoo, table)
    base = sess.resolve_task("sent", sample.X, sample.y)
    b0 = sess.dstore.stats.loaded_bytes
    _register_fleet(sess, sample, 1)
    rm = sess.models["sent_ft0"]
    read = sess.dstore.stats.loaded_bytes - b0
    assert rm.is_delta and rm.base_model_id == "m0"
    assert rm.base_fp == base.trunk_fp == rm.trunk_fp != ""
    assert rm.loaded_bytes == rm.delta_bytes == read > 0
    assert rm.loaded_bytes < base.loaded_bytes
    assert rm.stored_bytes == rm.delta_bytes   # only deltas on disk
    # warm-trunk staging: Eq. 7 charges only the delta bytes
    assert rm.profile.model_bytes == float(rm.delta_bytes)
    assert base.profile.model_bytes > rm.profile.model_bytes


def test_delta_fleet_shares_one_embed_lane(tmp_path, serve_zoo, table,
                                           sample):
    """K fine-tunes + their base ride ONE embed lane; each keeps its own
    head stage and ServerStats reports the delta counters."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    heads = _register_fleet(sess, sample, 3)
    server = MorphingServer(session=sess, max_wait_s=0.001)
    X = table["emb"][table["len"] > 50]
    F = serve_zoo[0].features(X)
    with server:
        for task in ["sent"] + sorted(heads):
            out = server.predict(f"PREDICT emb USING TASK {task} "
                                 "FROM reviews WHERE len > 50",
                                 timeout=10.0)
            want = (F.mean(axis=1) if task == "sent"
                    else F @ heads[task])
            np.testing.assert_allclose(np.asarray(out.scores), want,
                                       atol=1e-5)
    assert len(server._lanes) == 1
    st = server.stats()
    assert st.lanes == 1
    assert st.tasks_by_lane == {sess.models["sent"].trunk_fp: 4}
    assert st.delta_tasks == 3
    assert st.delta_stored_bytes == sum(
        sess.models[t].delta_bytes for t in heads)
    assert st.delta_loaded_bytes == sum(
        sess.models[t].loaded_bytes for t in heads)
    # after the base's first request every fine-tune row is a share hit
    assert st.share_hits >= 3 * len(X)


def test_compressed_fleet_serving_parity_and_bytes(tmp_path, serve_zoo,
                                                   table, sample):
    """K=8 head-delta fleet served through MorphingServer with delta
    compression ON vs OFF: row-level score parity within the declared
    quantization bound, strictly fewer delta bytes read from disk, and
    the compression gauges surfaced on ServerStats/QueryReport."""
    K = 8

    def run_fleet(root, compress):
        sess = make_session(root, serve_zoo, table,
                            config=EngineConfig(compress_deltas=compress))
        sess.resolve_task("sent", sample.X, sample.y)
        heads = _register_fleet(sess, sample, K)
        scores = {}
        with MorphingServer(session=sess, max_wait_s=0.001) as server:
            for task in sorted(heads):
                out = server.predict(f"PREDICT emb USING TASK {task} "
                                     "FROM reviews WHERE len > 50",
                                     timeout=10.0)
                scores[task] = np.asarray(out.scores)
            st = server.stats()
        return sess, heads, scores, st

    sess_c, heads, got, st_c = run_fleet(tmp_path / "on", True)
    sess_u, _, ref, st_u = run_fleet(tmp_path / "off", False)
    assert sorted(got) == sorted(ref) and len(got) == K
    # parity: per-weight quant error <= declared bound, so a score row
    # F_i . w is off by at most bound * ||F_i||_1
    bound = st_c.quant_error_bound
    assert bound > 0.0
    X = table["emb"][table["len"] > 50]
    F = serve_zoo[0].features(X)
    atol = bound * float(np.abs(F).sum(axis=1).max()) + 1e-6
    for task in got:
        np.testing.assert_allclose(got[task], ref[task], atol=atol)
        # exact weights differ: parity must come from the bound, not
        # from compression silently being a no-op
    assert sess_c.dstore.stats.compressed_delta_bytes > 0
    # compressed fleet reads strictly fewer delta bytes off disk
    assert 0 < st_c.delta_loaded_bytes < st_u.delta_loaded_bytes
    assert sum(sess_c.dstore.delta_bytes(f"m0-ft{i}") for i in range(K)) \
        < sum(sess_u.dstore.delta_bytes(f"m0-ft{i}") for i in range(K))
    # gauges ride ServerStats and QueryReport; OFF run declares no bound
    assert st_u.quant_error_bound == 0.0 == st_u.compressed_delta_bytes
    rep = sess_c.sql("PREDICT emb USING TASK sent_ft0 FROM reviews "
                     "WHERE len > 50").report
    assert rep.quant_error_bound == bound
    assert rep.compressed_delta_bytes == \
        sess_c.dstore.stats.compressed_delta_bytes


def test_trunk_delta_variant_gets_own_lane(tmp_path, serve_zoo, table,
                                           sample):
    """A fine-tune whose TRUNK carries deltas is a different embedder:
    distinct fingerprint, own lane, scores from the composed trunk."""
    sess = make_session(tmp_path, serve_zoo, table)
    base = sess.resolve_task("sent", sample.X, sample.y)
    Wd = (serve_zoo[0].W + 0.01).astype(np.float32)
    sess.register_finetune("m0-tft", "m0", {"trunk/W": Wd})
    sess.create_task(TaskSpec("sent_t", "series", ("P", "N")))
    rm = sess.resolve_task("sent_t", sample.X, sample.y,
                           model_id="m0-tft")
    assert rm.trunk_fp != base.trunk_fp
    assert rm.base_fp == base.trunk_fp        # lineage still recorded
    server = MorphingServer(session=sess, max_wait_s=0.001)
    with server:
        out = server.predict("PREDICT emb USING TASK sent_t FROM reviews "
                             "WHERE len > 50", timeout=10.0)
        server.predict("PREDICT emb USING TASK sent FROM reviews "
                       "WHERE len > 50", timeout=10.0)
    assert len(server._lanes) == 2
    X = table["emb"][table["len"] > 50]
    from repro.core.zoo import ZooModel
    twin = ZooModel(name="twin", source_family="gauss", W=Wd,
                    mode="linear")
    np.testing.assert_allclose(np.asarray(out.scores),
                               twin.features(X).mean(axis=1), atol=1e-4)


def test_finetune_head_mode_keeps_trunk_on_disk(tmp_path, serve_zoo,
                                                table, sample):
    """head-mode fine-tune resolution: share hits from the base's
    traffic keep the (shared) trunk lazy — never materialized."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    sess.sql("PREDICT emb USING TASK sent FROM reviews")  # warm share
    rng = np.random.default_rng(5)
    w = np.abs(rng.standard_normal(12)).astype(np.float32)
    w /= w.sum()
    sess.register_finetune("m0-hft", "m0", {"head/w": w})
    sess.create_task(TaskSpec("sent_h", "series", ("P", "N")))
    rm = sess.resolve_task("sent_h", sample.X, sample.y, mode="head",
                           model_id="m0-hft")
    res = sess.sql("PREDICT emb USING TASK sent_h FROM reviews")
    assert not rm.zoo_model.materialized      # share hits: trunk on disk
    assert res.report.share_hit_rate == 1.0
    X = np.asarray(table["emb"])
    np.testing.assert_allclose(res.rows["_score"],
                               serve_zoo[0].features(X) @ w, atol=1e-5)


def test_finetune_partial_mode_slices_delta_rows(tmp_path, serve_zoo):
    """partial-mode fine-tune with a trunk delta: base and delta rows
    are width-sliced consistently and match the full-trunk scores."""
    rng = np.random.default_rng(0)
    table8 = {"len": rng.integers(1, 200, 200),
              "emb": rng.standard_normal((200, 8)).astype(np.float32)}
    sample8 = make_task(np.random.default_rng(2), "gauss", n=96, dim=8,
                        classes=3)
    Wd = (serve_zoo[0].W * 1.02).astype(np.float32)
    outs = {}
    for mode in ("partial", "full"):
        sess = make_session(tmp_path / mode, serve_zoo, table8)
        sess.resolve_task("sent", sample8.X, sample8.y)
        sess.register_finetune("m0-pft", "m0", {"trunk/W": Wd})
        sess.create_task(TaskSpec("sent_p", "series", ("P", "N")))
        rm = sess.resolve_task("sent_p", sample8.X, sample8.y,
                               mode=mode, model_id="m0-pft")
        if mode == "partial":
            assert "+w8" in rm.version and "+w8" in rm.trunk_fp
            assert rm.loaded_bytes < rm.stored_bytes + rm.delta_bytes
        outs[mode] = sess.sql("PREDICT emb USING TASK sent_p "
                              "FROM reviews WHERE len > 50")
    np.testing.assert_allclose(outs["partial"].rows["_score"],
                               outs["full"].rows["_score"], atol=1e-5)


def test_warm_trunk_discount_requires_resident_trunk(tmp_path,
                                                     serve_zoo, table,
                                                     sample):
    """The Eq. 7 delta-staging discount only applies when a sharing
    model's trunk is actually loaded/staged — a lazy head-mode
    resolution that never materialized must not understate TransCost."""
    rng = np.random.default_rng(5)
    w = np.abs(rng.standard_normal(12)).astype(np.float32)
    w /= w.sum()
    # base resolved head-mode with NO traffic: trunk never materializes
    sess = make_session(tmp_path, serve_zoo, table)
    base = sess.resolve_task("sent", sample.X, sample.y, mode="head")
    assert not base.zoo_model.materialized
    sess.register_finetune("m0-ft0", "m0", {"head/w": w})
    sess.create_task(TaskSpec("ft", "series", ("P", "N")))
    rm = sess.resolve_task("ft", sample.X, sample.y, model_id="m0-ft0")
    assert rm.profile.model_bytes > rm.delta_bytes   # full staging cost
    # with a materialized base the discount applies
    warm = make_session(tmp_path / "warm", serve_zoo, table)
    warm.resolve_task("sent", sample.X, sample.y)    # full: staged
    warm.register_finetune("m0-ft0", "m0", {"head/w": w})
    warm.create_task(TaskSpec("ft", "series", ("P", "N")))
    rmw = warm.resolve_task("ft", sample.X, sample.y, model_id="m0-ft0")
    assert rmw.profile.model_bytes == float(rmw.delta_bytes)


def test_finetune_resolution_conflicts(tmp_path, serve_zoo, table,
                                       sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    _register_fleet(sess, sample, 1)
    # rebinding a resolved task to another model requires force
    with pytest.raises(ValueError, match="force=True"):
        sess.resolve_task("sent_ft0", sample.X, sample.y, model_id="m0")
    # unknown model ids fail with a actionable message
    with pytest.raises(KeyError, match="register_finetune"):
        sess.resolve_task("sent", sample.X, sample.y, model_id="nope",
                          force=True)
    # update validation: unknown layers and shape mismatches
    with pytest.raises(KeyError, match="head/extra"):
        sess.register_finetune("m0-bad", "m0",
                               {"head/extra": np.ones(3, np.float32)})
    with pytest.raises(ValueError, match="shape"):
        sess.register_finetune("m0-bad", "m0",
                               {"head/w": np.ones(3, np.float32)})
    # fine-tunes need the decoupled store
    blob = make_session(tmp_path / "blob", serve_zoo, table,
                       model_store="blob")
    with pytest.raises(ValueError, match="decoupled"):
        blob.register_finetune("x", "m0", {})
    blob.resolve_task("sent", sample.X, sample.y)
    with pytest.raises(ValueError, match="decoupled"):
        blob.resolve_task("sent", sample.X, sample.y, model_id="m0",
                          force=True)
