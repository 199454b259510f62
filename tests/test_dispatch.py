"""Dispatch tier: picklable cross-process envelopes, front-door routing
to worker processes, staging-aware placement, calibration memo, and
worker failover (heartbeats + lease re-dispatch)."""
import json
import pickle
import time

import numpy as np
import pytest

from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.engine import (DispatchServer, EngineConfig, MorphingServer,
                          MorphingSession, PlacementPolicy)
from repro.engine import session as session_mod
from repro.engine.serve import ServerStats
from repro.pipeline.admission import CircuitOpen, Rejected, RequestError
from repro.pipeline.cost import (HardwareProfile, load_profile_memo,
                                 profile_memo_fingerprint,
                                 store_profile_memo)


# -- fixtures --------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_zoo():
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=120, dim=16, classes=3)
    return [pretrain_model(src, width=12, seed=1, name="m0")]


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
                 backend="numpy", **kw):
    sess = MorphingSession(zoo=zoo, root=tmp_path, model_store=model_store,
                           backend=backend, **kw)
    sess.register_table("reviews", {k: v.copy() for k, v in table.items()})
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0
    return sess


def make_dispatch(tmp_path, zoo, table, sample, *, workers=2, **kw):
    sess = make_session(tmp_path, zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    kw.setdefault("placement", PlacementPolicy(watermark_rows=1 << 20))
    srv = DispatchServer(session=sess, workers=workers,
                         worker_backend="numpy", **kw)
    return sess, srv


def _ref(sess, thr):
    return np.asarray(sess.sql(
        f"PREDICT emb USING TASK sent FROM reviews "
        f"WHERE len > {thr}").rows["_score"])


# -- satellite: picklable cross-process envelopes --------------------------

def _roundtrip(obj):
    return pickle.loads(pickle.dumps(obj))


def test_rejected_pickles_with_fields():
    e = Rejected("interactive queue full", lane="trunk:abc",
                 priority="interactive", queued_units=512, cap=256,
                 reason="queue_full")
    r = _roundtrip(e)
    assert isinstance(r, Rejected) and str(r) == str(e)
    # regression guard: *every* attribute must survive transport, so a
    # newly added field can't silently break the dispatch tier
    assert r.__dict__ == e.__dict__


def test_circuit_open_pickles_with_fields():
    e = CircuitOpen("lane breaker open", lane="trunk:abc",
                    priority="batch", failures=7)
    r = _roundtrip(e)
    assert isinstance(r, CircuitOpen)
    assert r.failures == 7 and r.reason == "breaker_open"
    assert r.__dict__ == e.__dict__


def test_request_error_pickles_with_fields():
    e = RequestError("batch failed after 3 attempts", lane="trunk:abc",
                     attempts=3, req_ids=(4, 5, 6))
    r = _roundtrip(e)
    assert isinstance(r, RequestError)
    assert r.attempts == 3 and r.req_ids == (4, 5, 6)
    assert r.__dict__ == e.__dict__


def test_server_stats_pickles_equal():
    st = ServerStats(requests=5, rows=100, share_hits=3,
                     requests_by_task={"sent": 5},
                     share_hit_rate_by_lane={"trunk:a": 0.5},
                     breaker_open_lanes=["trunk:a"])
    assert _roundtrip(st) == st


@pytest.mark.parametrize("store", ["decoupled", "blob"])
def test_resolved_model_pickles(tmp_path, serve_zoo, table, sample, store):
    sess = make_session(tmp_path / store, serve_zoo, table,
                        model_store=store)
    rm = sess.resolve_task("sent", sample.X, sample.y)
    rm2 = _roundtrip(rm)
    for f in ("task", "model_id", "version", "load_mode", "store",
              "stored_bytes", "in_dim", "head_dim", "trunk_fp",
              "base_model_id", "delta_bytes"):
        assert getattr(rm2, f) == getattr(rm, f), f
    X = sample.X[:8].astype(np.float32)
    np.testing.assert_allclose(rm2.head(rm2.features(X)),
                               rm.head(rm.features(X)), atol=1e-6)


# -- satellite: on-disk calibration memo -----------------------------------

def test_profile_memo_roundtrip_and_staleness(tmp_path):
    path = tmp_path / "memo.json"
    prof = HardwareProfile(name="host", flops_per_s=1e9, mem_bw=2e9,
                           link_bw=3e9, launch_latency_s=1e-5,
                           measured=True)
    fp = profile_memo_fingerprint(("numpy", None))
    store_profile_memo(path, fp, prof)
    assert load_profile_memo(path)[fp] == prof
    # a second entry merges rather than clobbers
    store_profile_memo(path, fp + "|v2", prof)
    assert set(load_profile_memo(path)) == {fp, fp + "|v2"}
    # staleness guard: a changed topology fingerprint simply misses
    assert load_profile_memo(path).get(fp + "|jaxdev=99") is None


def test_profile_memo_corrupt_and_drifted_entries_reprobe(tmp_path):
    path = tmp_path / "memo.json"
    path.write_text("{not json")
    assert load_profile_memo(path) == {}
    path.write_text(json.dumps({"fp": {"no_such_field": 1}}))
    assert load_profile_memo(path) == {}
    assert load_profile_memo(tmp_path / "absent.json") == {}


def test_fingerprint_embeds_topology():
    host = profile_memo_fingerprint(("numpy", None))
    assert "cpus=" in host and "jax=" not in host
    jax_fp = profile_memo_fingerprint(("jax", False))
    assert "jax=" in jax_fp
    assert host != jax_fp
    assert (profile_memo_fingerprint(("jax-mesh", False, 2))
            != profile_memo_fingerprint(("jax-mesh", False, 4)))
    # a profile measured on the CPU never serves a chip
    assert "platform=cpu" in jax_fp and "kind=cpu" in jax_fp


def test_dispatch_refuses_jax_workers_off_the_cpu(tmp_path, serve_zoo,
                                                  table, monkeypatch):
    """A chip belongs to one process: jax workers are refused unless JAX
    is held to the CPU, while numpy workers are always allowed."""
    sess = make_session(tmp_path, serve_zoo, table)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="one process"):
        DispatchServer(session=sess, workers=1, worker_backend="jax")
    srv = DispatchServer(session=sess, workers=1, worker_backend="numpy")
    assert srv._worker_cfg.backend == "numpy"


def test_dispatch_front_door_builds_numpy_session(tmp_path):
    """Without a session the front door builds its own, and it never
    holds a jax backend: it runs no inference."""
    srv = DispatchServer(config=EngineConfig(model_store="decoupled"),
                         root=tmp_path, workers=1, worker_backend="numpy",
                         auto_calibrate=False)
    assert srv.session.config.backend == "numpy"
    assert {type(b).__name__ for b in srv.session.backends.values()} == {
        "NumpyBackend"}


def test_session_auto_calibration_writes_memo(tmp_path, serve_zoo, table):
    memo = tmp_path / "hw_calib_memo.json"
    with session_mod._FAST_CALIB_LOCK:
        saved = dict(session_mod._FAST_CALIB_CACHE)
        session_mod._FAST_CALIB_CACHE.clear()
    try:
        sess = MorphingSession(
            zoo=serve_zoo, root=tmp_path / "s",
            config=EngineConfig(model_store="decoupled", backend="numpy",
                                calib_memo_path=str(memo)))
        assert sess.hw
        entries = load_profile_memo(memo)
        assert entries, "auto-calibration should persist its probe"
        fp = profile_memo_fingerprint(("numpy", None))
        assert fp in entries and entries[fp].measured
        # second session reads the memo instead of re-probing
        with session_mod._FAST_CALIB_LOCK:
            session_mod._FAST_CALIB_CACHE.clear()
        sess2 = MorphingSession(
            zoo=serve_zoo, root=tmp_path / "s2",
            config=EngineConfig(model_store="decoupled", backend="numpy",
                                calib_memo_path=str(memo)))
        assert sess2.hw["host"].flops_per_s == entries[fp].flops_per_s
    finally:
        with session_mod._FAST_CALIB_LOCK:
            session_mod._FAST_CALIB_CACHE.clear()
            session_mod._FAST_CALIB_CACHE.update(saved)


# -- MorphingServer plumbing the tier rides on -----------------------------

def test_submit_rows_matches_sql(tmp_path, serve_zoo, table, sample):
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    ref = _ref(sess, 50)
    X = np.asarray(table["emb"])[np.asarray(table["len"]) > 50]
    with MorphingServer(session=sess) as srv:
        out = srv.result(srv.submit_rows("sent", X), timeout=30)
    np.testing.assert_allclose(out.scores, ref, atol=1e-5)


def test_unstage_trunk_releases_and_relanes(tmp_path, serve_zoo, table,
                                            sample):
    sess = make_session(tmp_path, serve_zoo, table)
    rm = sess.resolve_task("sent", sample.X, sample.y)
    key = rm.trunk_fp or rm.version
    sql = "PREDICT emb USING TASK sent FROM reviews WHERE len > 50"
    with MorphingServer(session=sess) as srv:
        first = srv.predict(sql, timeout=30)
        assert srv.unstage_trunk(key) is True
        assert srv.unstage_trunk(key) is False      # idempotent
        again = srv.predict(sql, timeout=30)        # re-lanes + re-stages
        np.testing.assert_allclose(again.scores, first.scores, atol=1e-5)


# -- dispatch tier: routing, placement, failover ---------------------------

def test_dispatch_requires_decoupled_store(tmp_path, serve_zoo, table):
    sess = make_session(tmp_path, serve_zoo, table, model_store="blob")
    with pytest.raises(ValueError, match="decoupled"):
        DispatchServer(session=sess, workers=1)


def test_dispatch_parity_and_stats(tmp_path, serve_zoo, table, sample):
    sess, srv = make_dispatch(tmp_path, serve_zoo, table, sample)
    refs = {thr: _ref(sess, thr) for thr in (20, 60, 100)}
    with srv:
        ids = {thr: srv.submit("PREDICT emb USING TASK sent FROM reviews "
                               f"WHERE len > {thr}")
               for thr in refs}
        for thr, rid in ids.items():
            out = srv.result(rid, timeout=60)
            np.testing.assert_allclose(out.scores, refs[thr], atol=1e-5)
        st = srv.stats()
        assert st.workers == 2 and st.alive_workers == 2
        assert st.requests == 3 and st.leases >= 1
        assert st.worker_rows >= sum(len(r) for r in refs.values())
        assert st.per_worker and all(isinstance(s, ServerStats)
                                     for s in st.per_worker.values())
        assert st.duplicates_dropped == 0 and st.worker_deaths == 0


def test_finetune_fleet_stages_on_one_worker(tmp_path, serve_zoo, table,
                                             sample):
    """K fine-tunes of one base ride a single worker's shared embed lane
    under light load — the trunk is staged on exactly one worker."""
    sess = make_session(tmp_path, serve_zoo, table)
    sess.resolve_task("sent", sample.X, sample.y)
    rng = np.random.default_rng(11)
    dim = sess.models["sent"].head_dim
    tasks = ["sent"]
    for i in range(3):
        w = np.abs(rng.standard_normal(dim)).astype(np.float32)
        w /= w.sum()
        name, mid = f"sent_ft{i}", f"m0-ft{i}"
        sess.register_finetune(mid, "m0", {"head/w": w})
        sess.create_task(TaskSpec(name, "series", ("P", "N")))
        sess.resolve_task(name, sample.X, sample.y, model_id=mid)
        tasks.append(name)
    trunk = sess.models["sent"].trunk_fp
    assert all(sess.models[t].trunk_fp == trunk for t in tasks)
    srv = DispatchServer(session=sess, workers=2, worker_backend="numpy",
                         placement=PlacementPolicy(watermark_rows=1 << 20))
    with srv:
        for t in tasks:
            out = srv.predict(f"PREDICT emb USING TASK {t} FROM reviews "
                              "WHERE len > 40", timeout=60)
            assert out.rows > 0
        st = srv.stats()
        staged = [w for w, b in st.staged_bytes_by_worker.items() if b > 0]
        assert len(staged) == 1, st.staged_bytes_by_worker
        assert st.replicas_by_trunk == {trunk: 1}
        assert st.trunks_by_worker[staged[0]] == [trunk]


def test_scale_out_under_load_then_drain_back(tmp_path, serve_zoo, table,
                                              sample):
    sess, srv = make_dispatch(
        tmp_path, serve_zoo, table, sample,
        placement=PlacementPolicy(watermark_rows=256, cost_gated=False,
                                  idle_scale_in_s=0.5),
        monitor_interval_s=0.1)
    trunk = sess.models["sent"].trunk_fp
    rng = np.random.default_rng(7)
    X = rng.standard_normal((256, 16)).astype(np.float32)
    with srv:
        srv.result(srv.submit_rows("sent", X), timeout=60)   # place trunk
        ids = [srv.submit_rows("sent", X + i) for i in range(40)]
        for rid in ids:
            srv.result(rid, timeout=120)
        st = srv.stats()
        assert st.scale_outs >= 1, "watermark burst should add a replica"
        # idle: the extra replica drains back to one worker
        deadline = time.time() + 30
        while time.time() < deadline:
            st = srv.stats()
            if (st.scale_ins >= 1
                    and st.replicas_by_trunk.get(trunk) == 1):
                break
            time.sleep(0.2)
        assert st.scale_ins >= 1
        assert st.replicas_by_trunk.get(trunk) == 1
        staged = [w for w, b in st.staged_bytes_by_worker.items() if b > 0]
        assert len(staged) == 1


def test_worker_death_redispatches_with_parity(tmp_path, serve_zoo, table,
                                               sample):
    """Hard-kill a worker mid-batch: survivors complete the full request
    set with fault-free answers, no duplicates, re-dispatch counted."""
    sess, srv = make_dispatch(tmp_path, serve_zoo, table, sample,
                              monitor_interval_s=0.1,
                              heartbeat_timeout_s=1.0)
    thrs = list(range(10, 110, 10))
    refs = {thr: _ref(sess, thr) for thr in thrs}
    with srv:
        warm = srv.predict("PREDICT emb USING TASK sent FROM reviews "
                           "WHERE len > 150", timeout=60)
        assert warm.rows > 0
        st0 = srv.stats()
        victim = [w for w, b in st0.staged_bytes_by_worker.items()
                  if b > 0][0]
        # slow the victim's backends so its leases are in flight when it
        # dies (training/fault.py injection over the command channel)
        srv.inject_fault(victim, {"slow_rate": 1.0, "slow_s": 0.5})
        ids = {thr: srv.submit("PREDICT emb USING TASK sent FROM reviews "
                               f"WHERE len > {thr}") for thr in thrs}
        time.sleep(0.3)              # let leases land on the victim
        srv.kill_worker(victim)
        for thr, rid in ids.items():
            out = srv.result(rid, timeout=120)
            np.testing.assert_allclose(out.scores, refs[thr], atol=1e-5)
        st = srv.stats()
        assert st.worker_deaths == 1
        assert st.redispatches >= 1
        assert st.duplicates_dropped == 0
        assert st.alive_workers == 1
        # the trunk moved with the load: a survivor now holds it
        staged = [w for w, b in st.staged_bytes_by_worker.items() if b > 0]
        assert staged and victim not in staged


def test_injected_faults_retried_inside_worker(tmp_path, serve_zoo, table,
                                               sample):
    """Transient backend faults injected in a worker are absorbed by its
    lane retry budget — answers stay correct, no failed batches."""
    sess = make_session(tmp_path, serve_zoo, table, enable_share=False)
    sess.resolve_task("sent", sample.X, sample.y)
    srv = DispatchServer(session=sess, workers=1, worker_backend="numpy",
                         placement=PlacementPolicy(watermark_rows=1 << 20))
    refs = {thr: _ref(sess, thr) for thr in (30, 70)}
    with srv:
        warm = srv.predict("PREDICT emb USING TASK sent FROM reviews "
                           "WHERE len > 150", timeout=60)
        assert warm.rows > 0
        srv.inject_fault(0, {"scripted_errors": [0], "seed": 5})
        for thr, ref in refs.items():
            out = srv.predict("PREDICT emb USING TASK sent FROM reviews "
                              f"WHERE len > {thr}", timeout=60)
            np.testing.assert_allclose(out.scores, ref, atol=1e-5)
        srv.inject_fault(0, None)
        st = srv.stats()
        assert st.retries >= 1
        assert st.failed_batches == 0
        assert st.worker_deaths == 0
