"""Semantic share cache: IVF-flat ANN index, calibrated-radius embedding
reuse (error-bounded vs the exact oracle, hypothesis property tests),
the CacheTier/CacheChain protocol, SIMILARITY query lowering, and the
shared EngineConfig construction surface."""
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.engine import (EngineConfig, LogicalPlan, MorphingServer,
                          MorphingSession, lower_similarity, parse)
from repro.engine.sql import encode_text
from repro.pipeline.share import (AnnConfig, AnnShareTier, CacheChain,
                                  CacheTier, IvfFlatIndex, TierLookup,
                                  VectorShareCache, fingerprint_rows)


# -- fixtures --------------------------------------------------------------

@pytest.fixture(scope="module")
def mini_zoo():
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=120, dim=8, classes=3)
    return [pretrain_model(src, width=12, seed=1, name="m0")]


def _session(tmp_path, zoo, **kw):
    sess = MorphingSession(zoo=zoo, root=tmp_path, chunk_rows=64, **kw)
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0
    return sess


def _resolve(sess):
    sess.resolve_task("sent", np.zeros((4, 8), np.float32),
                      np.zeros(4, np.int64))


def _iso_embed(dim, out, scale, seed=0):
    """Isometry-scaled linear embedder: ||f(a)-f(b)|| == scale*||a-b||
    exactly, so the calibrated Lipschitz estimate equals ``scale`` and
    the tier's error bound is a theorem, not a hope."""
    m = max(dim, out)
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((m, m)))
    M = (scale * q[:dim]).astype(np.float32)   # M @ M.T == scale^2 * I
    return lambda A: np.asarray(A, np.float32).reshape(len(A), -1) @ M


# -- IVF-flat index --------------------------------------------------------

def test_ivf_full_probe_matches_brute_force():
    rng = np.random.default_rng(0)
    V = rng.standard_normal((300, 16)).astype(np.float32)
    idx = IvfFlatIndex(nlist=8, nprobe=8, train_min=32)
    idx.add(V)
    Q = rng.standard_normal((50, 16)).astype(np.float32)
    d, i = idx.search1(Q)
    bd = np.linalg.norm(Q[:, None] - V[None], axis=2)
    np.testing.assert_array_equal(i, bd.argmin(axis=1))
    np.testing.assert_allclose(d, bd.min(axis=1), rtol=1e-4, atol=1e-5)


def test_ivf_below_train_min_is_brute_force():
    rng = np.random.default_rng(1)
    V = rng.standard_normal((10, 4)).astype(np.float32)
    idx = IvfFlatIndex(nlist=4, nprobe=1, train_min=64)
    idx.add(V)
    d, i = idx.search1(V)
    np.testing.assert_array_equal(i, np.arange(10))
    assert (d < 1e-2).all()


def test_ivf_incremental_add_and_retrain():
    rng = np.random.default_rng(2)
    idx = IvfFlatIndex(nlist=8, nprobe=8, train_min=32)
    chunks = [rng.standard_normal((80, 8)).astype(np.float32)
              for _ in range(4)]
    for c in chunks:
        idx.add(c)
    V = np.concatenate(chunks)
    assert len(idx) == len(V)
    # every member row finds itself at distance ~0 (full probe)
    d, i = idx.search1(V[::7])
    np.testing.assert_array_equal(i, np.arange(len(V))[::7])
    assert (d < 1e-2).all()


def test_ivf_recall_floor_on_near_duplicates():
    """Default nprobe on a seeded near-duplicate corpus: >= 0.95 of
    queries must find their true (very close) nearest neighbor."""
    rng = np.random.default_rng(3)
    V = rng.standard_normal((600, 12)).astype(np.float32)
    idx = IvfFlatIndex(nlist=16, nprobe=4, train_min=64)
    idx.add(V)
    Q = V + rng.standard_normal(V.shape).astype(np.float32) * 1e-3
    _, i = idx.search1(Q)
    recall = float((i == np.arange(len(V))).mean())
    assert recall >= 0.95, recall


def test_ivf_empty_and_miss():
    idx = IvfFlatIndex()
    d, i = idx.search1(np.zeros((3, 4), np.float32))
    assert (i == -1).all() and np.isinf(d).all()


# -- CacheTier protocol + chain --------------------------------------------

def test_cache_tier_protocol_isinstance():
    assert isinstance(VectorShareCache(), CacheTier)
    assert isinstance(AnnShareTier(), CacheTier)


def test_exact_tier_lookup_insert_roundtrip():
    cache = VectorShareCache()
    rng = np.random.default_rng(4)
    rows = rng.standard_normal((20, 6)).astype(np.float32)
    embs = rng.standard_normal((20, 3)).astype(np.float32)
    tl = cache.lookup_many("t", "c", rows)
    assert isinstance(tl, TierLookup) and tl.miss.all()
    cache.insert_many("t", "c", tl.keys, rows, embs)
    tl2 = cache.lookup_many("t", "c", rows)
    assert not tl2.miss.any() and tl2.hits == 20
    np.testing.assert_allclose(tl2.found, embs)
    assert len(tl2.approx_idx) == 0      # exact tier never approximates


def test_chain_exact_tier_leads():
    """A row in the exact tier is served byte-exact even when the ANN
    tier could approximate it."""
    exact = VectorShareCache()
    ann = AnnShareTier(AnnConfig(max_dist=10.0, audit_rate=0.0))
    chain = CacheChain([exact, ann])
    rng = np.random.default_rng(5)
    rows = rng.standard_normal((30, 6)).astype(np.float32)
    embs = rng.standard_normal((30, 4)).astype(np.float32)
    keys = fingerprint_rows(rows)
    chain.insert_many("t", "c", keys, rows, embs)
    tl = chain.lookup_many("t", "c", rows)
    assert not tl.miss.any()
    assert len(tl.approx_idx) == 0
    np.testing.assert_allclose(tl.found, embs)
    # a near-duplicate falls through to the ANN tier
    q = rows[:5] + 1e-4
    tq = chain.lookup_many("t", "c", q)
    assert not tq.miss.any() and len(tq.approx_idx) == 5
    np.testing.assert_allclose(tq.found, embs[:5])


def test_ann_cold_tier_never_serves():
    """Until calibration the radius is 0: the tier cannot serve wild
    guesses from an uncalibrated distance threshold."""
    ann = AnnShareTier(AnnConfig())
    rng = np.random.default_rng(6)
    rows = rng.standard_normal((40, 8)).astype(np.float32)
    ann.insert_many("t", "c", fingerprint_rows(rows), rows,
                    rng.standard_normal((40, 4)).astype(np.float32))
    assert ann.radius("t", "c") == 0.0
    tl = ann.lookup_many("t", "c", rows + 1e-6)
    assert tl.miss.all()


def test_ann_calibrates_and_serves_within_radius():
    cfg = AnnConfig(error_bound=0.1, audit_rate=0.0, seed=0)
    ann = AnnShareTier(cfg)
    embed = _iso_embed(8, 4, scale=2.0)
    rng = np.random.default_rng(7)
    base = rng.standard_normal((200, 8)).astype(np.float32)
    ann.insert_many("t", "c", fingerprint_rows(base), base, embed(base))
    near = base + rng.standard_normal(base.shape).astype(np.float32) * 1e-3
    ann.insert_many("t", "c", fingerprint_rows(near), near, embed(near))
    r = ann.radius("t", "c")
    # isometry: lip_hat == 2.0 exactly -> radius == bound/(1.5*2)
    assert r == pytest.approx(cfg.error_bound / (1.5 * 2.0), rel=1e-3)
    probe = base + rng.standard_normal(base.shape).astype(np.float32) \
        * (r * 0.2)
    tl = ann.lookup_many("t", "c", probe)
    assert tl.hits > 0.9 * len(probe)
    # every served embedding is within the error bound of the oracle
    err = np.linalg.norm(tl.found[~tl.miss] - embed(probe)[~tl.miss],
                         axis=1)
    assert err.max() <= cfg.error_bound + 1e-5
    # far rows stay misses
    far = base + 10.0
    assert ann.lookup_many("t", "c", far).miss.all()


def test_record_audit_counts_false_accepts_and_shrinks_radius():
    ann = AnnShareTier(AnnConfig(error_bound=0.1))
    rng = np.random.default_rng(8)
    base = rng.standard_normal((100, 8)).astype(np.float32)
    embed = _iso_embed(8, 4, scale=1.0)
    ann.insert_many("t", "c", fingerprint_rows(base), base, embed(base))
    near = base + 1e-3
    ann.insert_many("t", "c", fingerprint_rows(near), near, embed(near))
    r0 = ann.radius("t", "c")
    assert r0 > 0
    # report an audited hit whose exact recomputation blew the bound
    ann.record_audit("t", "c", "v1", dists=np.array([r0 / 2]),
                     errors=np.array([0.5]))
    assert ann.stats.false_accepts == 1
    assert ann.radius("t", "c") < r0


def test_chain_get_or_embed_single_flight_and_audit():
    calls = {"rows": 0}
    embed = _iso_embed(6, 3, scale=1.0)

    def counting_embed(A):
        calls["rows"] += len(A)
        return embed(A)

    chain = CacheChain([VectorShareCache(),
                        AnnShareTier(AnnConfig(error_bound=0.1,
                                               audit_rate=1.0))])
    rng = np.random.default_rng(9)
    rows = rng.standard_normal((50, 6)).astype(np.float32)
    dup = np.concatenate([rows, rows])      # in-flight duplicates
    E = chain.get_or_embed("t", "c", dup, counting_embed)
    assert calls["rows"] == 50              # single-flight dedup
    np.testing.assert_allclose(E, embed(dup), atol=1e-5)
    # warm: no new computation
    chain.get_or_embed("t", "c", rows, counting_embed)
    assert calls["rows"] == 50
    # near-duplicates calibrate, then serve approximately; with
    # audit_rate=1 every approx hit is recomputed exactly and served
    # exact (keeping the radius honest costs the audit rows only)
    near = rows + 1e-4
    chain.get_or_embed("t", "c", near, counting_embed)
    near2 = rows + 2e-4
    before = calls["rows"]
    E2 = chain.get_or_embed("t", "c", near2, counting_embed)
    ann = chain.ann
    assert ann.stats.approx_hits > 0
    assert ann.stats.audits > 0
    np.testing.assert_allclose(E2, embed(near2), atol=1e-5)  # audited=exact
    assert calls["rows"] > before           # audits did recompute


# -- hypothesis property tests ---------------------------------------------

@settings(max_examples=10, deadline=None)
@given(dim=st.sampled_from([4, 8, 16]),
       out=st.sampled_from([2, 4]),
       scale=st.floats(min_value=0.5, max_value=4.0),
       dtype=st.sampled_from(["float32", "float64"]),
       eps_frac=st.floats(min_value=0.05, max_value=0.9))
def test_property_ann_error_within_bound(dim, out, scale, dtype,
                                         eps_frac):
    """Across dtypes/shapes/scales: every ANN-served embedding is within
    the configured error bound of the exact oracle."""
    cfg = AnnConfig(error_bound=0.2, audit_rate=0.0, seed=1)
    chain = CacheChain([VectorShareCache(), AnnShareTier(cfg)])
    embed = _iso_embed(dim, out, scale=scale, seed=dim)
    rng = np.random.default_rng(dim * 31 + out)
    base = rng.standard_normal((150, dim)).astype(dtype)
    chain.get_or_embed("t", "c", base, embed)
    chain.get_or_embed("t", "c", (base + 1e-3).astype(dtype), embed)
    ann = chain.ann
    r = ann.radius("t", "c")
    assert r == pytest.approx(cfg.error_bound / (1.5 * scale), rel=1e-2)
    probe = (base + rng.standard_normal(base.shape)
             * (r * eps_frac / np.sqrt(dim))).astype(dtype)
    served = chain.get_or_embed("t", "c", probe, embed)
    err = np.linalg.norm(served - embed(probe), axis=1)
    assert err.max() <= cfg.error_bound + 1e-4
    assert ann.stats.approx_hits > 0        # the tier actually served


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_property_recall_floor_seeded_corpus(seed):
    """Recall floor: on a seeded near-duplicate corpus with a calibrated
    radius, >= 95% of in-radius queries are served by the tier."""
    cfg = AnnConfig(error_bound=0.3, audit_rate=0.0, seed=2)
    ann = AnnShareTier(cfg)
    embed = _iso_embed(8, 4, scale=1.0, seed=seed)
    rng = np.random.default_rng(seed)
    base = rng.standard_normal((400, 8)).astype(np.float32)
    ann.insert_many("t", "c", fingerprint_rows(base), base, embed(base))
    near = base + rng.standard_normal(base.shape).astype(np.float32) * 1e-3
    ann.insert_many("t", "c", fingerprint_rows(near), near, embed(near))
    r = ann.radius("t", "c")
    probe = base + rng.standard_normal(base.shape).astype(np.float32) \
        * (r * 0.1)
    tl = ann.lookup_many("t", "c", probe)
    assert tl.hits / len(probe) >= 0.95


# -- SIMILARITY parsing + lowering -----------------------------------------

def test_parse_similarity_vector_and_limit():
    s = parse("SELECT id FROM t ORDER BY SIMILARITY(emb, [1.0, -2, 0.5]) "
              "LIMIT 5")
    assert s.plan.ops() == ["scan", "project", "sort", "limit"]
    srt = s.plan.nodes[2]
    np.testing.assert_allclose(srt.args["query"], [1.0, -2.0, 0.5])
    assert srt.args["ascending"] is False
    assert srt.args["drop_col"] == "emb"     # carried only for ordering
    assert s.plan.nodes[3].args["k"] == 5


def test_parse_similarity_text_asc_and_predict():
    s = parse("PREDICT emb USING TASK sent FROM t "
              "ORDER BY SIMILARITY(emb, 'cheap hotel') ASC LIMIT 3")
    srt = next(n for n in s.plan.nodes if n.op == "sort")
    assert srt.args["query"] == "cheap hotel"
    assert srt.args["ascending"] is True


def test_parse_similarity_errors():
    with pytest.raises(ValueError, match="aggregates"):
        parse("SELECT COUNT(*) FROM t ORDER BY SIMILARITY(e, [1]) LIMIT 2")
    with pytest.raises(ValueError, match="LIMIT"):
        parse("SELECT a FROM t LIMIT 0")
    with pytest.raises(ValueError, match="quoted"):
        parse("SELECT a FROM t ORDER BY SIMILARITY(e, bare) LIMIT 2")


def test_encode_text_deterministic_unit_norm():
    a = encode_text("hello world", 16)
    b = encode_text("hello world", 16)
    np.testing.assert_array_equal(a, b)
    assert np.linalg.norm(a) == pytest.approx(1.0)
    assert not np.allclose(a, encode_text("other text", 16))


def test_lower_similarity_pass_conditions():
    q = np.ones(3, np.float32)
    p = LogicalPlan.scan("t").project(["a", "e"]) \
        .order_by_similarity("e", q).limit(4)
    p = lower_similarity(p)
    assert p.ops() == ["index_scan", "project"]
    assert p.nodes[0].args["k"] == 4 and p.nodes[0].args["table"] == "t"
    # a filter blocks the lowering (predicates must see all rows)
    p2 = lower_similarity(LogicalPlan.scan("t").filter([("a", ">", 1)])
                          .order_by_similarity("e", q).limit(4))
    assert p2.nodes[0].op == "scan"
    # ascending (farthest-first) blocks it too
    p3 = lower_similarity(LogicalPlan.scan("t")
                          .order_by_similarity("e", q, ascending=True)
                          .limit(4))
    assert p3.nodes[0].op == "scan"
    # no limit: full sort, nothing to index-scan
    p4 = lower_similarity(LogicalPlan.scan("t")
                          .order_by_similarity("e", q))
    assert p4.nodes[0].op == "scan"


# -- similarity queries end-to-end -----------------------------------------

def test_similarity_topk_warm_cache_no_trunk(tmp_path, mini_zoo):
    sess = _session(tmp_path, mini_zoo,
                    config=EngineConfig(cache_tiers=("exact", "ann"),
                                        ann=AnnConfig(error_bound=0.2)))
    _resolve(sess)
    rng = np.random.default_rng(0)
    n = 200
    T = {"id": np.arange(n),
         "emb": rng.standard_normal((n, 8)).astype(np.float32)}
    sess.register_table("reviews", T)
    sess.sql("PREDICT emb USING TASK sent FROM reviews")     # warm cache
    q = T["emb"][17]
    vec = "[" + ", ".join(f"{x:.6f}" for x in q) + "]"
    res = sess.sql(f"PREDICT emb USING TASK sent FROM reviews "
                   f"ORDER BY SIMILARITY(emb, {vec}) LIMIT 5")
    assert res.report.index_scan
    assert res.report.sim_trunk_rows == 0       # warm: no trunk forward
    assert res.rows["id"][0] == 17              # nearest = the row itself
    assert len(res.rows["id"]) == 5
    assert res.rows["_sim"][0] == pytest.approx(0.0, abs=1e-5)
    assert (np.diff(res.rows["_sim"]) <= 1e-6).all()   # nearest first


def test_similarity_select_drops_order_column(tmp_path, mini_zoo):
    sess = _session(tmp_path, mini_zoo)
    _resolve(sess)
    rng = np.random.default_rng(1)
    T = {"id": np.arange(50),
         "emb": rng.standard_normal((50, 8)).astype(np.float32)}
    sess.register_table("reviews", T)
    vec = "[" + ", ".join(f"{x:.6f}" for x in T["emb"][3]) + "]"
    res = sess.sql(f"SELECT id FROM reviews "
                   f"ORDER BY SIMILARITY(emb, {vec}) LIMIT 3")
    assert list(res.rows) == ["id", "_sim"]     # emb carried then dropped
    assert res.rows["id"][0] == 3
    assert res.report.index_scan                # raw row space lowers too


def test_similarity_with_filter_falls_back(tmp_path, mini_zoo):
    sess = _session(tmp_path, mini_zoo)
    _resolve(sess)
    rng = np.random.default_rng(2)
    n = 80
    T = {"id": np.arange(n), "len": rng.integers(0, 100, n),
         "emb": rng.standard_normal((n, 8)).astype(np.float32)}
    sess.register_table("reviews", T)
    vec = "[" + ", ".join(f"{x:.6f}" for x in T["emb"][5]) + "]"
    res = sess.sql(f"SELECT id FROM reviews WHERE len >= 0 "
                   f"ORDER BY SIMILARITY(emb, {vec}) LIMIT 4")
    assert not res.report.index_scan            # filter blocks lowering
    assert res.rows["id"][0] == 5               # but ordering still holds
    assert len(res.rows["id"]) == 4


def test_similarity_text_query_runs(tmp_path, mini_zoo):
    sess = _session(tmp_path, mini_zoo)
    _resolve(sess)
    rng = np.random.default_rng(3)
    T = {"id": np.arange(30),
         "emb": rng.standard_normal((30, 8)).astype(np.float32)}
    sess.register_table("reviews", T)
    res = sess.sql("SELECT id FROM reviews "
                   "ORDER BY SIMILARITY(emb, 'some query text') LIMIT 2")
    assert len(res.rows["id"]) == 2


def test_session_ann_scores_match_exact_oracle(tmp_path, mini_zoo):
    """End-to-end: ANN-mode predictions on near-duplicate traffic match
    the exact session's scores within the configured error bound."""
    bound = 0.2
    sess = _session(tmp_path, mini_zoo,
                    config=EngineConfig(cache_tiers=("exact", "ann"),
                                        ann=AnnConfig(error_bound=bound,
                                                      audit_rate=0.0)))
    _resolve(sess)
    rng = np.random.default_rng(4)
    n = 200
    base = rng.standard_normal((n, 8)).astype(np.float32)
    sess.register_table("t", {"emb": base})
    sess.sql("PREDICT emb USING TASK sent FROM t")            # fill
    near1 = base + rng.standard_normal((n, 8)).astype(np.float32) * 1e-3
    sess.register_table("t", {"emb": near1})
    sess.sql("PREDICT emb USING TASK sent FROM t")            # calibrate
    near2 = base + rng.standard_normal((n, 8)).astype(np.float32) * 1e-3
    sess.register_table("t", {"emb": near2})
    res = sess.sql("PREDICT emb USING TASK sent FROM t")
    assert res.report.approx_hits > 0
    rm = sess.models["sent"]
    oracle = rm.head(rm.features(near2))
    err = np.abs(np.asarray(res.rows["_score"]) - oracle)
    assert err.max() <= bound + 1e-5


# -- EngineConfig ----------------------------------------------------------

def test_engine_config_and_kwargs_equivalent(tmp_path, mini_zoo):
    a = MorphingSession(zoo=mini_zoo, root=tmp_path / "a",
                        config=EngineConfig(chunk_rows=32, workers=2,
                                            enable_share=False,
                                            model_store="decoupled"))
    b = MorphingSession(zoo=mini_zoo, root=tmp_path / "b", chunk_rows=32,
                        workers=2, enable_share=False,
                        model_store="decoupled")
    for s in (a, b):
        assert (s.chunk_rows, s.workers, s.enable_share, s.model_store) \
            == (32, 2, False, "decoupled")
    assert a.config == b.config


def test_engine_config_kwargs_overlay(tmp_path, mini_zoo):
    sess = MorphingSession(zoo=mini_zoo, root=tmp_path,
                           config=EngineConfig(chunk_rows=32),
                           chunk_rows=16)       # explicit kwarg wins
    assert sess.chunk_rows == 16
    assert sess.config.chunk_rows == 16


def test_engine_config_validation():
    with pytest.raises(ValueError, match="model_store"):
        EngineConfig(model_store="nope").validate()
    with pytest.raises(ValueError, match="cache tier"):
        EngineConfig(cache_tiers=("exact", "bogus")).validate()
    with pytest.raises(ValueError, match="start with 'exact'"):
        EngineConfig(cache_tiers=("ann",)).validate()
    with pytest.raises(ValueError, match="device_count"):
        EngineConfig(device_count=0).validate()


def test_engine_config_ann_tier_wiring(tmp_path, mini_zoo):
    sess = MorphingSession(zoo=mini_zoo, root=tmp_path,
                           cache_tiers=("exact", "ann"),
                           ann=AnnConfig(error_bound=0.42))
    assert sess.ann is not None
    assert sess.ann.cfg.error_bound == 0.42
    assert sess.cache_chain.tiers == [sess.share, sess.ann]
    # default sessions stay exact-only
    sess2 = MorphingSession(zoo=mini_zoo, root=tmp_path / "x")
    assert sess2.ann is None


def test_server_devices_kwarg_deprecated(tmp_path, mini_zoo):
    sess = _session(tmp_path, mini_zoo)
    with pytest.warns(DeprecationWarning, match="device_count"):
        MorphingServer(session=sess, devices=1)
    # conflicting value still raises (after the warning)
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="conflicts"):
            MorphingServer(session=sess, devices=3)


def test_server_policy_from_config(tmp_path, mini_zoo):
    from repro.pipeline.admission import AdmissionPolicy
    pol = AdmissionPolicy(max_queue_rows=64)
    sess = _session(tmp_path, mini_zoo,
                    config=EngineConfig(policy=pol))
    srv = MorphingServer(session=sess)
    assert srv.policy is pol


# -- serving with the ANN tier ---------------------------------------------

def test_server_ann_counters(tmp_path, mini_zoo):
    sess = _session(tmp_path, mini_zoo,
                    config=EngineConfig(cache_tiers=("exact", "ann"),
                                        ann=AnnConfig(error_bound=0.2,
                                                      audit_rate=0.2)))
    _resolve(sess)
    rng = np.random.default_rng(5)
    n = 128
    base = rng.standard_normal((n, 8)).astype(np.float32)
    sess.register_table("t0", {"emb": base})
    sess.register_table("t1", {"emb": base + rng.standard_normal(
        (n, 8)).astype(np.float32) * 1e-3})
    sess.register_table("t2", {"emb": base + rng.standard_normal(
        (n, 8)).astype(np.float32) * 1e-3})
    with MorphingServer(session=sess) as srv:
        srv.predict("PREDICT emb USING TASK sent FROM t0")   # fill
        srv.predict("PREDICT emb USING TASK sent FROM t1")   # calibrate
        srv.predict("PREDICT emb USING TASK sent FROM t2")   # ANN hits
        st = srv.stats()
        assert st.approx_hits > 0
        assert st.share_hit_rate > 0
        assert st.false_accepts == 0         # tiny perturbations: exact
        srv.reset_telemetry()
        assert srv.stats().approx_hits == 0
