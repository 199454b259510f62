"""DAG scheduling (Algorithm 1), cost model, batcher, vector sharing."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.pipeline import (Dag, Node, OpProfile, PipelineExecutor,
                            VectorShareCache, WindowBatcher, batch_cost,
                            choose_batch_size, choose_device, filter_op,
                            groupby_agg, join, op_cost, run_batched,
                            simd_normalize_embed, window_op)


# -- DAG / Algorithm 1 ---------------------------------------------------

def _diamond():
    d = Dag()
    d.add(Node("src", "scan"))
    d.add(Node("a", "filter", fn=lambda x: x, cost_hint=1), deps=("src",))
    d.add(Node("b", "predict", fn=lambda x: x, cost_hint=9), deps=("src",))
    d.add(Node("c", "join", fn=lambda a, b: a, cost_hint=1,
               meta={"arg_order": {"a": 0, "b": 1}}), deps=("a", "b"))
    return d


def test_topological_order_and_priority():
    d = _diamond()
    order = d.execution_order()
    assert d.validate_topological(order)
    # higher-cost ready op scheduled first within a wave
    waves = d.stages()
    assert waves[1][0] == "b"


def test_cycle_detection():
    d = _diamond()
    d.edges.append(type(d.edges[0])("c", "a", "data"))
    with pytest.raises(ValueError):
        d.execution_order()


def test_edge_labels():
    d = _diamond()
    d.add(Node("ddl", "sink", fn=lambda x: x), deps=(),
          control_deps=("c",))
    labels = {(e.src, e.dst): e.label for e in d.label_edges()}
    assert labels[("c", "ddl")] == "control"
    assert labels[("src", "a")] == "data"


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 12), st.integers(0, 20))
def test_random_dag_topological(n, extra):
    """Property: random DAGs (edges only i->j, i<j) always get a valid
    topological order."""
    rng = np.random.default_rng(n * 101 + extra)
    d = Dag()
    for i in range(n):
        d.add(Node(f"n{i}", "scan", cost_hint=float(rng.random())),
              deps=tuple(f"n{j}" for j in range(i)
                         if rng.random() < 0.3))
    order = d.execution_order()
    assert d.validate_topological(order)
    assert len(order) == n


# -- cost model ----------------------------------------------------------

def test_cost_model_monotonic_rows():
    p = OpProfile(flops_per_row=1e6, bytes_per_row=1e3, model_bytes=1e7)
    assert op_cost(p, 10, "tpu") <= op_cost(p, 1000, "tpu")
    assert op_cost(p, 10, "host") <= op_cost(p, 1000, "host")


def test_device_choice_scales():
    small = OpProfile(flops_per_row=1e4, bytes_per_row=64, model_bytes=1e5)
    big = OpProfile(flops_per_row=2e9, bytes_per_row=4096, model_bytes=4e9)
    assert choose_device(small, 10) == "host"
    assert choose_device(big, 4096) == "tpu"


def test_api_device_by_latency():
    p = OpProfile(flops_per_row=1e12, bytes_per_row=1e6, model_bytes=8e10,
                  api_latency_s=0.02)
    # giant model, tiny batch: remote endpoint wins
    assert choose_device(p, 1) == "api"


def test_batch_size_tradeoff():
    p = OpProfile(flops_per_row=2e7, bytes_per_row=1e5, model_bytes=1e8)
    b = choose_batch_size(p, "tpu", mem_cap_bytes=4e6 + 1e8)
    assert b <= 32  # memory cap binds
    b2 = choose_batch_size(p, "tpu", mem_cap_bytes=1e12)
    assert b2 >= b


# -- batcher --------------------------------------------------------------

def test_batched_equals_unbatched():
    rng = np.random.default_rng(0)
    W = rng.standard_normal((8, 4)).astype(np.float32)
    rows = [rng.standard_normal(8).astype(np.float32) for _ in range(37)]
    f = lambda x: x @ W
    out1 = np.stack(run_batched(rows, f, batch_size=1))
    out16 = np.stack(run_batched(rows, f, batch_size=16))
    np.testing.assert_allclose(out1, out16, rtol=1e-6)


def test_window_batcher_stats():
    f = lambda x: x.sum(axis=1)
    b = WindowBatcher(f, batch_size=8)
    for i in range(20):
        b.add(i, np.ones(4))
    res = b.finish()
    assert len(res) == 20
    assert b.stats.batches == 3   # 8 + 8 + 4
    assert b.stats.rows == 20


# -- relational ops + sharing ---------------------------------------------

def test_join_groupby_window():
    left = {"k": np.array([1, 2, 2, 3]), "x": np.arange(4.0)}
    right = {"k": np.array([2, 3, 4]), "y": np.array([10.0, 20.0, 30.0])}
    j = join(left, right, "k")
    assert len(j["k"]) == 3  # 2,2,3 match
    g = groupby_agg(j, "k", "y", "mean")
    assert dict(zip(g["k"], g["mean_y"])) == {2: 10.0, 3: 20.0}
    w = window_op({"v": np.arange(10.0)}, "v", 3)
    assert "mean3_v" in w


def test_vector_share_cache_disk_tier(tmp_path):
    calls = {"n": 0}

    def embed(X):
        calls["n"] += 1
        return X @ np.ones((X.shape[1], 4), np.float32)

    c1 = VectorShareCache(tmp_path)
    X = np.ones((10, 8), np.float32)
    c1.get_or_embed("t", "c", X, embed)
    assert calls["n"] == 1
    c1.get_or_embed("t", "c", X, embed)
    assert calls["n"] == 1 and c1.hit_rate == 0.5
    # new process (fresh cache) hits the disk tier
    c2 = VectorShareCache(tmp_path)
    c2.get_or_embed("t", "c", X, embed)
    assert calls["n"] == 1


def test_fingerprint_rows_matches_content():
    from repro.pipeline.share import fingerprint_rows

    rng = np.random.default_rng(0)
    X = rng.standard_normal((64, 16)).astype(np.float32)
    fps = fingerprint_rows(X)
    assert fps.shape == (64,) and fps.dtype == np.uint64
    # deterministic, content-addressed: equal rows hash equal wherever
    # they sit; distinct rows hash distinct
    np.testing.assert_array_equal(fps, fingerprint_rows(X.copy()))
    Y = X.copy()
    Y[3] = X[40]
    fps2 = fingerprint_rows(Y)
    assert fps2[3] == fps[40]
    assert len(set(fps.tolist())) == 64
    # dtype participates: same bytes under another dtype must not alias
    assert (fingerprint_rows(X.view(np.int32)) != fps).any()
    assert fingerprint_rows(np.zeros((0, 4))).shape == (0,)
    # low-entropy rows (zeros with one hot bit) must still spread
    Z = np.zeros((32, 16), np.float32)
    Z[np.arange(32), np.arange(32) % 16] = 1.0 + np.arange(32) // 16
    assert len(set(fingerprint_rows(Z).tolist())) == 32


def test_share_cache_lookup_many_row_granular():
    cache = VectorShareCache()
    rng = np.random.default_rng(1)
    X = rng.standard_normal((20, 8)).astype(np.float32)
    E = np.tanh(X @ np.ones((8, 4), np.float32))
    look = cache.lookup_many("t", "c", X, version="v1")
    assert look.found is None and look.miss.all() and len(look.keys) == 20
    cache.insert_many("t", "c", look.keys, X, E, version="v1")
    # overlapping second chunk: cached rows hit, the new row misses
    X2 = np.concatenate([X[5:], rng.standard_normal((1, 8))
                         .astype(np.float32)])
    look2 = cache.lookup_many("t", "c", X2, version="v1")
    assert look2.miss.sum() == 1 and look2.miss[-1]
    np.testing.assert_allclose(look2.found[:-1], E[5:], atol=0)
    # version partitions the key space
    look3 = cache.lookup_many("t", "c", X, version="v2")
    assert look3.found is None and look3.miss.all()
    assert cache.stats.hits == 15


def test_share_cache_single_row_block_stays_bounded():
    """A lone row block must shed its oldest rows at capacity instead of
    growing forever (and permanently starving the chunk tier)."""
    row_bytes = 4 * 4 + 8                     # width-4 float32 + fp
    cache = VectorShareCache(capacity_bytes=64 * row_bytes)
    rng = np.random.default_rng(0)
    for i in range(8):                        # 8 x 32 fresh rows, 1 block
        X = rng.standard_normal((32, 8)).astype(np.float32)
        keys = cache.lookup_many("t", "c", X).keys
        cache.insert_many("t", "c", keys, X, np.ones((32, 4), np.float32))
        assert cache._rows_used <= cache.capacity
        # the newest rows survive the shedding
        assert not cache.lookup_many("t", "c", X).miss.any()


def test_share_cache_row_blocks_evict_lru():
    cache = VectorShareCache(capacity_bytes=4 * 64 * 4 * 2)  # ~2 blocks
    X = np.arange(64 * 8, dtype=np.float32).reshape(64, 8)
    E = np.ones((64, 4), np.float32)
    for i in range(4):                        # 4 key spaces, LRU evicts
        keys = cache.lookup_many("t", f"c{i}", X).keys
        cache.insert_many("t", f"c{i}", keys, X, E)
    look = cache.lookup_many("t", "c0", X)
    assert look.found is None and look.miss.all()   # oldest evicted
    assert not cache.lookup_many("t", "c3", X).miss.any()  # newest stays


_KEYS = [3, 10, 10**6, 2**40, 2**63 + 5]        # sorted, distinct
_MANY = np.unique(np.random.default_rng(7).integers(
    0, 2**64 - 1, 1024, np.uint64, endpoint=True))
# case: (sorted keys, probes)
SEARCH_CASES = {
    "unsorted_with_duplicates": (_KEYS, [2**40, 7, 3, 2**40, 10, 7,
                                         2**63 + 5, 3, 11, 3]),
    "outside_the_keys": (_KEYS, [2, 2**63 + 6, 0, 2**64 - 1, 1, 2**63]),
    "ends_of_uint64": ([0, 5, 2**64 - 1], [2**64 - 1, 0, 1, 2**64 - 2, 0,
                                           2**64 - 1]),
    "empty_index": ([], [5, 0, 2**64 - 1, 5]),
    "empty_probes": (_KEYS, []),
    "single_probe": (_KEYS, [10**6]),
    "all_hits": (_KEYS, _KEYS[::-1] + _KEYS[1::2]),
    "all_misses": (_KEYS, [k + 1 for k in _KEYS] + [0, 4, 9]),
    "many_random": (_MANY[::2], np.random.default_rng(8).choice(_MANY, 4096)),
}


@pytest.mark.parametrize("case", sorted(SEARCH_CASES))
def test_search_in_key_order_matches_searchsorted(case):
    """``_search`` sorts its probes before the search and scatters the
    answers back: each answer equals a plain ``searchsorted`` in request
    order and an equality test at the insertion point, and with
    ``vals`` the entry of ``vals`` there, clipped to the last."""
    from repro.pipeline.share import _search
    keys, q = (np.array(a, np.uint64) for a in SEARCH_CASES[case])
    pos = np.searchsorted(keys, q)
    want = (keys.take(pos, mode="clip") == q if len(keys)
            else np.zeros(len(q), bool))
    at, found = _search(keys, q)
    np.testing.assert_array_equal(at, pos)
    np.testing.assert_array_equal(found, want)
    assert (at.dtype, found.dtype) == (pos.dtype, np.dtype(bool))
    if len(keys):
        vals = np.arange(len(keys), dtype=np.int64)[::-1] * 7
        rows, found = _search(keys, q, vals)
        np.testing.assert_array_equal(rows, vals.take(pos, mode="clip"))
        np.testing.assert_array_equal(found, want)


# case: (initial capacity, op weights put / lookup / drop, steps spent
# on an empty block first, keys a lookup also probes outside the universe)
ROW_BLOCK_CASES = {
    "growth": (1, (3, 1, 0), 0, 0),
    "shed": (16, (2, 1, 1), 0, 0),
    "pending_run_across_puts": (16, (4, 1, 1), 0, 0),
    "empty_block": (8, (1, 1, 1), 12, 0),
    "wide_lookups": (16, (2, 2, 1), 0, 512),
}


@pytest.mark.parametrize("case", sorted(ROW_BLOCK_CASES))
def test_row_block_index_matches_a_dict(case):
    """A ``_RowBlock`` driven through a seeded interleaving of puts
    (fresh, repeated and in-call duplicate fingerprints), lookups,
    buffer growth and sheds agrees with a dict after every step, and
    after a lookup its index is the sorted fingerprints held."""
    from repro.pipeline.share import _RowBlock
    cap, weights, empty_steps, outside = ROW_BLOCK_CASES[case]
    rng = np.random.default_rng(sorted(ROW_BLOCK_CASES).index(case))
    width = 3
    # few keys, so puts repeat them; the ends of uint64 too
    universe = np.unique(np.concatenate([
        rng.integers(0, 2**63, 60, dtype=np.uint64),
        np.array([0, 2**64 - 1], np.uint64)]))
    block = _RowBlock(width, np.float32, cap=cap)
    ref = {}                                  # fingerprint -> row stored
    order = []                                # fingerprints, oldest first
    puts_onto_a_run = 0
    for step in range(120):
        op = rng.choice(["put", "lookup", "drop"],
                        p=np.array(weights) / sum(weights))
        if op == "put":
            n = 0 if step < empty_steps else int(rng.integers(0, 24))
            fps = rng.choice(universe, n)     # in-call duplicates too
            rows = rng.standard_normal((n, width)).astype(np.float32)
            puts_onto_a_run += block._run is not None and n > 0
            fresh = {}
            for fp, row in zip(fps.tolist(), rows):
                if fp not in ref:             # first one in wins
                    fresh.setdefault(fp, row)
            ref.update(fresh)
            order += sorted(fresh)            # a put stores them sorted
            assert block.put(fps, rows) == len(fresh) * (width * 4 + 8)
        elif op == "drop":
            freed = block.drop_oldest(float(rng.uniform(0.1, 0.9)))
            gone = len(order) - block.used
            for fp in order[:gone]:
                del ref[fp]
            assert freed == gone * (width * 4 + 8)
            order = order[gone:]
        else:
            q = np.concatenate([universe, rng.choice(universe, 16)])
            if outside:                       # misses between held keys
                q = np.concatenate([q, rng.integers(
                    0, 2**64 - 1, outside, np.uint64, endpoint=True)])
                assert len(q) > 8 * len(universe) > 8 * block.used
            rng.shuffle(q)
            idx, found = block.lookup(q)
            want = np.array([fp in ref for fp in q.tolist()])
            np.testing.assert_array_equal(found, want)
            if want.any():
                np.testing.assert_array_equal(
                    block.E[idx[found]],
                    np.stack([ref[fp] for fp in q[found].tolist()]))
            if block.used:
                assert block._run is None
            np.testing.assert_array_equal(
                block._sorted, np.sort(block.fps[:block.used]))
            np.testing.assert_array_equal(block.fps[block._order],
                                          block._sorted)
        assert block.used == len(order) <= len(block.E)
        np.testing.assert_array_equal(block.fps[:block.used],
                                      np.array(order, np.uint64))
        if order:
            np.testing.assert_array_equal(
                block.E[:block.used], np.stack([ref[fp] for fp in order]))
    assert len(block.E) > cap or case == "empty_block"
    if case == "pending_run_across_puts":
        assert puts_onto_a_run >= 5
    if case in ("shed", "pending_run_across_puts", "empty_block",
                "wide_lookups"):
        assert 0 < len(order) < len(universe)  # the sheds left keys out


def test_pipeline_chunked_matches_single_shot():
    rng = np.random.default_rng(0)
    n = 500
    table = {"x": rng.standard_normal((n, 8)).astype(np.float32),
             "v": rng.integers(0, 50, n)}
    W = rng.standard_normal((8, 3)).astype(np.float32)

    def predict(b):
        out = dict(b)
        out["p"] = (b["x"] @ W).sum(axis=1)
        return out

    d = Dag()
    d.add(Node("src", "scan"))
    d.add(Node("f", "filter",
               fn=lambda b: filter_op(b, lambda x: x["v"] > 10)),
          deps=("src",))
    d.add(Node("p", "predict", fn=predict, cost_hint=5), deps=("f",))
    ex = PipelineExecutor(d)
    full = ex.execute({"src": table})["p"]
    chunked = ex.execute_chunked("src", table, chunk_rows=64, sink_id="p")
    np.testing.assert_allclose(np.sort(full["p"]), np.sort(chunked["p"]),
                               rtol=1e-6)


def test_join_duplicate_keys_both_sides_ordering():
    """Vectorized sort-merge join must match hash-join semantics: probe
    rows in order, ties expanded in build-side row order."""
    left = {"k": np.array([2, 1, 2]), "x": np.array([10.0, 20.0, 30.0])}
    right = {"k": np.array([2, 3, 2, 1]),
             "y": np.array([1.0, 2.0, 3.0, 4.0])}
    j = join(left, right, "k")
    np.testing.assert_array_equal(j["k"], [2, 2, 1, 2, 2])
    np.testing.assert_array_equal(j["x"], [10.0, 10.0, 20.0, 30.0, 30.0])
    np.testing.assert_array_equal(j["y"], [1.0, 3.0, 4.0, 1.0, 3.0])


def test_join_string_keys_and_column_suffix():
    left = {"k": np.array(["a", "b", "c"]), "v": np.arange(3.0)}
    right = {"k": np.array(["b", "c", "d"]), "v": np.array([9.0, 8.0, 7.0])}
    j = join(left, right, "k")
    np.testing.assert_array_equal(j["k"], ["b", "c"])
    np.testing.assert_array_equal(j["v"], [1.0, 2.0])
    np.testing.assert_array_equal(j["v_r"], [9.0, 8.0])


def test_join_no_matches_and_empty_sides():
    left = {"k": np.array([1, 2]), "x": np.array([1.0, 2.0])}
    right = {"k": np.array([3, 4]), "y": np.array([5.0, 6.0])}
    j = join(left, right, "k")
    assert len(j["k"]) == 0 and len(j["y"]) == 0
    j2 = join({"k": np.zeros(0, np.int64), "x": np.zeros(0)},
              right, "k")
    assert len(j2["k"]) == 0
    j3 = join(left, {"k": np.zeros(0, np.int64), "y": np.zeros(0)}, "k")
    assert len(j3["k"]) == 0


def test_join_matches_naive_reference():
    rng = np.random.default_rng(0)
    left = {"k": rng.integers(0, 20, 200), "x": rng.standard_normal(200)}
    right = {"k": rng.integers(0, 20, 60), "y": rng.standard_normal(60)}
    j = join(left, right, "k")
    li, ri = [], []
    for i, k in enumerate(left["k"]):
        for jj, kk in enumerate(right["k"]):
            if k == kk:
                li.append(i)
                ri.append(jj)
    np.testing.assert_array_equal(j["k"], left["k"][li])
    np.testing.assert_allclose(j["x"], left["x"][li])
    np.testing.assert_allclose(j["y"], right["y"][ri])
