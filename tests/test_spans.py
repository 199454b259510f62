"""In-program spans and counters on the served path (repro.pipeline.spans):
the lane sinks ServerStats exports, their counters against the server's
own, the re-sort and eviction counts, queue waits, the telemetry reset,
and the spans on a profiler trace's host plane."""
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.engine import MorphingServer, MorphingSession
from repro.pipeline import spans

ROOT = Path(__file__).resolve().parents[1]
SQL = "PREDICT emb USING TASK sent FROM reviews"

# each parent span and the spans that open directly inside it
CHILDREN = {
    "engine.submit": ("engine.parse", "engine.filter"),
    "lane.step": ("lane.stack", "share.lookup", "lane.dedup",
                  "backend.run_infer", "lane.scatter", "share.insert",
                  "lane.head"),
    "share.lookup": ("share.fingerprint", "share.resort"),
    "share.insert": ("share.grow", "share.evict"),
    "backend.run_infer": ("backend.pad", "backend.call", "backend.fetch",
                          "backend.compile"),
}


@pytest.fixture(scope="module")
def zoo():
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=120, dim=16, classes=3)
    return [pretrain_model(src, width=12, seed=1, name="m0")]


@pytest.fixture(scope="module")
def sample():
    return make_task(np.random.default_rng(1), "gauss", n=128, dim=16,
                     classes=3)


def serve(tmp_path, zoo, sample, n=600, **kw):
    """A started server over an ``n``-row table, jax backend (Pallas in
    interpret mode on the CPU)."""
    rng = np.random.default_rng(0)
    sess = MorphingSession(zoo=zoo, root=tmp_path, backend="jax",
                           model_store="decoupled", auto_calibrate=False,
                           **kw)
    sess.register_table("reviews", {
        "id": np.arange(n),
        "emb": rng.standard_normal((n, 16)).astype(np.float32)})
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0
    sess.resolve_task("sent", sample.X, sample.y)
    return MorphingServer(session=sess).start()


def ids(a, b):
    return f"{SQL} WHERE id >= {a} AND id < {b}"


def test_span_without_a_sink_only_annotates():
    outer, inner = spans.Sink(), spans.Sink()
    with spans.span("lane.step"):
        spans.count("backend.rows", 5)           # no sink bound: dropped
    with spans.bound(outer):
        with spans.bound(inner), spans.span("lane.step"):
            spans.count("backend.rows", 3)
        with spans.span("lane.publish"):
            pass
    with spans.span("lane.collect"):
        pass
    assert inner.calls == {"lane.step": 1}
    assert inner.counts == {"backend.rows": 3}
    assert outer.calls == {"lane.publish": 1} and outer.counts == {}
    assert inner.seconds["lane.step"] >= 0.0


def test_child_spans_fit_inside_their_parents(tmp_path, zoo, sample):
    server = serve(tmp_path, zoo, sample)
    front = spans.Sink()
    try:
        with spans.bound(front):                 # the client's own spans
            for a, b in ((0, 300), (0, 300), (200, 500), (0, 600)):
                server.result(server.submit(ids(a, b)), timeout=60.0)
    finally:
        server.stop()                            # the last publish ends
    st = server.stats()
    sec = dict(st.span_seconds)
    assert set(sec) <= set(spans.SPAN_NAMES)
    assert set(front.seconds) == {"engine.submit", "engine.parse",
                                  "engine.filter"}
    assert front.calls["engine.submit"] == 4
    sec.update(front.seconds)
    for parent, children in CHILDREN.items():
        assert sum(sec.get(c, 0.0) for c in children) <= sec[parent], parent
    assert st.span_calls["lane.step"] == st.batches == 4
    assert st.span_calls["lane.publish"] == 4
    assert st.span_calls["lane.collect"] >= 4


def test_counters_match_the_server_counters(tmp_path, zoo, sample):
    server = serve(tmp_path, zoo, sample)
    try:
        for a, b in ((0, 300), (100, 400), (0, 600), (0, 600)):
            server.predict(ids(a, b), timeout=60.0)
        st = server.stats()
    finally:
        server.stop()
    c = st.counts
    assert c["share.lookup_rows"] == st.share_hits + st.share_misses == 1800
    assert c["backend.rows"] == st.embed_rows == 600
    # three trunk calls of 300, 100 and 200 new rows: buckets 512, 128, 256
    assert c["backend.bucket_rows"] == 512 + 128 + 256
    assert c["backend.new_shapes"] == 3
    assert st.span_calls["backend.compile"] == 3
    assert "backend.call" not in st.span_calls
    assert c["share.insert_rows"] == 600
    assert st.share_rows_held == 600


def test_one_resort_per_batch_after_an_insert(tmp_path, zoo, sample):
    server = serve(tmp_path, zoo, sample)
    try:
        def batch(a, b):
            server.reset_telemetry()
            server.predict(ids(a, b), timeout=60.0)
            st = server.stats()
            return (st.span_calls.get("share.resort", 0),
                    st.counts.get("share.resort_rows", 0),
                    st.counts.get("share.insert_rows", 0))
        assert batch(0, 200) == (0, 0, 200)      # empty cache: no sort
        assert batch(0, 200) == (1, 200, 0)      # merges the insert
        assert batch(0, 200) == (0, 0, 0)        # all hits, sorted
        assert batch(100, 300) == (0, 0, 100)    # sorted still; inserts
        assert batch(0, 300) == (1, 100, 0)      # merges the 100 only
    finally:
        server.stop()


def test_evicted_rows_match_drop_oldest(tmp_path, zoo, sample):
    # 12 float32 features and an 8-byte fingerprint a row: 56 B; the
    # cache holds 357 rows, so each 300-row insert after the first takes
    # it to 600 rows and it sheds the oldest 300
    server = serve(tmp_path, zoo, sample, n=1300,
                   share_capacity_bytes=20_000)
    try:
        for a in range(0, 1200, 300):
            server.predict(ids(a, a + 300), timeout=60.0)
        st = server.stats()
        # 200 hits merge the last insert; 100 new rows take the cache to
        # 400 and it sheds 200, keeping 100 rows the index held and the
        # 100 new
        server.reset_telemetry()
        first = server.predict(ids(1000, 1300), timeout=60.0)
        shed = server.stats()
        # the next lookup merges the 100 new rows; it re-sorts none of
        # the 100 the index kept through the shed
        server.reset_telemetry()
        again = server.predict(ids(1200, 1300), timeout=60.0)
        after = server.stats()
    finally:
        server.stop()
    c = st.counts
    assert c["share.insert_rows"] == 1200
    assert st.share_rows_held == 300
    assert c["share.evicted_rows"] == 1200 - st.share_rows_held
    assert st.span_calls["share.evict"] == 3
    # each shed re-allocates the buffers to the rows kept, so each later
    # insert grows them again, copying the 300 rows held
    assert st.span_calls["share.grow"] == 3
    assert c["share.grow_bytes"] == 3 * 300 * 56
    assert (shed.span_calls["share.resort"],
            shed.counts["share.resort_rows"]) == (1, 300)
    assert shed.counts["share.evicted_rows"] == 200
    assert shed.share_rows_held == 200
    assert (after.span_calls["share.resort"],
            after.counts["share.resort_rows"]) == (1, 100)
    assert after.share_hits == 100 and after.share_misses == 0
    assert after.share_rows_held == 200
    np.testing.assert_array_equal(again.scores, first.scores[200:])


def test_queue_waits_are_no_longer_than_latencies(tmp_path, zoo, sample):
    server = serve(tmp_path, zoo, sample)
    errors = []

    def client(k):
        try:
            for i in range(4):
                a = (100 * (k + i)) % 500
                server.predict(ids(a, a + 100), timeout=60.0)
        except Exception as e:                   # asserted empty below
            errors.append(e)
    threads = [threading.Thread(target=client, args=(k,)) for k in range(4)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert not any(t.is_alive() for t in threads) and not errors
        st = server.stats()
        lane = next(iter(server._lanes.values()))
        waits = sorted(lane.batcher.queue_wait_snapshot())
        lat, _ = lane.batcher.telemetry()
    finally:
        server.stop()
    assert len(waits) == len(lat) == 16
    assert all(w <= x for w, x in zip(waits, sorted(lat)))
    assert 0.0 <= st.p50_queue_wait_s <= st.p50_latency_s
    assert st.p50_queue_wait_s <= st.p95_queue_wait_s <= st.p95_latency_s


def test_reset_telemetry_zeroes_every_sink(tmp_path, zoo, sample):
    server = serve(tmp_path, zoo, sample)
    try:
        server.predict(ids(0, 300), timeout=60.0)
        before = server.stats()
        server.reset_telemetry()
        st = server.stats()
    finally:
        server.stop()
    assert before.span_seconds and before.counts
    assert before.p50_queue_wait_s > 0.0
    assert st.span_seconds == {} and st.span_calls == {} and st.counts == {}
    assert st.p50_queue_wait_s == st.p95_queue_wait_s == 0.0
    assert st.share_rows_held == before.share_rows_held == 300  # a gauge


def test_profiler_trace_holds_the_program_spans(tmp_path, zoo, sample):
    import jax
    sys.path.insert(0, str(ROOT / "bench"))
    from harness import program_spans as ps
    from harness import trace as tr
    server = serve(tmp_path / "engine", zoo, sample)
    try:
        server.predict(ids(0, 300), timeout=60.0)   # compile outside
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                server.predict(ids(0, 300), timeout=60.0)
                server.predict(ids(200, 500), timeout=60.0)
        finally:
            jax.profiler.stop_trace()
    finally:
        server.stop()
    trace = ps.load(str(tmp_path / "trace"))
    for name in ("engine.submit", "engine.parse", "engine.filter",
                 "lane.step", "lane.publish", "share.lookup",
                 "share.fingerprint", "share.resort", "share.insert",
                 "lane.dedup", "backend.run_infer", "backend.pad",
                 "backend.fetch", "lane.head"):
        assert trace.spans[name], name
    assert len(trace.spans["lane.step"]) == 2
    (a, b), = trace.spans["backend.run_infer"]
    assert ps.span_at(trace, (a + b) / 2).startswith("backend.")
    # the harness's own reading keeps its spans alone
    assert set(tr.load(str(tmp_path / "trace")).spans) == set(tr.SPANS)
