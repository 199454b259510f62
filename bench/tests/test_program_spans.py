"""The program's spans in a trace (``harness.program_spans``) and the
readers of the program's spans and counters (``ServerStats``), on
synthetic inputs whose numbers are worked out by hand."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import program_spans as ps  # noqa: E402
from harness import spec as specs  # noqa: E402
from harness import trace as tr  # noqa: E402
from test_bench_trace import MS, _ctx, synthetic  # noqa: E402


def with_program_spans() -> tr.Trace:
    """``synthetic()`` with the program's spans added. The lane worker:
    lane.collect 0-2 ms, then lane.step 2-72 ms holding share.lookup 2-5
    (share.resort 3-5), the harness's run_infer 5-45 (backend.run_infer
    6-44, its backend.fetch 26-44) and lane.head 55-72 (the harness's
    head 60-70), then lane.publish 72-74. A client: the harness's submit
    49-91 around engine.submit 50-90."""
    t = synthetic()
    t0 = t.lo_ns

    def iv(a, b):
        return [(t0 + a * MS, t0 + b * MS)]
    t.spans.update({
        "lane.collect": iv(0, 2), "lane.step": iv(2, 72),
        "share.lookup": iv(2, 5), "share.resort": iv(3, 5),
        "backend.run_infer": iv(6, 44), "backend.fetch": iv(26, 44),
        "lane.head": iv(55, 72), "lane.publish": iv(72, 74),
        "engine.submit": iv(50, 90), "submit": iv(49, 91)})
    return t


def test_span_at_names_the_innermost_lane_span_first():
    t = with_program_spans()

    def at(ms):
        return ps.span_at(t, t.lo_ns + ms * MS)
    assert at(1) == "lane.collect"
    assert at(4) == "share.resort"
    assert at(5.5) == "run_infer"        # harness span outside the program's
    assert at(30) == "backend.fetch"
    assert at(50.5) == "lane.step"       # the lane beats a concurrent submit
    assert at(65) == "head"              # harness head inside lane.head
    assert at(80) == "engine.submit"     # no lane span: the innermost front
    assert at(90.5) == "submit"
    assert at(95) == tr.HOST_OTHER


def test_harness_spans_alone_are_named_as_trace_names_them():
    t = synthetic()
    t.spans["submit"] = [(t.lo_ns + 3 * MS, t.lo_ns + 65 * MS)]
    for ms in (1, 4, 7, 46, 50, 62, 66, 80):
        moment = t.lo_ns + ms * MS
        assert ps.span_at(t, moment) == tr.span_at(t, moment), ms
    assert ps.idle_gaps(t) == tr.breakdown(t)["idle_gaps"]


def test_idle_gaps_by_hand():
    # chip 0 idles 0-10 (middle 5: run_infer, which opened after
    # share.resort), 25-30 (backend.fetch) and 40-100 (middle 70: the
    # harness's head, inside lane.head)
    gaps = ps.idle_gaps(with_program_spans())
    assert [g[0] for g in gaps] == ["head", "run_infer", "backend.fetch"]
    assert [g[1] for g in gaps] == pytest.approx([0.060, 0.010, 0.005])
    assert ps.idle_gaps(with_program_spans(), top=1) == gaps[:1]
    assert ps.idle_gaps(tr.Trace(0, 10, {}, {})) == []


def test_idle_by_span_by_hand():
    # chip 0 idles 0-10, 25-30 and 40-100 ms; each stretch is split over
    # the name of each moment of it (see with_program_spans)
    idle = ps.idle_by_span(with_program_spans())
    want_ms = {"engine.submit": 16, "lane.step": 10, "head": 10,
               tr.HOST_OTHER: 9, "backend.fetch": 8, "lane.head": 7,
               "backend.run_infer": 5, "lane.collect": 2, "share.resort": 2,
               "run_infer": 2, "lane.publish": 2, "share.lookup": 1,
               "submit": 1}
    assert set(idle) == set(want_ms)
    for name, ms in want_ms.items():
        assert idle[name] == pytest.approx(ms * 1e-3), name
    assert list(idle)[0] == "engine.submit"
    assert sum(idle.values()) == pytest.approx(0.075)
    # harness spans only: every stretch as span_at names it
    idle = ps.idle_by_span(synthetic())
    assert idle == pytest.approx({"run_infer": 0.015, "head": 0.010,
                                  tr.HOST_OTHER: 0.050})
    assert ps.idle_by_span(tr.Trace(0, 10, {}, {})) == {}


def _full_ctx(trace):
    ctx = _ctx(trace, 2 * 65536, [65536, 65536])
    ctx.spans.seconds.update(submit=[0.001, 0.003, 0.002],
                             run_infer=[0.010, 0.030])
    ctx.stats = SimpleNamespace(embed_rows=2 * 65536, batches=4,
                                mean_coalesced=1.5, share_hits=3,
                                share_misses=1, approx_hits=0,
                                share_hit_rate=0.75)
    return ctx


EXISTING = ("submit_ms", "lane.mean_coalesced", "share.hit_rate",
            "backend.ms_per_krow", "device.idle_share", "mfu",
            "fused_embed_roofline")


def test_existing_readers_ignore_program_spans():
    plain, ctx_plain = synthetic(), _full_ctx(synthetic())
    ctx_prog = _full_ctx(with_program_spans())
    for name in EXISTING:
        read = specs.metric_reader(name)
        assert read(ctx_prog) == read(ctx_plain), name
    assert ctx_prog.notes == ctx_plain.notes
    assert tr.breakdown(with_program_spans()) == tr.breakdown(plain)


def _program_stats(**kw):
    """ServerStats as the program exports them over a window: 4 lane
    steps, whose direct children sum to 1.9 of their 2.0 s."""
    st = SimpleNamespace(
        batches=4, p50_queue_wait_s=0.012, share_rows_held=3_000_000,
        span_seconds={"lane.step": 2.0, "lane.stack": 0.1,
                      "share.lookup": 0.5, "share.fingerprint": 0.1,
                      "share.resort": 0.3, "lane.dedup": 0.05,
                      "backend.run_infer": 0.6, "backend.pad": 0.1,
                      "lane.scatter": 0.05, "share.insert": 0.4,
                      "lane.head": 0.2, "lane.collect": 5.0},
        span_calls={"lane.step": 4, "share.resort": 2, "share.grow": 1,
                    "share.evict": 0},
        counts={"share.lookup_rows": 100_000, "share.insert_rows": 40_000,
                "share.resort_rows": 6_000_000, "share.grow_bytes": 1024,
                "backend.rows": 30_000, "backend.bucket_rows": 40_000})
    for k, v in kw.items():
        setattr(st, k, v)
    return st


NEW = {"lane.queue_wait_ms": 12.0,          # 0.012 s
       "lane.self_ms_per_batch": 25.0,      # (2.0 - 1.9) s / 4 steps
       "share.lookup_ms_per_krow": 2.0,     # (0.5 - 0.3) s / 100 krows
       "share.resort_ms_per_batch": 75.0,   # 0.3 s / 4 steps
       "share.insert_ms_per_krow": 10.0,    # 0.4 s / 40 krows
       "backend.pad_share": 25.0}           # 10,000 of 40,000 rows


@pytest.mark.parametrize("name", sorted(NEW))
def test_program_span_readers_by_hand(name):
    ctx = _ctx(None, 0, [])
    ctx.stats = _program_stats()
    assert specs.metric_reader(name)(ctx) == pytest.approx(NEW[name])
    # a program without these spans and counters: nothing to read
    ctx.stats = SimpleNamespace(batches=4, embed_rows=10)
    assert specs.metric_reader(name)(ctx) is None


def test_resort_reader_notes_the_cache_state():
    ctx = _ctx(None, 0, [])
    ctx.stats = _program_stats()
    specs.metric_reader("share.resort_ms_per_batch")(ctx)
    assert ctx.notes == [
        "share cache in the window: 3000000 rows held at the close; 2 "
        "re-sorts of 6000000 rows; 1 grows copying 1024 bytes; 0 "
        "evictions of 0 rows"]
    # steps but no re-sort: a zero, not nothing
    ctx.stats = _program_stats(span_seconds={"lane.step": 1.0})
    assert specs.metric_reader("share.resort_ms_per_batch")(ctx) == 0.0
