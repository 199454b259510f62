"""The reduction from a trace, spans and counters to per-layer metrics,
on a synthetic trace whose numbers are worked out by hand."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import spec as specs  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.cell import Context, Spans  # noqa: E402

MS = 1_000_000  # ns


def synthetic() -> tr.Trace:
    """A 100 ms window (from 1000 ms) on two chips. Chip 0 runs a kernel
    from 10-20 ms and 30-40 ms and a copy at 15-25 ms that overlaps it;
    chip 1 runs the kernel at 50-60 ms and an op that starts before the
    window opens. Host spans: run_infer 5-45 ms, head 60-70 ms."""
    t0 = 1000 * MS

    def op(a, b, name):
        return tr.Op(t0 + a * MS, t0 + b * MS, name)
    return tr.Trace(
        lo_ns=t0, hi_ns=t0 + 100 * MS,
        devices={
            "/device:TPU:0": [
                op(10, 20, "fused_embed.1"),
                op(15, 25, "copy.2"),
                op(30, 40, "fused_embed.1")],
            "/device:TPU:1": [
                op(-5, 5, "fusion.3"),
                op(50, 60, "fused_embed")]},
        spans={"run_infer": [(t0 + 5 * MS, t0 + 45 * MS)],
               "head": [(t0 + 60 * MS, t0 + 70 * MS)], "submit": []})


def test_op_names_from_hlo_text():
    text = ('%fused_embed.1 = f32[65536,39]{1,0} custom-call(f32[65536,16]'
            '{1,0} %copy), custom_call_target="tpu_custom_call"')
    assert tr.op_name(text) == "fused_embed.1"
    copy = ('%copy.1 = f32[65536,39]{0,1} copy(f32[65536,39]{1,0} '
            '%fused_embed.1)')
    assert tr.op_name(copy) == "copy.1"
    t = tr.Trace(0, 10, {"d": [tr.Op(0, 1, tr.op_name(copy)),
                               tr.Op(1, 3, tr.op_name(text))]}, {})
    assert [o.name for o in tr.kernel_ops(t, "fused_embed")] == [
        "fused_embed.1"]
    assert tr.covered_share(tr.kernel_ops(t, "fused_embed"),
                            [(0, 2)]) == pytest.approx(0.5)


def test_union_and_gaps_by_hand():
    assert tr.union_ns([(0, 10), (5, 15), (20, 30)], 0, 100) == 25
    assert tr.union_ns([(-10, 10), (90, 120)], 0, 100) == 20
    assert tr.gaps([(10, 20), (15, 25), (30, 40)], 0, 50) == [
        (0, 10), (25, 30), (40, 50)]


def test_busy_idle_and_kernel_time():
    t = synthetic()
    assert t.window_s == pytest.approx(0.1)
    # chip 0: 10-25 and 30-40 -> 25 ms; chip 1: 0-5 and 50-60 -> 15 ms
    assert tr.device_busy_s(t) == pytest.approx([0.025, 0.015])
    ops = tr.kernel_ops(t, "fused_embed")
    assert len(ops) == 3
    assert tr.clipped_s(ops, t.lo_ns, t.hi_ns) == pytest.approx(0.030)
    ctx = SimpleNamespace(trace=t)
    idle = specs.metric_reader("device.idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - 0.020 / 0.1))


def test_breakdown_names_gaps_by_host_span():
    b = tr.breakdown(synthetic())
    ops = dict(b["device_ops"])
    assert ops["fused_embed.1"] == pytest.approx(0.020)
    assert ops["fusion.3"] == pytest.approx(0.005)      # clipped to window
    # chip 0's gaps: 0-10 (run_infer at 5), 25-30 (run_infer), 40-100
    # (middle 70: the head span's end)
    assert b["idle_gaps"][0][1] == pytest.approx(0.060)
    assert b["idle_gaps"][0][0] == "head"
    assert [g[0] for g in b["idle_gaps"][1:]] == ["run_infer", "run_infer"]
    assert tr.span_at(synthetic(), 1000 * MS + 80 * MS) == tr.HOST_OTHER


def _ctx(trace, rows, calls, window_s=0.1, chips=2, mode="linear"):
    cfg = {"trunk": "zoo", "mode": mode, "in_dim": 16, "width": 39}
    spans = Spans()
    spans.trunk_rows = list(calls)
    stats = SimpleNamespace(embed_rows=rows)
    return Context(cell={}, config=cfg,
                   trunk=specs.trunk_module(cfg), stats=stats,
                   window_s=window_s, chips=chips,
                   peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                   spans=spans, trace=trace)


def test_roofline_arithmetic_and_bound():
    # two calls of 65,536 rows: each reads 65,536 x 16 x 4 B, writes
    # 65,536 x 39 x 4 B, and reads the 16 x 39 weights once
    rows = 65536
    nbytes = 4 * (rows * 16 + 16 * 39 + rows * 39)
    flops = 2 * rows * 16 * 39
    least = 2 * max(flops / 197e12, nbytes / 819e9)
    ctx = _ctx(synthetic(), 2 * rows, [rows, rows])
    share = specs.metric_reader("fused_embed_roofline")(ctx)
    assert share == pytest.approx(100 * least / 0.030)
    assert "memory-bound" in ctx.notes[0]
    # no kernel op in the trace: nothing to read, never a zero
    empty = tr.Trace(0, 10, {"/device:TPU:0": [tr.Op(1, 2, "copy")]}, {})
    assert specs.metric_reader("fused_embed_roofline")(
        _ctx(empty, 10, [10])) is None


def test_mfu_arithmetic():
    # linear: 2 x 16 x 39 = 1,248 operations a row
    ctx = _ctx(None, 1_000_000, [], window_s=10.0, chips=2)
    mfu = specs.metric_reader("mfu")(ctx)
    assert mfu == pytest.approx(100 * 1248 * 1e6 / (10.0 * 2 * 197e12))
    # radial: 3 x 16 x 39 + 39 = 1,911 a row
    ctx = _ctx(None, 1_000_000, [], window_s=10.0, chips=1, mode="radial")
    assert specs.metric_reader("mfu")(ctx) == pytest.approx(
        100 * 1911 * 1e6 / (10.0 * 197e12))
    assert specs.metric_reader("mfu")(_ctx(None, 0, [])) is None


def test_host_span_and_counter_readers():
    ctx = _ctx(None, 4000, [])
    ctx.spans.seconds["submit"] = [0.001, 0.003, 0.002]
    ctx.spans.seconds["run_infer"] = [0.010, 0.030]
    ctx.stats = SimpleNamespace(embed_rows=4000, batches=4,
                                mean_coalesced=1.5, share_hits=3,
                                share_misses=1, approx_hits=0,
                                share_hit_rate=0.75)
    assert specs.metric_reader("submit_ms")(ctx) == pytest.approx(2.0)
    assert specs.metric_reader("backend.ms_per_krow")(ctx) == \
        pytest.approx(40.0 / 4.0)
    assert specs.metric_reader("lane.mean_coalesced")(ctx) == 1.5
    assert specs.metric_reader("share.hit_rate")(ctx) == pytest.approx(75.0)
