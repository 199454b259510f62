"""The correctness check, driven through a whole run at a size a test can
hold, with the timed path broken underneath: each fault has to turn
``correct`` false, and the run left whole has to stay correct. The
control (the reference one precision step down) has to fail too."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import spec as specs  # noqa: E402
from harness.cell import run_cell  # noqa: E402
from harness.control import control_gap  # noqa: E402
from traffic.generator import Traffic  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 77
# every cell the tests drive: (config, traffic, chips), as BENCHMARK.json
# holds it or will hold it once the cell is measured on the chip
CELLS = {"linear-cold": ("zoo-linear", "linear-cold", 1),
         "linear-hot": ("zoo-linear", "linear-hot", 1),
         "radial-cold": ("zoo-radial", "radial-cold", 1),
         "linear-cold-x4": ("zoo-linear", "linear-cold-x4", 4)}


def bench_with(cell: str) -> dict:
    bench = specs.benchmark()
    if all(c["name"] != cell for c in bench["workloads"]):
        cfg, traffic, chips = CELLS[cell]
        bench["workloads"].append({"name": cell, "config": cfg,
                                   "traffic": traffic, "chips": chips})
        if all(c["name"] != cfg for c in bench["configs"]):
            bench["configs"].append({"name": cfg,
                                     "file": f"bench/configs/{cfg}.json"})
    return bench


def small(cell: str) -> dict:
    """The cell's traffic on 16,384-row tables with 64-1,024-row
    requests: every layer of a run, at a size the CPU holds."""
    wl = specs.workload(CELLS[cell][1])
    return {"tables": {t: {"rows": 16384, "width": 16}
                       for t in wl["tables"]},
            "mix": [dict(m, rows={"lo": 64, "hi": 1024}) for m in wl["mix"]],
            "check_requests": 8, "resend_requests": 4}


def run(cell: str, fault=None, seconds: float = 1.5, **wl) -> dict:
    return run_cell(bench_with(cell), cell, SEED, seconds, False,
                    time.perf_counter(), lambda _m: None, PEAKS,
                    workload_overrides={**small(cell), **wl},
                    engine_overrides={"share_capacity_bytes": 16 << 20},
                    break_path=fault)


def trunk_output_altered(eng):
    """One feature of every 64th row of each trunk call is off by 0.01,
    so that every sampled request holds an altered row."""
    run_infer = eng.backend.run_infer

    def broken(spec, batch):
        out = dict(run_infer(spec, batch))
        F = np.array(out[spec.out], np.float32)
        F[::64, 0] += 0.01
        out[spec.out] = F
        return out
    eng.backend.run_infer = broken


def head_output_altered(eng):
    """Every 64th score of each head call is off by 1e-3."""
    run_head = eng.backend.run_head

    def broken(spec, F):
        y = np.array(run_head(spec, F), np.float32)
        y[::64] += 1e-3
        return y
    eng.backend.run_head = broken


def cached_rows_altered(eng):
    """The share cache hands back a neighbour's embedding for every 64th
    hit."""
    share = eng.session.share
    lookup = share.lookup_many

    def broken(*a, **kw):
        look = lookup(*a, **kw)
        hit = np.flatnonzero(~look.miss)
        if look.found is not None and len(hit) > 1:
            look.found[hit[:-1:64]] = look.found[hit[1::64]]
        return look
    share.lookup_many = broken


def stored_rows_altered(eng):
    """The share cache stores every 64th embedding it is given with one
    feature off by 0.01; the answer that computed it is right."""
    share = eng.session.share
    insert = share.insert_many

    def broken(table, column, keys, rows, embs, **kw):
        embs = np.array(embs, np.float32)
        embs[::64, 0] += 0.01
        return insert(table, column, keys, rows, embs, **kw)
    share.insert_many = broken


def cache_forgets(eng):
    """The share cache stores nothing: every row is computed again."""
    eng.session.share.insert_many = lambda *a, **kw: None


def rows_selected_off_by_one(eng):
    """The filter's row snapshot starts one row late."""
    rows_for = eng.server._rows_for

    def broken(table, col, preds):
        X = rows_for(table, col, preds)
        full = np.asarray(eng.session.tables[table][col])
        lo = int(next(v for c, op, v in preds if op == ">="))
        return full[lo + 1:lo + 1 + len(X)] if len(X) else X
    eng.server._rows_for = broken


@pytest.mark.parametrize("cell,fault,correct", [
    ("linear-cold", None, True),
    ("linear-cold", trunk_output_altered, False),
    ("linear-cold", head_output_altered, False),
    ("linear-cold", rows_selected_off_by_one, False),
    ("linear-cold", cached_rows_altered, False),
    ("linear-cold", stored_rows_altered, False),
    ("linear-cold", cache_forgets, False),
    ("radial-cold", trunk_output_altered, False),
    ("linear-hot", None, True),
    ("linear-hot", cached_rows_altered, False),
], ids=["linear-cold-whole", "linear-cold-trunk", "linear-cold-head",
        "linear-cold-filter", "linear-cold-cache", "linear-cold-insert",
        "linear-cold-forget", "radial-cold-trunk", "linear-hot-whole",
        "linear-hot-cache"])
def test_fault_turns_correct_false(cell, fault, correct):
    res = run(cell, fault)
    assert res["attempted"] > 0
    assert res["correct"] is correct, res["compared"]
    assert list(res)[-1] == "compared"


def test_fill_stops_at_the_first_eviction_and_watches_the_window():
    """``fill_cache`` stops once the cache has evicted the first round's
    marker, and after the window reads the oldest round held at the
    open: with a short window the cache still holds it, and in a cache
    of a sixteenth of the size the window's inserts evict it."""
    for cap_mib, seconds, evicted in ((16, 0.2, "no"), (1, 3.0, "yes")):
        lines = []
        res = run_cell(bench_with("linear-cold"), "linear-cold", SEED,
                       seconds, False, time.perf_counter(), lines.append,
                       PEAKS, workload_overrides=small("linear-cold"),
                       engine_overrides={
                           "share_capacity_bytes": cap_mib << 20})
        assert res["correct"] is True, res["compared"]
        fill = next(x for x in lines if x.startswith("set-up fill_cache"))
        assert "the cache evicted within" in fill, fill
        assert f"share cache evicted inside the window: {evicted}" in lines


def test_a_marker_reads_the_first_rows_of_its_request():
    """A fill round's marker is a shorter ``fresh`` request of the same
    index: it has to read the same first rows."""
    fresh = specs.kind("fresh")
    t = Traffic(specs.workload("linear-cold"), SEED)
    long = fresh.new_rows(t, "events", 2, 7, 131072)
    short = fresh.new_rows(t, "events", 2, 7, 1024)
    assert (t.rows_of(long)[:1024] == t.rows_of(short)).all()


@pytest.mark.parametrize("cell", ["linear-cold", "radial-cold"])
def test_control_fails(cell):
    """The reference one precision step below the configuration, in the
    program's place, is not correct: on every seed tried."""
    bench = bench_with(cell)
    c = specs.cell(bench, cell)
    cfg = specs.config(bench, c["config"])
    wl = {**specs.workload(c["traffic"]), **small(cell)}
    for seed in (1, 2, 3):
        gap = control_gap(cfg, wl, seed, 16)["score_gap"]
        assert gap["value"] > gap["limit"], (seed, gap)


MESH_SCRIPT = r"""
import json, sys, time
sys.path.insert(0, {bench!r})
import numpy as np
from harness import spec as specs
from harness.cell import run_cell
sys.path.insert(0, {tests!r})
from test_bench_faults import PEAKS, SEED, bench_with, small

def exchange_left_out(eng):
    # each chip's slice of the batch comes back, but only chip 0's is
    # gathered: the other rows are chip 0's rows again
    st = eng.backend._staged[next(iter(eng.backend._staged))]
    fn = st.features_fn
    def broken(X, *w):
        out = np.asarray(fn(X, *w))
        q = len(out) // 4
        return np.concatenate([out[:q]] * 4)
    st.features_fn = broken

bench = bench_with("linear-cold-x4")
out = {{}}
for name, fault in (("whole", None), ("exchange", exchange_left_out)):
    res = run_cell(bench, "linear-cold-x4", SEED, 1.5, False,
                   time.perf_counter(), lambda m: None, PEAKS,
                   workload_overrides=small("linear-cold"),
                   engine_overrides={{"share_capacity_bytes": 16 << 20}},
                   break_path=fault)
    out[name] = [res["correct"], res["compared"]["score_gap"]["value"]]
print(json.dumps(out))
"""


def test_mesh_exchange_left_out_is_caught():
    """linear-cold's traffic on a four-device mesh (four host devices):
    whole, it is correct; with the gather of the chips' slices left out,
    it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    code = MESH_SCRIPT.format(bench=str(BENCH),
                              tests=str(Path(__file__).parent))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["whole"][0] is True, out
    assert out["exchange"][0] is False, out
