"""BENCHMARK.json against its contract, everything loaded by name from
its files, and the entry point's refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import spec as specs  # noqa: E402
from traffic.generator import Traffic  # noqa: E402

BM = specs.benchmark()
E2E = {m["name"] for m in BM["end_to_end"]}


def test_keys_names_and_units():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert all(specs.valid_name(n) for n in names), names
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in BM[k]}) == len(BM[k])
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert specs.valid_unit(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    cells = {c["name"] for c in BM["workloads"]}
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    assert "setup_s" in E2E
    pairs = [(c["config"], c["traffic"]) for c in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in BM["workloads"]:
        assert specs.valid_name(c["config"]) and specs.valid_name(c["traffic"])
        assert c["chips"] in (1, 4) and 0 < len(c["why"]) <= 200
    assert sum(c["chips"] == 4 for c in BM["workloads"]) <= max(
        1, len(BM["workloads"]) // 2)


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_loads_and_moves_a_reported_metric(m):
    assert callable(specs.metric_reader(m["name"]))
    assert m["moves"] in E2E and m["moves"] != "setup_s"
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert m["layer"] and "\n" not in m["layer"]
    moves = next(e for e in BM["end_to_end"] if e["name"] == m["moves"])
    for cell in m.get("workloads", [c["name"] for c in BM["workloads"]]):
        specs.cell(BM, cell)
        assert "workloads" not in moves or cell in moves["workloads"]


@pytest.mark.parametrize("c", BM["workloads"], ids=lambda c: c["name"])
def test_cell_loads_by_name(c):
    cfg = specs.config(BM, c["config"])
    assert cfg["name"] == c["config"] and cfg["reduced"] == []
    tm = specs.trunk_module(cfg)
    assert tm.flops_per_row(cfg) > 0
    wl = specs.workload(c["traffic"])
    assert wl["why"] and wl["clients"] >= 1
    # the arrival driver, set-up steps and request kinds it names load
    # from their own files
    assert callable(specs.arrivals(wl["arrivals"]).drive)
    for step in wl["setup"]:
        assert callable(specs.step(step["step"]).run)
    for m in wl["mix"]:
        kind = specs.kind(m["kind"])
        for fn in ("cycle", "layout", "request", "rows", "write",
                   "trunk_rows"):
            assert callable(getattr(kind, fn)), (m["kind"], fn)
    t = Traffic(wl, 2**31 + 11)
    reqs = [t.next_request() for _ in range(2 * t.cycle)]
    # every seed sends the same work: each cycle holds the same sizes
    first = sorted(r.n for r in reqs[:t.cycle])
    assert first == sorted(r.n for r in reqs[t.cycle:])
    other = Traffic(wl, 5)
    assert first == sorted(other.next_request().n for _ in range(t.cycle))
    # a request's rows are rebuilt from the seed alone
    r = next(r for r in reqs if r.n <= 4096)
    assert (t.rows_of(r) == Traffic(wl, 2**31 + 11).rows_of(r)).all()
    assert len(t.rows_of(r)) == r.n
    for m in specs.metrics_for(BM, c["name"], "per_layer"):
        assert specs.metric_reader(m["name"])
    assert {m["name"] for m in specs.metrics_for(
        BM, c["name"], "end_to_end")} >= {"setup_s", "rows_per_s"}


def test_configs_files_and_peaks():
    files = [c["file"] for c in BM["configs"]]
    assert len(set(files)) == len(files)
    for c in BM["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert 0 < len(c["source"]) <= 200 and set(c) == {
            "name", "source", "file", "reduced", "why"}
    assert specs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        specs.peaks("cpu")


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu"}, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "linear-cold",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[
        -1].startswith("{")
    assert "not a TPU" in p.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert json.loads((tmp_path / "BENCHMARK.json").read_text()) == BM


def test_a_kind_may_give_its_own_reference(monkeypatch):
    """The check asks a request's kind for the reference's answers where
    the kind gives them (a kind naming fine-tuned tasks would), and
    otherwise runs the trunk module's reference over the kind's rows."""
    import numpy as np
    from types import SimpleNamespace
    from traffic import generator as g
    t = Traffic(specs.workload("linear-cold"), 7)
    reqs = [t.next_request() for _ in range(3)]
    trunk = SimpleNamespace()
    tm = SimpleNamespace(reference_scores=lambda tr, X: X[:, 0] * 2)
    plain = g.reference(t, tm, trunk, reqs)
    for r, want in zip(reqs, plain):
        assert (want == t.rows_of(r)[:, 0] * 2).all()
    own = SimpleNamespace(reference=lambda tr, m, k, rs: [
        np.full(r.n, r.index, np.float32) for r in rs])
    monkeypatch.setattr(g.specs, "kind", lambda name: own)
    got = g.reference(t, tm, trunk, reqs)
    assert [a[0] for a in got] == [r.index for r in reqs]
