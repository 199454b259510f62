"""Everything the harness reads by name: ``BENCHMARK.json``, the cell's
workload and configuration files, the trunk module, the request kinds,
set-up steps and arrival drivers a workload names, the metric readers
and the table of peaks. A new cell, configuration or metric is new files
and new entries here, never an edit of this module."""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: "
                   f"{[c['name'] for c in bench['workloads']]})")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def workload(name: str) -> dict:
    """A traffic file, with ``extends`` resolved: the named file's
    parameters, overlaid by this file's own."""
    spec = load_json(BENCH_DIR / "workloads" / f"{name}.json")
    base = spec.pop("extends", None)
    if base is None:
        return spec
    merged = workload(base)
    merged.update(spec)
    return merged


def _module(path: Path, name: str) -> ModuleType:
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def trunk_module(cfg: dict) -> ModuleType:
    return _module(BENCH_DIR / "trunks" / f"{cfg['trunk']}.py",
                   f"bench_trunk_{cfg['trunk']}")


def kind(name: str) -> ModuleType:
    """A request kind, ``bench/kinds/<name>.py``."""
    return _module(BENCH_DIR / "kinds" / f"{name}.py", f"bench_kind_{name}")


def step(name: str) -> ModuleType:
    """A set-up step, ``bench/steps/<name>.py``."""
    return _module(BENCH_DIR / "steps" / f"{name}.py", f"bench_step_{name}")


def arrivals(name: str) -> ModuleType:
    """An arrival driver, ``bench/arrivals/<name>.py``."""
    return _module(BENCH_DIR / "arrivals" / f"{name}.py",
                   f"bench_arrivals_{name}")


def metric_reader(name: str) -> Callable:
    """``read(ctx) -> float | None`` from ``bench/metrics/<name>.py``."""
    return _module(BENCH_DIR / "metrics" / f"{name}.py",
                   f"bench_metric_{name.replace('.', '_')}").read


def metrics_for(bench: dict, cell_name: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    without a ``workloads`` key, and those that list it."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks(kind: str) -> Dict[str, float]:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def valid_name(s: Optional[str]) -> bool:
    return isinstance(s, str) and bool(NAME_RE.match(s))


def valid_unit(s: Optional[str]) -> bool:
    return isinstance(s, str) and bool(UNIT_RE.match(s))
