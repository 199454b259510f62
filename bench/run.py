"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload linear-cold --seed 7 --seconds 10 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs``) and a traffic file (``bench/workloads``). The run
builds the engine and its inputs from ``--seed``, warms up every trunk
shape the traffic uses and runs the cell's own set-up steps (a share
cache filled until it evicts, or a scored table), then drives
``MorphingServer.submit`` for ``--seconds`` with the workload's arrival
driver. Afterwards it sends the last answered requests again, so that
the share cache serves them, and checks those answers and a sample of
the window's against the plain reference.

Earlier lines report the device, the set-up split, the window's counts
and the compiles inside it. The last lines of standard error give each
number compared beside its limit; the last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``) and ``device``.

It fails, and prints no result, unless JAX finds a TPU with as many
chips as the cell asks for. JAX's persistent compilation cache is kept
in ``<checkout>/.jax_cache``, so only a cell's first run in a checkout
compiles, unless the program builds a new program for a new seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program under {ROOT / 'src'}: this checkout holds "
                    "the benchmark alone")
    sys.path.insert(0, str(ROOT / "src"))
    from harness import spec as specs
    bench = specs.benchmark(ROOT)
    try:
        cell = specs.cell(bench, args.workload)
    except KeyError as e:
        return fail(str(e))
    import jax
    # one fixed directory inside the checkout, whatever the environment
    # says: the path is part of the cache's key, and the two sides of a
    # comparison must share nothing
    cache = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"jax found platform {devs[0].platform!r} "
                    f"({len(devs)} device(s)), not a TPU")
    if len(devs) < int(cell["chips"]):
        return fail(f"cell {cell['name']} needs {cell['chips']} chips, jax "
                    f"found {len(devs)}")
    try:
        peaks = specs.peaks(devs[0].device_kind)
    except KeyError as e:
        return fail(str(e))
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}; cell {cell['name']} on {cell['chips']}; "
        f"compile cache {cache}")
    from harness.cell import run_cell
    seed = args.seed % (1 << 64)
    result = run_cell(bench, cell["name"], seed, args.seconds,
                      bool(args.trace), T_START, log, peaks)
    compared = result.pop("compared")
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
