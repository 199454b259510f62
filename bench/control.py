"""Read the correctness check's control on this machine's chip.

    python3 bench/control.py --workload linear-cold --seeds 11 12 13

For each seed, the reference computed one precision step below the
configuration answers the first ``--requests`` requests of the cell's
sequence, and the benchmark's own comparison judges the answers. Each
line gives the numbers compared beside their limits; the control has
to fail. The limit of each number is set between these readings and
the program's own, which every run of ``bench/run.py`` prints.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=64)
    args = ap.parse_args(argv)
    from harness import spec as specs
    from harness.control import control_gap, lower_precision
    import jax
    bench = specs.benchmark()
    c = specs.cell(bench, args.workload)
    cfg = specs.config(bench, c["config"])
    wl = specs.workload(c["traffic"])
    dev = jax.devices()[0]
    for seed in args.seeds:
        compared = control_gap(cfg, wl, seed % (1 << 64), args.requests)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "platform": dev.platform, "kind": dev.device_kind,
                          "control": lower_precision(cfg),
                          "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
