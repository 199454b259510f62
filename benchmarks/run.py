"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (values that are ratios or
counts are emitted as plain values; see each module). Modules may also
write machine-readable JSON artifacts next to the working directory —
``bench_engine`` writes ``BENCH_engine.json`` (rows/s per execution
backend, jax-vs-numpy speedup, share hit rate, compile/stage counts) so
the perf trajectory is tracked per PR.
"""
from __future__ import annotations

import os
import sys
import traceback

from repro.device import enable_compile_cache

MODULES = [
    "bench_series",      # Fig 6
    "bench_nlp",         # Fig 7
    "bench_image",       # Fig 8
    "bench_storage",     # Fig 9
    "bench_selection",   # Fig 10
    "bench_placement",   # Figs 11-12
    "bench_batchsize",   # Table 3
    "bench_sharing",     # Fig 13
    "bench_engine",      # ours: end-to-end engine vs per-row inference
    "bench_serving",     # ours: MorphingServer vs per-request execution
    "bench_sharding",    # ours: mesh-parallel embed lanes vs 1 device
    #                    # (run standalone for real simulated devices:
    #                    # earlier benches fix the jax device topology)
    "bench_roofline",    # ours: §Roofline summary
]


def main() -> int:
    print("name,us_per_call,derived")
    failed = []
    only = sys.argv[1:] if len(sys.argv) > 1 else None
    for mod_name in MODULES:
        if only and mod_name not in only:
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}",
                             fromlist=["run"])
            mod.run()
        except Exception:
            failed.append(mod_name)
            traceback.print_exc()
    for artifact in ("BENCH_engine.json", "BENCH_serving.json",
                     "BENCH_sharding.json"):
        if os.path.exists(artifact):
            print(f"# artifact: {artifact}")
    if failed:
        print(f"# FAILED: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
