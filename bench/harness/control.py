"""The control of the correctness check: the reference, put in the
program's place and computed one precision step below what the
configuration states, has to come out as not correct.

- ``zoo-linear`` states float32 at ``Precision.HIGHEST``: the control is
  three bfloat16 passes (``precision="high"``).
- ``zoo-radial`` states float32 with no matmul: the control runs the
  forward in bfloat16.

The control answers the requests a window would send first (and the
largest of the mix) and is judged by the same comparison as the
program. The benchmark's own runs never run it; ``bench/control.py``
reads it on the chip, and ``test_control_fails`` in
``bench/tests/test_bench_faults.py`` on the CPU.
"""
from __future__ import annotations

from harness import spec as specs
from harness.cell import check, sample
from harness.serving import Done
from traffic.generator import Traffic


def lower_precision(cfg: dict) -> dict:
    if cfg["mode"] == "linear":
        return {"precision": "high"}
    return {"dtype": "bfloat16"}


def control_gap(cfg: dict, wl: dict, seed: int, requests: int) -> dict:
    """The control's numbers compared, over the first ``requests`` of the
    seed's sequence, with the cell's own sample size and limit."""
    tm = specs.trunk_module(cfg)
    trunk = tm.build(cfg, seed)[0]
    traffic = Traffic(wl, seed)
    low = lower_precision(cfg)
    done = []
    for _ in range(requests):
        r = traffic.next_request()
        done.append(Done(r, 0.0, 0.0, tm.reference_scores(
            trunk, traffic.rows_of(r), **low)))
    compared, _ = check(traffic, tm, trunk,
                        sample(done, seed, int(wl["check_requests"])), 0,
                        float(cfg["check"]["score_gap_limit"]))
    return compared
