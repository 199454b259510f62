"""Training launcher: config -> mesh -> sharded train loop with
checkpoint/restart, straggler monitoring, and metrics logging.

CPU-scale example (runs here):
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
      --steps 30 --batch 4 --seq 128
Production pods use the same entry point with --mesh single|multi.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.data import DataConfig, SyntheticCorpus
from repro.device import enable_compile_cache
from repro.distributed.sharding import axis_rules, rules_for_config, tree_shardings
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import batch_axes, build_model
from repro.storage import CheckpointManager
from repro.training import (OptimizerConfig, init_state, make_train_step,
                            state_axes)
from repro.training.fault import StragglerMonitor, TrainController


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mesh", choices=["none", "host", "single", "multi"],
                    default="none")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg, attn_impl="naive" if args.smoke else "chunked")
    opt_cfg = OptimizerConfig(learning_rate=args.lr, warmup_steps=10,
                              total_steps=args.steps)
    step_fn = make_train_step(model, opt_cfg, accum_steps=args.accum)

    mesh = None
    if args.mesh == "host":
        n = len(jax.devices())
        mesh = make_host_mesh(max(1, n // 2), min(2, n))
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    rng = jax.random.PRNGKey(0)
    data = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=args.seq,
                                      global_batch=args.batch))

    if mesh is not None:
        rules = rules_for_config(cfg)
        p_shard = tree_shardings(mesh, model.param_axes(), rules)
        o_shard = tree_shardings(mesh, state_axes(model.param_axes()), rules)
        b_shard = tree_shardings(mesh, batch_axes(cfg), rules)
        ctx = axis_rules(rules, mesh=mesh)
        jitted = jax.jit(step_fn, in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None))
    else:
        import contextlib
        ctx = contextlib.nullcontext()
        jitted = jax.jit(step_fn)

    with ctx:
        params = model.init(rng)
        opt = init_state(params, opt_cfg.opt_dtype)
        ckpt = CheckpointManager(Path(args.ckpt_dir) / cfg.arch_id)

        losses = []

        def one_step(state, step):
            params, opt = state
            batch = {k: jax.numpy.asarray(v)
                     for k, v in data.batch(step).items()}
            params, opt, out = jitted(params, opt, batch)
            losses.append(float(out["loss"]))
            if step % args.log_every == 0:
                print(f"step {step}: loss={out['loss']:.4f} "
                      f"gnorm={out['grad_norm']:.3f} lr={out['lr']:.2e}")
            return (params, opt)

        controller = TrainController(one_step, ckpt,
                                     ckpt_every=args.ckpt_every,
                                     monitor=StragglerMonitor())
        t0 = time.time()
        (params, opt), step = controller.run((params, opt), args.steps)
        dt = time.time() - t0
    tokens = args.steps * args.batch * args.seq
    print(f"done: {step} steps in {dt:.1f}s "
          f"({tokens / dt:.0f} tok/s); loss {losses[0]:.3f} -> "
          f"{losses[-1]:.3f}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
