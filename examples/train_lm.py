"""End-to-end training driver: a ~15M-param gemma-family LM trained for a
few hundred steps on the synthetic Markov corpus, with async checkpointing,
a simulated mid-run preemption (restart from checkpoint), and loss curve.

Run:  PYTHONPATH=src python examples/train_lm.py  (~2-4 min on CPU)
"""
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.data import DataConfig, SyntheticCorpus
from repro.device import enable_compile_cache
from repro.models import build_model
from repro.storage import CheckpointManager
from repro.training import OptimizerConfig, init_state, make_train_step
from repro.training.fault import TrainController


def main(steps: int = 250) -> None:
    cfg = smoke_config("gemma-2b").replace(
        num_layers=4, d_model=256, d_ff=512, vocab_size=512,
        num_heads=4, head_dim=64)
    print(f"arch={cfg.arch_id}(reduced) params="
          f"{cfg.param_count() / 1e6:.1f}M")
    model = build_model(cfg, attn_impl="naive")
    opt_cfg = OptimizerConfig(learning_rate=3e-3, warmup_steps=20,
                              total_steps=steps, weight_decay=0.01)
    step_fn = jax.jit(make_train_step(model, opt_cfg))
    data = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=128, global_batch=8, seed=3,
                                      branching=4))

    params = model.init(jax.random.PRNGKey(0))
    opt = init_state(params)
    losses = []
    fail_once = {steps // 2}

    def one_step(state, step):
        if step in fail_once:          # simulated preemption mid-run
            fail_once.clear()
            raise RuntimeError("simulated host preemption")
        p, o = state
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        p, o, out = step_fn(p, o, batch)
        losses.append((step, float(out["loss"])))
        if step % 25 == 0:
            print(f"  step {step:4d}: loss {out['loss']:.4f}")
        return (p, o)

    with tempfile.TemporaryDirectory() as td:
        ckpt = CheckpointManager(Path(td) / "ck")
        tc = TrainController(one_step, ckpt, ckpt_every=50)
        t0 = time.time()
        state, step = tc.run((params, opt), steps)
        dt = time.time() - t0
    first = losses[0][1]
    last = losses[-1][1]
    events = [k for k, _ in tc.events]
    print(f"{step} steps in {dt:.0f}s; loss {first:.3f} -> {last:.3f} "
          f"(drop {first - last:.3f}); events: "
          f"failures={events.count('failure')} "
          f"restarts={events.count('restart')} "
          f"checkpoints={events.count('checkpoint')}")
    assert last < first - 0.5, "model must learn the bigram structure"


if __name__ == "__main__":
    enable_compile_cache()
    main()
