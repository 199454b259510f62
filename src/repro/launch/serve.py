"""Serving launcher: prefill + continuous-batching decode engine.

CPU-scale example (runs here):
  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      --smoke --requests 8 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.device import enable_compile_cache
from repro.models import build_model
from repro.pipeline import OpProfile, choose_batch_size
from repro.training import make_serve_step


class ServingEngine:
    """Batched prefill+decode over a fixed-size slot pool (the serving
    side of the paper's window-function batch inference)."""

    def __init__(self, model, params, *, max_len: int, batch_slots: int):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.slots = batch_slots
        self.serve_step = jax.jit(make_serve_step(model))
        self._prefill = jax.jit(
            lambda p, toks: model.prefill(p, toks, max_len=max_len))

    def generate(self, prompts: np.ndarray, gen_tokens: int) -> np.ndarray:
        """prompts: [B, S] -> generated ids [B, gen_tokens] (greedy)."""
        B = prompts.shape[0]
        outs = []
        for lo in range(0, B, self.slots):
            chunk = prompts[lo:lo + self.slots]
            logits, state = self._prefill(self.params, jnp.asarray(chunk))
            tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
            gen = [tok]
            for _ in range(gen_tokens - 1):
                tok, state = self.serve_step(self.params, state, tok)
                gen.append(tok)
            outs.append(jnp.concatenate(gen, axis=1))
        return np.asarray(jnp.concatenate(outs, axis=0))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encoder_decoder:
        raise SystemExit("use examples/serve_encdec.py for enc-dec archs")
    model = build_model(cfg, attn_impl="naive" if args.smoke else "chunked")
    params = model.init(jax.random.PRNGKey(0))

    # cost-model batch size (Eq. 11) for the decode step
    n = cfg.param_count()
    prof = OpProfile(flops_per_row=2.0 * n, bytes_per_row=cfg.d_model * 2,
                     model_bytes=n * 2)
    slots = choose_batch_size(prof, "tpu", mem_cap_bytes=8e9,
                              candidates=(1, 2, 4, 8, 16, 32))
    print(f"serving {cfg.arch_id}: batch slots={slots} (cost model)")

    engine = ServingEngine(model, params, max_len=args.prompt_len + args.gen,
                           batch_slots=slots)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.requests, args.prompt_len)).astype(np.int32)
    t0 = time.time()
    out = engine.generate(prompts, args.gen)
    dt = time.time() - t0
    total = args.requests * args.gen
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({total / dt:.1f} tok/s); sample: {out[0][:8].tolist()}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
