"""Serving example: batched greedy decoding for a reduced zoo LM through
the cost-model-sized serving engine, plus an API-registered remote model
participating in the same pipeline (paper §3.1 API-based storage).

Run:  PYTHONPATH=src python examples/serve_pipeline.py
"""
import time

import jax
import numpy as np

from repro.configs import smoke_config
from repro.device import enable_compile_cache
from repro.launch.serve import ServingEngine
from repro.models import build_model
from repro.pipeline import OpProfile, choose_batch_size
from repro.storage import ApiModelRegistry


def main() -> None:
    cfg = smoke_config("h2o-danube-1.8b")
    model = build_model(cfg, attn_impl="naive")
    params = model.init(jax.random.PRNGKey(0))

    n = cfg.param_count()
    prof = OpProfile(flops_per_row=2.0 * n, bytes_per_row=cfg.d_model * 2,
                     model_bytes=n * 2)
    slots = choose_batch_size(prof, "tpu", mem_cap_bytes=4e9,
                              candidates=(1, 2, 4, 8, 16))
    engine = ServingEngine(model, params, max_len=64, batch_slots=slots)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    t0 = time.time()
    out = engine.generate(prompts, gen_tokens=16)
    dt = time.time() - t0
    print(f"local zoo model: {out.shape[0] * out.shape[1]} tokens "
          f"in {dt:.2f}s (batch slots={slots}, SWA window="
          f"{cfg.sliding_window})")

    # remote API model registered as a logical operator with retry+cache
    api = ApiModelRegistry()
    api.register("frontier-llm", lambda toks: np.asarray(toks)[..., ::-1],
                 latency_s=0.02, failure_rate=0.3, max_retries=5)
    res = api.invoke("frontier-llm", prompts[:2], np.random.default_rng(1))
    st = api.stats["frontier-llm"]
    print(f"api model: calls={st['calls']} retries={st['retries']} "
          f"-> result {res.shape} (failures retried transparently)")
    res2 = api.invoke("frontier-llm", prompts[:2], np.random.default_rng(2))
    print(f"api cache hits: {api.stats['frontier-llm']['cache_hits']}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
