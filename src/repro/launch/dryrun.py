import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes, record memory/cost/collective analyses as JSON.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.analysis.hlo import model_flops, roofline_terms
from repro.analysis.hlo_cost import analyze_hlo
from repro.analysis.jaxpr_flops import count_flops
from repro.configs import SHAPES, get_config, list_archs, shapes_for
from repro.device import enable_compile_cache
from repro.distributed.sharding import (axis_rules, rules_for_config,
                                        tree_shardings)
from repro.launch.mesh import dp_size, make_production_mesh
from repro.models import batch_axes, build_model, input_specs
from repro.training import (OptimizerConfig, abstract_state,
                            make_prefill_step, make_serve_step,
                            make_train_step, state_axes)

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_BF16_OPT = {"llama3-405b", "kimi-k2-1t-a32b"}  # bf16 moments (HBM budget)


def _rule_overrides(cfg, shape, mesh):
    """Shape-aware rule tweaks (see DESIGN.md §6 and EXPERIMENTS.md §Perf).

    Decode shards the KV cache length over 'model' (flash-decode style);
    per-token q-head compute is tiny, so heads are replicated — sharding
    both would force an all-gather of the cache over 'model'.
    """
    ov = {}
    if shape.kind in ("train", "prefill") and cfg.seq_parallel:
        ov["residual_seq"] = ("model",)
    if shape.kind == "decode":
        ov["act_heads"] = None
        ov["act_kv_heads"] = None
        dp = dp_size(mesh)
        if shape.global_batch % dp != 0:  # long_500k: batch 1
            ov["batch"] = None
            ov["cache_seq"] = ("data", "model")
        else:
            ov["cache_seq"] = ("model",)
    return ov


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               opt_overrides=None, variant: str = "opt",
               rule_extra=None, cfg_overrides=None):
    """Build + lower + compile one cell; returns (record, compiled).

    variant='baseline' reproduces the paper-faithful naive implementation
    (f32-upcast decode, replicated KV length) for §Perf before/after.
    """
    from repro.models.attention import set_decode_f32_upcast
    from repro.models.moe import set_moe_bf16_collectives
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    tags = set(variant.split("+"))
    if "baseline" in tags:
        set_decode_f32_upcast(True)
        set_moe_bf16_collectives(False)
        overrides = {}  # naive: cache replicated over 'model'
    else:
        set_decode_f32_upcast(False)
        set_moe_bf16_collectives("bf16coll" in tags)
        overrides = _rule_overrides(cfg, shape, mesh)
        if "sp" in tags:  # sequence-parallel residual stream
            overrides["residual_seq"] = ("model",)
    if rule_extra:
        overrides.update(rule_extra)
    rules = rules_for_config(cfg, multi_pod=multi_pod, overrides=overrides)
    model = build_model(cfg)
    opt_cfg = OptimizerConfig(
        opt_dtype="bfloat16" if arch in _BF16_OPT else "float32")
    if opt_overrides:
        import dataclasses
        opt_cfg = dataclasses.replace(opt_cfg, **opt_overrides)

    aparams = model.abstract()
    p_shard = tree_shardings(mesh, model.param_axes(), rules)
    b_specs = input_specs(cfg, shape)
    b_shard = tree_shardings(mesh, batch_axes(cfg), rules)

    with axis_rules(rules, mesh=mesh):
        if shape.kind == "train":
            accum = min(cfg.grad_accum, max(1, shape.global_batch // dp_size(mesh)))
            step = make_train_step(model, opt_cfg, accum_steps=accum)
            aopt = abstract_state(aparams, opt_cfg.opt_dtype)
            o_shard = tree_shardings(mesh, state_axes(model.param_axes()),
                                     rules)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None))
            step_args = (aparams, aopt, b_specs)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                             out_shardings=None)
            step_args = (aparams, b_specs)
        else:  # decode
            step = make_serve_step(model)
            B = shape.global_batch
            acache = jax.eval_shape(
                lambda: model.init_cache(B, shape.seq_len))
            c_shard = tree_shardings(mesh, model.cache_axes(), rules)
            tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            t_shard = tree_shardings(mesh, {"t": ("batch", None)}, rules)["t"]
            jitted = jax.jit(step,
                             in_shardings=(p_shard, c_shard, t_shard),
                             out_shardings=(None, c_shard))
            step_args = (aparams, acache, tok)

        lowered = jitted.lower(*step_args)
        # exact GLOBAL matmul FLOPs from the jaxpr (scan x length,
        # ragged_dot = 2mkn, shard_map body x mesh size)
        jaxpr_flops = count_flops(jax.make_jaxpr(step)(*step_args))
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):     # jax 0.4.x: list of one dict
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    mem_d = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        mem_d[f] = getattr(mem, f, None)
    hlo = compiled.as_text()
    hc = analyze_hlo(hlo)
    flops_pd = jaxpr_flops / chips
    bytes_pd = hc.bytes_accessed
    terms = roofline_terms(flops_pd, bytes_pd, hc.collective_operand_bytes)
    mf = model_flops(cfg, shape, per_device=True, chips=chips)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips,
        "compile_s": compile_s,
        "flops_per_device": flops_pd,
        "hlo_dot_flops_per_device": hc.dot_flops,
        "xla_cost_flops_loop_once": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_device": bytes_pd,
        "xla_bytes_loop_once": float(cost.get("bytes accessed", 0.0)),
        "collectives": hc.to_dict(),
        "memory_analysis": mem_d,
        "roofline": terms,
        "model_flops_per_device": mf,
        "useful_flops_ratio": (mf / flops_pd) if flops_pd else None,
        "hlo_bytes": len(hlo),
        "loop_trip_counts": hc.loop_trip_counts[:32],
    }
    return rec, compiled


def run_cell(arch, shape_name, multi_pod, out_dir: Path, tag: str = ""):
    key = f"{arch}/{shape_name}/{'multi' if multi_pod else 'single'}"
    out = out_dir / ("multi" if multi_pod else "single") / arch
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{shape_name}{tag}.json"
    try:
        rec, compiled = lower_cell(arch, shape_name, multi_pod)
        print(compiled.memory_analysis())
        ca = compiled.cost_analysis()
        print({k: ca[k] for k in sorted(ca)[:6]} if ca else None)
        path.write_text(json.dumps(rec, indent=1))
        r = rec["roofline"]
        print(f"OK  {key}: compute={r['compute_s']:.4f}s "
              f"memory={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s "
              f"dominant={r['dominant']} "
              f"useful={rec['useful_flops_ratio'] and rec['useful_flops_ratio']:.3f} "
              f"(compile {rec['compile_s']:.0f}s)")
        return True
    except Exception as e:
        traceback.print_exc()
        path.with_suffix(".err").write_text(
            f"{type(e).__name__}: {e}\n{traceback.format_exc()}")
        print(f"FAIL {key}: {type(e).__name__}: {e}")
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(ARTIFACTS))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    out_dir = Path(args.out)

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = []
    archs = [args.arch] if args.arch else list_archs()
    for a in archs:
        cfg = get_config(a)
        shapes = ([args.shape] if args.shape
                  else [s.name for s in shapes_for(cfg)])
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    ok = fail = skip = 0
    for a, s, mp in cells:
        p = (out_dir / ("multi" if mp else "single") / a / f"{s}.json")
        if args.skip_existing and p.exists():
            skip += 1
            continue
        if run_cell(a, s, mp, out_dir):
            ok += 1
        else:
            fail += 1
    print(f"done: ok={ok} fail={fail} skipped={skip}")
    return 0 if fail == 0 else 1


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
