"""Mixture-of-Experts layer: top-k routing with two implementations.

``dense``  — reference: every expert computes every token, gated combine.
             O(E x) FLOPs; used by smoke tests and as the allclose oracle.
``ragged`` — production: sort token-copies by expert, grouped matmul via
             ``jax.lax.ragged_dot`` with a capacity bound. Runs single-device
             or expert-parallel (EP) under ``shard_map`` where each model-rank
             owns E/ep experts, computes only copies routed to them, and the
             combine is a psum over the EP axis. Expert weights are
             FSDP-sharded on d_model and all-gathered per layer (transient).

Both return ``(y, aux_loss)`` where aux is the switch-style load-balance
loss: E * sum_e(frac_tokens_e * mean_prob_e).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.spec import P


def moe_specs(cfg) -> dict:
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    return {
        "router": P((d, m.num_experts), ("embed", None), init="small"),
        "wi": P((m.num_experts, d, f), ("experts", "expert_embed", "expert_mlp"),
                fan_in=d),
        "wg": P((m.num_experts, d, f), ("experts", "expert_embed", "expert_mlp"),
                fan_in=d),
        "wo": P((m.num_experts, f, d), ("experts", "expert_mlp", "expert_embed"),
                fan_in=f),
    }


def _act(cfg):
    return jax.nn.gelu if cfg.activation == "geglu" else jax.nn.silu


@jax.custom_vjp
def bf16_grad(x):
    """Identity with a bf16 cotangent: halves the FSDP reduce-scatter of
    expert-weight gradients (error well below optimizer noise; §Perf)."""
    return x


def _bf16_grad_fwd(x):
    return x, None


def _bf16_grad_bwd(_, g):
    return (g.astype(jnp.bfloat16).astype(g.dtype),)


bf16_grad.defvjp(_bf16_grad_fwd, _bf16_grad_bwd)

# §Perf knob: bf16 collectives for the MoE block (EP combine psum and
# FSDP grad reduce-scatter). Toggled by the dry-run hillclimb variants.
_BF16_COLLECTIVES = False


def set_moe_bf16_collectives(flag: bool) -> None:
    global _BF16_COLLECTIVES
    _BF16_COLLECTIVES = flag


def _route(cfg, router_w, x2d, dp_axis=None):
    """x2d: [T, D] -> (probs [T,E] f32, gate [T,k], idx [T,k], aux)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # switch-style load-balance aux: E * sum_e f_e * p_e, where f_e is the
    # (stop-grad) fraction of routed assignments and p_e the mean router
    # prob. Under data-parallel shard_map both means are pmean'd over the
    # dp axis so the aux matches the global-batch value exactly.
    E = m.num_experts
    hard = jnp.zeros((x2d.shape[0], E), jnp.float32)
    hard = hard.at[jnp.arange(x2d.shape[0])[:, None], idx].set(1.0)
    frac = jax.lax.stop_gradient(hard.mean(0) / m.top_k)
    pbar = probs.mean(0)
    if dp_axis is not None:
        frac = jax.lax.pmean(frac, dp_axis)
        pbar = jax.lax.pmean(pbar, dp_axis)
    aux = E * jnp.sum(frac * pbar)
    return probs, gate, idx, aux


def moe_dense(cfg, p: dict, x: jax.Array):
    """Reference: [.., D] -> all-experts dense compute, gated combine."""
    m = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    _, gate, idx, aux = _route(cfg, p["router"], x2)
    act = _act(cfg)
    h = jnp.einsum("td,edf->etf", x2, p["wi"].astype(dt))
    g = jnp.einsum("td,edf->etf", x2, p["wg"].astype(dt))
    h = act(g.astype(jnp.float32)).astype(dt) * h
    y_e = jnp.einsum("etf,efd->etd", h, p["wo"].astype(dt))  # [E,T,D]
    T = x2.shape[0]
    comb = jnp.zeros((T, m.num_experts), dt)
    comb = comb.at[jnp.arange(T)[:, None], idx].add(gate.astype(dt))
    y = jnp.einsum("etd,te->td", y_e, comb)
    return y.reshape(shape), aux


def _capacity(tokens_times_k: int, shards: int, cf: float) -> int:
    cap = int(math.ceil(tokens_times_k / shards * cf))
    return max(8, -(-cap // 8) * 8)  # round up to multiple of 8


def moe_ragged_local(cfg, p: dict, x: jax.Array, *,
                     ep_axis: Optional[str] = None,
                     fsdp_axis=None, dp_axis=None):
    """Sort + ragged_dot MoE. Call directly (single device) or inside
    shard_map with ``ep_axis`` = the expert-parallel mesh axis name."""
    m = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    T = x2.shape[0]
    k = m.top_k

    wi, wg, wo = p["wi"], p["wg"], p["wo"]
    if fsdp_axis is not None:  # FSDP all-gather of expert weights (transient)
        wi = jax.lax.all_gather(wi, fsdp_axis, axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, fsdp_axis, axis=2, tiled=True)
        if _BF16_COLLECTIVES:
            # bf16 cotangents -> the grad reduce-scatter (the transpose of
            # these gathers) moves half the bytes
            wi, wg, wo = bf16_grad(wi), bf16_grad(wg), bf16_grad(wo)

    _, gate, idx, aux = _route(cfg, p["router"], x2, dp_axis=dp_axis)

    E_local = wi.shape[0]
    ep = 1
    if ep_axis is not None:
        ep = jax.lax.axis_size(ep_axis)
        rank = jax.lax.axis_index(ep_axis)
        local_id = idx - rank * E_local
    else:
        local_id = idx
    own = (local_id >= 0) & (local_id < E_local)

    flat_id = jnp.where(own, local_id, E_local).reshape(-1)        # [T*k]
    flat_gate = jnp.where(own, gate, 0.0).reshape(-1)
    order = jnp.argsort(flat_id)                                    # stable
    cap = _capacity(T * k, ep, m.capacity_factor)
    cap = min(cap, T * k)
    sel = order[:cap]                                               # kept copies
    tok = sel // k                                                  # token of copy
    xs = x2[tok]                                                    # [cap, D]

    counts = jnp.bincount(flat_id, length=E_local + 1)[:E_local]
    cum = jnp.cumsum(counts)
    cum_cl = jnp.minimum(cum, cap)
    gs = jnp.concatenate([cum_cl[:1], jnp.diff(cum_cl)]).astype(jnp.int32)

    act = _act(cfg)
    h = jax.lax.ragged_dot(xs, wi.astype(dt), gs)
    g = jax.lax.ragged_dot(xs, wg.astype(dt), gs)
    h = act(g.astype(jnp.float32)).astype(dt) * h
    y_cp = jax.lax.ragged_dot(h, wo.astype(dt), gs)                 # [cap, D]

    w_cp = flat_gate[sel] * (jnp.arange(cap) < cum_cl[-1])          # drop overflow
    y = jnp.zeros((T, shape[-1]), jnp.float32)
    y = y.at[tok].add(y_cp.astype(jnp.float32) * w_cp[:, None])
    if ep_axis is not None:
        if _BF16_COLLECTIVES:  # EP combine in bf16: half the ICI bytes
            y = jax.lax.psum(y.astype(dt), ep_axis).astype(jnp.float32)
        else:
            y = jax.lax.psum(y, ep_axis)
    return y.astype(dt).reshape(shape), aux


def moe_batched_local(cfg, p: dict, x: jax.Array, *,
                      ep_axis: Optional[str] = None,
                      fsdp_axis=None, dp_axis=None):
    """Fixed per-expert capacity MoE via gather + batched matmul.

    The production TPU path (§Perf iteration on kimi-k2): sorted token
    copies are scattered into a dense [E_local, cap_e, D] buffer and each
    expert runs one MXU-friendly batched dot — no ragged/grouped kernel
    needed, and (unlike ragged_dot's CPU decomposition) no [E, T, D]
    expansion anywhere. Tokens beyond a per-expert capacity drop (classic
    Switch semantics, capacity_factor-controlled).
    """
    m = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    T, D = x2.shape
    k = m.top_k

    wi, wg, wo = p["wi"], p["wg"], p["wo"]
    if fsdp_axis is not None:
        wi = jax.lax.all_gather(wi, fsdp_axis, axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, fsdp_axis, axis=2, tiled=True)
        if _BF16_COLLECTIVES:
            wi, wg, wo = bf16_grad(wi), bf16_grad(wg), bf16_grad(wo)

    _, gate, idx, aux = _route(cfg, p["router"], x2, dp_axis=dp_axis)

    E_local = wi.shape[0]
    ep = 1
    if ep_axis is not None:
        ep = jax.lax.axis_size(ep_axis)
        rank = jax.lax.axis_index(ep_axis)
        local_id = idx - rank * E_local
    else:
        local_id = idx
    own = (local_id >= 0) & (local_id < E_local)

    # slot-level gather: each of the E_local*cap_e expert slots pulls its
    # token row directly (never materializing all T*k copies — 12.6x less
    # gather traffic at top-8 with 1.25x capacity; §Perf kimi iteration 2)
    cap_e = _capacity(T * k, ep * E_local, m.capacity_factor)
    flat_id = jnp.where(own, local_id, E_local).reshape(-1)       # [T*k]
    flat_gate = jnp.where(own, gate, 0.0).reshape(-1)
    order = jnp.argsort(flat_id)                                   # stable
    counts = jnp.bincount(flat_id, length=E_local + 1)[:E_local]
    starts = jnp.concatenate(
        [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)])[:-1]
    n_slots = E_local * cap_e
    e_idx = jnp.arange(n_slots) // cap_e
    pos = jnp.arange(n_slots) % cap_e
    valid = pos < counts[e_idx]
    src = jnp.where(valid, starts[e_idx] + pos, 0)
    copy_idx = order[src]                                          # [slots]
    tok_slot = jnp.where(valid, copy_idx // k, T)                  # T = pad
    gate_slot = jnp.where(valid, flat_gate[copy_idx], 0.0)

    x2p = jnp.concatenate([x2.astype(dt), jnp.zeros((1, D), dt)], axis=0)
    xs = x2p[tok_slot].reshape(E_local, cap_e, D)

    act = _act(cfg)
    h = jnp.einsum("ecd,edf->ecf", xs, wi.astype(dt))
    g = jnp.einsum("ecd,edf->ecf", xs, wg.astype(dt))
    h = act(g.astype(jnp.float32)).astype(dt) * h
    y_e = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))             # [E,cap,D]

    y = jnp.zeros((T + 1, D), jnp.float32)
    y = y.at[tok_slot].add(
        y_e.reshape(-1, D).astype(jnp.float32)
        * gate_slot[:, None].astype(jnp.float32))[:T]
    if ep_axis is not None:
        if _BF16_COLLECTIVES:
            y = jax.lax.psum(y.astype(dt), ep_axis).astype(jnp.float32)
        else:
            y = jax.lax.psum(y, ep_axis)
    return y.astype(dt).reshape(shape), aux


_LOCAL_IMPLS = {"ragged": moe_ragged_local, "batched": moe_batched_local}


def moe_apply(cfg, p: dict, x: jax.Array, *, mesh=None, ep_axis: str = "model",
              fsdp_axes=None):
    """Dispatch on impl + mesh. x: [B, S, D] (replicated over 'model')."""
    local = _LOCAL_IMPLS.get(cfg.moe.impl, moe_ragged_local)
    if cfg.moe.impl == "dense" or mesh is None or ep_axis not in mesh.axis_names:
        if cfg.moe.impl == "dense":
            return moe_dense(cfg, p, x)
        return local(cfg, p, x)

    from jax.sharding import PartitionSpec as PS
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fsdp = dp if fsdp_axes is None else fsdp_axes
    x_spec = PS(dp, None, None)
    p_specs = {
        "router": PS(None, None),
        "wi": PS(ep_axis, fsdp, None),
        "wg": PS(ep_axis, fsdp, None),
        "wo": PS(ep_axis, None, fsdp),
    }

    def inner(xl, pl):
        return local(cfg, pl, xl, ep_axis=ep_axis,
                     fsdp_axis=fsdp, dp_axis=dp)

    from repro.distributed.sharding import shard_map
    y, aux = shard_map(
        inner, mesh=mesh, in_specs=(x_spec, p_specs),
        out_specs=(x_spec, PS()))(x, p)
    return y, aux
