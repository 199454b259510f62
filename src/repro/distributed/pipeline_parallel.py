"""GPipe-style pipeline parallelism over a mesh axis (the `pod` axis of
the multi-pod mesh: 2 stages x 256-chip pods, cutting cross-pod traffic
to one activation transfer per microbatch tick).

Collective pipelining under `shard_map`: each stage rank owns L/S layer
groups; microbatches ripple through a ppermute ring for M + S - 1 ticks.
Differentiable end-to-end (ppermute transposes to the reverse permute, so
the backward schedule falls out of autodiff), so the same runner serves
training.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_apply(stage_fn: Callable, stage_params, microbatches: jax.Array,
                *, axis: str) -> jax.Array:
    """Run inside shard_map. stage_fn(params, x) -> y applies this rank's
    layer group. microbatches: [M, mb, ...] (replicated across stages).
    Returns [M, mb, ...] outputs of the final stage (replicated).
    """
    S = jax.lax.axis_size(axis)
    sid = jax.lax.axis_index(axis)
    M = microbatches.shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]
    zero = jnp.zeros_like(microbatches[0])

    def tick(carry, t):
        buf_in, outputs = carry
        # stage 0 injects microbatch t (clamped; masked later)
        x0 = microbatches[jnp.clip(t, 0, M - 1)]
        x = jnp.where(sid == 0, x0, buf_in)
        y = stage_fn(stage_params, x)
        buf_next = jax.lax.ppermute(y, axis, perm)
        # final stage emits microbatch t-(S-1) at tick t
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        is_out = jnp.logical_and(sid == S - 1, t >= S - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(is_out, y, outputs[out_idx]), out_idx, 0)
        return (buf_next, outputs), None

    outputs0 = jnp.zeros_like(microbatches)
    (_, outputs), _ = jax.lax.scan(tick, (zero, outputs0),
                                   jnp.arange(T))
    # replicate the final-stage outputs to every rank
    return jax.lax.psum(jnp.where(sid == S - 1, outputs, 0.0), axis)


def make_pipelined_fn(stage_fn: Callable, mesh: Mesh, *, axis: str = "pod",
                      params_spec=None) -> Callable:
    """Wrap stage_fn into a jit-able pipelined forward.

    params are sharded over ``axis`` on their leading (stage) dim;
    microbatches are replicated. Returns f(stage_params, microbatches).
    """
    pspec = params_spec if params_spec is not None else P(axis)

    def fn(stage_params, microbatches):
        def inner(p, mb):
            # leading stage dim is 1 per rank -> squeeze
            local = jax.tree.map(lambda a: a[0], p)
            return gpipe_apply(lambda pp, x: stage_fn(pp, x), local, mb,
                               axis=axis)
        from repro.distributed.sharding import shard_map
        return shard_map(
            inner, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: pspec, stage_params), P()),
            out_specs=P())(stage_params, microbatches)

    return fn


def pipeline_bubble_fraction(num_micro: int, num_stages: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (num_stages - 1) / (num_micro + num_stages - 1)
