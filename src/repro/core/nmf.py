"""Non-negative matrix factorization (paper §4.2, Eq. 2), in JAX.

Decomposes the historical transfer-performance matrix V [M models x N
tasks] into W [M x k] (model embeddings) and H [N x k] (task embeddings)
with multiplicative updates minimizing ||V - W H^T||_F^2 s.t. W,H >= 0.

Supports masked factorization (missing entries in V — not every model was
evaluated on every historical task) by weighting the objective.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

_EPS = 1e-9


class NMFResult(NamedTuple):
    W: jax.Array          # [M, k] model embeddings
    H: jax.Array          # [N, k] task embeddings
    loss_curve: jax.Array


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def nmf(V: jax.Array, k: int, *, iters: int = 300,
        mask: Optional[jax.Array] = None,
        seed: int = 0) -> NMFResult:
    M, N = V.shape
    rng = jax.random.PRNGKey(seed)
    r1, r2 = jax.random.split(rng)
    scale = jnp.sqrt(jnp.maximum(V.mean(), _EPS) / k)
    W = jax.random.uniform(r1, (M, k), jnp.float32, 0.1, 1.0) * scale
    H = jax.random.uniform(r2, (N, k), jnp.float32, 0.1, 1.0) * scale
    Vm = V if mask is None else V * mask

    def step(carry, _):
        W, H = carry
        WH = W @ H.T
        WHm = WH if mask is None else WH * mask
        # H <- H * (V^T W) / (WH^T W)
        H_new = H * (Vm.T @ W) / (WHm.T @ W + _EPS)
        WH = W @ H_new.T
        WHm = WH if mask is None else WH * mask
        W_new = W * (Vm @ H_new) / (WHm @ H_new + _EPS)
        resid = Vm - (W_new @ H_new.T if mask is None
                      else (W_new @ H_new.T) * mask)
        loss = jnp.sum(resid * resid)
        return (W_new, H_new), loss

    # float32 matmuls on every platform: at the TPU's default precision
    # (one bf16 pass) the factors drift far enough from the CPU's to
    # change which model the selector picks for a task
    with jax.default_matmul_precision("float32"):
        (W, H), losses = jax.lax.scan(step, (W, H), None, length=iters)
    return NMFResult(W, H, losses)


def reconstruction_error(V, W, H, mask=None) -> float:
    R = V - W @ H.T
    if mask is not None:
        R = R * mask
        denom = jnp.maximum(jnp.sum(mask * V * V), _EPS)
    else:
        denom = jnp.maximum(jnp.sum(V * V), _EPS)
    return float(jnp.sum(R * R) / denom)
