"""Pallas fused pre-embedding: normalize + project + tanh in one kernel.

The TPU adaptation of MorphingDB's SIMD vectorized pre-embedding (§5.1):
the paper normalizes pixels/token vectors with SIMD registers before a
projection; here the normalization is fused into the MXU matmul's operand
load so the raw rows are read from HBM exactly once. Projection weights
live in VMEM across the whole grid (D x K <= 16k x 512 bf16 = 16 MB cap;
typical embedders are far smaller).

The projection runs at ``Precision.HIGHEST``: float32 operands stay
float32 on the MXU (no single bf16 pass), so the kernel agrees with the
float32 numpy oracle on the chip as it does in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, *, mean: float, scale: float):
    x = (x_ref[...].astype(jnp.float32) - mean) * scale
    z = jnp.dot(x, w_ref[...].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
    o_ref[...] = jnp.tanh(z).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mean", "scale", "block_rows",
                                              "interpret"))
def fused_embed(x, w, *, mean: float = 0.0, scale: float = 1.0,
                block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: [N, D]; w: [D, K] -> tanh(((x-mean)*scale) @ w) [N, K].

    Any N is accepted: ragged row counts (the final chunk of a table not
    divisible by the block size) are zero-padded up to a whole number of
    blocks and the padding is sliced off the result.
    """
    N, D = x.shape
    K = w.shape[1]
    if N == 0:
        return jnp.zeros((0, K), x.dtype)
    br = min(block_rows, N)
    pad = (-N) % br
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    np_rows = N + pad
    out = pl.pallas_call(
        functools.partial(_kernel, mean=mean, scale=scale),
        grid=(np_rows // br,),
        in_specs=[pl.BlockSpec((br, D), lambda i: (i, 0)),
                  pl.BlockSpec((D, K), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_rows, K), x.dtype),
        interpret=interpret,
    )(xp, w)
    return out[:N] if pad else out
