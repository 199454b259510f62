"""jit'd public wrappers over the Pallas kernels.

``interpret`` defaults to :func:`repro.device.default_interpret`: False on
a TPU, True only where JAX is held to the CPU (the test path), an error
anywhere else. Interpret mode executes the same kernel bodies in Python
for correctness validation.
"""
from __future__ import annotations

from typing import Optional

from repro.device import default_interpret as _default_interpret
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_embed import fused_embed as _embed
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 512,
                    block_k: int = 512,
                    interpret: Optional[bool] = None):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k,
                  interpret=_default_interpret() if interpret is None
                  else interpret)


def decode_attention(q, k_cache, v_cache, length, *, block_k: int = 512,
                     interpret: Optional[bool] = None):
    return _decode(q, k_cache, v_cache, length, block_k=block_k,
                   interpret=_default_interpret() if interpret is None
                   else interpret)


def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: Optional[bool] = None):
    return _rmsnorm(x, w, eps=eps, block_rows=block_rows,
                    interpret=_default_interpret() if interpret is None
                    else interpret)


def fused_embed(x, w, *, mean: float = 0.0, scale: float = 1.0,
                block_rows: int = 256, interpret: Optional[bool] = None):
    return _embed(x, w, mean=mean, scale=scale, block_rows=block_rows,
                  interpret=_default_interpret() if interpret is None
                  else interpret)
