"""jit'd wrapper for the flash-attention kernel (Mosaic on TPU, interpret
mode on CPU); the model layer calls this when attn_impl="pallas"."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import flash as _flash
from repro.kernels import interpret_mode


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
    logit_soft_cap: Optional[float] = None,
) -> jnp.ndarray:
    s = q.shape[1]
    block = 128 if s % 128 == 0 else (64 if s % 64 == 0 else None)
    if block is None:
        raise ValueError(
            f"flash attention needs a sequence length divisible by 64, "
            f"got {s}; use attn_impl='chunked' for ragged lengths"
        )
    return _flash.flash_attention(
        q, k, v, causal=causal, window=window,
        logit_soft_cap=logit_soft_cap,
        block_q=block, block_k=block,
        interpret=interpret_mode(),
    )
