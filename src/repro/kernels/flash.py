"""Pallas kernel: GQA flash attention (causal / sliding window).

Online-softmax attention tiled for the TPU memory hierarchy: Q blocks of
(BLOCK_Q, head_dim) stay VMEM-resident while K/V stream in blocks of
(BLOCK_K, head_dim); the running max/denominator live in VMEM scratch and
persist across the sequential kv-block grid dimension (dimension
semantics: batch/head/q-block parallel, kv-block arbitrary).

GQA is handled in the index maps: q-head h reads kv-head h // q_per_kv —
no KV replication is ever materialized in VMEM.

The sliding-window mask makes this the sub-quadratic attention used by
the long_500k shape: kv blocks wholly outside [q - window, q] are
skipped via a mask (structurally zero blocks still stream; see §Perf for
the block-skip iteration).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, num_kv_blocks: int,
    soft_cap: Optional[float],
):
    qi = pl.program_id(2)          # q-block index
    ki = pl.program_id(3)          # kv-block index (sequential)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)         # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)         # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)         # (BK, D)

    s = jnp.dot(q, k.T) * scale                 # (BQ, BK)
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                          # (BQ, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                       # (BQ, BK)
    correction = jnp.exp(m_prev - m_new)         # (BQ, 1)
    l_new = correction * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * correction + jnp.dot(p, v)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "logit_soft_cap", "block_q", "block_k",
        "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,        # (B, S, H, D)
    k: jnp.ndarray,        # (B, S, G, D)
    v: jnp.ndarray,        # (B, S, G, D)
    causal: bool = True,
    window: Optional[int] = None,
    logit_soft_cap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, h, d = q.shape
    g = k.shape[2]
    assert h % g == 0
    q_per_kv = h // g
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    num_q_blocks = s // block_q
    num_kv_blocks = s // block_k
    scale = 1.0 / math.sqrt(d)

    # layout: (B, H, S, D) blocks
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kv_blocks=num_kv_blocks,
        soft_cap=logit_soft_cap,
    )

    out = pl.pallas_call(
        kernel,
        grid=(b, h, num_q_blocks, num_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, qpk=q_per_kv:
                         (bi, hi // qpk, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, qpk=q_per_kv:
                         (bi, hi // qpk, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)               # back to (B, S, H, D)
