"""Pallas kernel: Mamba2 SSD chunked scan [arXiv:2405.21060].

Grid: (batch, heads, num_chunks) with the chunk dimension sequential
("arbitrary"); the inter-chunk recurrent state (P, N) lives in VMEM
scratch and carries across chunk iterations — the TPU-native analogue of
the paper's chunk-parallel SSD: the within-chunk quadratic term uses the
MXU (Q x Q matmuls), the cross-chunk term is a rank-1-style state update,
and HBM traffic is one pass over x/dt/B/C.

Per-block shapes (Q = chunk length, P = head dim, N = state dim):
  x: (Q, P), dt: (Q, 1), B/C: (Q, N)  ->  y: (Q, P), state scratch (P, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_scr,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Q, 1)
    bm = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)         # (Q, N)
    a_h = a_ref[0, 0]                            # (1, 1) scalar A for head

    xdt = x * dt                                 # dt-weighted input
    a = dt * a_h                                 # (Q, 1) log-decay

    # Inclusive prefix sum of the log-decay, as a row and as a column.
    # Mosaic has no cumsum, so it is a masked (Q, Q) reduction instead:
    # row[j] = sum_{i <= j} a_i, and the column is the row's diagonal.
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    a_cum_row = jnp.sum(jnp.where(li <= lj, a, 0.0), axis=0,
                        keepdims=True)           # (1, Q)
    a_cum = jnp.sum(jnp.where(li == lj, a_cum_row, 0.0), axis=1,
                    keepdims=True)               # (Q, 1)

    # within-chunk decay matrix L[i, j] = exp(acum_i - acum_j), i >= j
    L = jnp.where(li >= lj, jnp.exp(a_cum - a_cum_row), 0.0)

    scores = jnp.dot(cm, bm.T) * L               # (Q, Q) masked CB^T
    y_diag = jnp.dot(scores, xdt)                # (Q, P)

    # carry-in from previous chunks' state
    state = state_scr[...]                        # (P, N)
    y_off = jnp.exp(a_cum) * jnp.dot(cm, state.T)  # (Q, P)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: state' = exp(a_tot) * state + sum_q decay_q B_q (x) xdt_q
    a_tot = jnp.sum(a, axis=0, keepdims=True)     # (1, 1)
    decay_to_end = jnp.exp(a_tot - a_cum)         # (Q, 1)
    new_contrib = jnp.dot((xdt * decay_to_end).T, bm)  # (P, N)
    state_scr[...] = state * jnp.exp(a_tot) + new_contrib


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jnp.ndarray,        # (B, S, H, P)
    dt: jnp.ndarray,       # (B, S, H) positive
    A: jnp.ndarray,        # (H,) negative
    Bm: jnp.ndarray,       # (B, S, G, N)
    Cm: jnp.ndarray,       # (B, S, G, N)
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y (B, S, H, P).  Heads map to their B/C group (H % G)."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    assert s % chunk == 0
    nc = s // chunk
    rep = h // g

    # layouts: (B, H, S, *) so heads are a parallel grid dim
    xt = jnp.moveaxis(x, 1, 2)                       # (B, H, S, P)
    dtt = jnp.moveaxis(dt, 1, 2)[..., None]          # (B, H, S, 1)
    bt = jnp.moveaxis(Bm, 1, 2)                      # (B, G, S, N)
    ct = jnp.moveaxis(Cm, 1, 2)
    a2 = A[None, :, None, None]                      # (1, H, 1, 1)
    a2 = jnp.broadcast_to(a2, (b, h, 1, 1))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, r=rep: (bi, hi // r, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, r=rep: (bi, hi // r, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a2, xt, dtt, bt, ct)
    return jnp.moveaxis(out, 1, 2)
