"""Pallas kernel: weighted model aggregation (FedLEO eqs. 4/9).

Computes out[n] = sum_k w[k] * x[k, n] for K stacked flattened parameter
vectors.  This is the FL server's hot-spot: for a 123B-parameter model
with K=5 orbit partials a single aggregation streams ~2.5 TB through
HBM, so it is purely memory-bound and the kernel's job is to tile the
stream through VMEM at full bandwidth with the accumulation in fp32.

TPU adaptation: block shape (K, BLOCK_N) with BLOCK_N a multiple of the
128-lane register width; K (the client axis) stays resident so each HBM
byte of x is touched exactly once.  Weights live in SMEM (scalar
prefetch) — they are K scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 16_384   # 16k lanes * K rows of fp32 comfortably < VMEM


def _aggregate_kernel(w_ref, x_ref, o_ref):
    """w: (K, 1) VMEM; x: (K, BLOCK_N) VMEM; o: (BLOCK_N,) VMEM."""
    x = x_ref[...].astype(jnp.float32)          # (K, BN)
    w = w_ref[...].astype(jnp.float32)          # (K, 1)
    o_ref[...] = jnp.sum(x * w, axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def aggregate_flat(
    x: jnp.ndarray,        # (K, N) stacked flattened params
    w: jnp.ndarray,        # (K,) normalized weights
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """Weighted sum over the leading axis; returns (N,)."""
    k, n = x.shape
    # Mosaic tiles the (BLOCK_N,) output over 128 lanes times the dtype's
    # sublane packing (1 row per 32-bit word for f32, 2 for bf16); a
    # block that ends mid-tile is refused, so a small N is padded to
    # whole tiles rather than made one ragged block.
    tile = 128 * max(1, 4 // x.dtype.itemsize)
    block_n = min(block_n, -(-n // tile) * tile)
    n_pad = (-n) % block_n
    if n_pad:
        x = jnp.pad(x, ((0, 0), (0, n_pad)))
    n_total = n + n_pad
    grid = (n_total // block_n,)

    out = pl.pallas_call(
        _aggregate_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, 1), lambda i: (0, 0)),          # weights
            pl.BlockSpec((k, block_n), lambda i: (0, i)),    # param stream
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_total,), x.dtype),
        interpret=interpret,
    )(w[:, None], x)
    return out[:n]
