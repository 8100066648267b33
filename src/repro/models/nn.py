"""Minimal functional NN building blocks (no flax in this container).

Every module is a pair of pure functions:

    params = init_*(rng, ...)
    out    = apply_*(params, x, ...)

Parameters are plain dict pytrees so the FL aggregation layer (weighted
sums over pytrees) and the sharding layer (NamedSharding per leaf by path
regex) stay trivial.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def _uniform_init(rng, shape, scale):
    return jax.random.uniform(rng, shape, jnp.float32, -scale, scale)


def init_dense(rng, in_dim: int, out_dim: int, use_bias: bool = True) -> Dict:
    k1, _ = jax.random.split(rng)
    scale = float(np.sqrt(1.0 / in_dim))
    p = {"w": _uniform_init(k1, (in_dim, out_dim), scale)}
    if use_bias:
        p["b"] = jnp.zeros((out_dim,), jnp.float32)
    return p


def apply_dense(p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def init_conv(
    rng, in_ch: int, out_ch: int, ksize: int = 3, use_bias: bool = True
) -> Dict:
    scale = float(np.sqrt(1.0 / (in_ch * ksize * ksize)))
    p = {"w": _uniform_init(rng, (ksize, ksize, in_ch, out_ch), scale)}
    if use_bias:
        p["b"] = jnp.zeros((out_ch,), jnp.float32)
    return p


def apply_conv(p: Dict, x: jnp.ndarray, stride: int = 1, padding: str = "SAME"):
    y = jax.lax.conv_general_dilated(
        x,
        p["w"].astype(x.dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def apply_conv_transpose(p: Dict, x: jnp.ndarray, stride: int = 2):
    y = jax.lax.conv_transpose(
        x,
        p["w"].astype(x.dtype),
        strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def max_pool(x: jnp.ndarray, window: int = 2) -> jnp.ndarray:
    """``window`` x ``window`` max-pool with stride ``window``, ``VALID``.

    Its gradient goes to the first element of each window, in row-major
    order, that equals the window's max: what ``reduce_window``'s own
    gradient (a select-and-scatter with a ``>=`` select) gives, ties
    included, but without a select-and-scatter, which is slow on the TPU."""
    return _reduce_max(x, window)


def _reduce_max(x: jnp.ndarray, window: int) -> jnp.ndarray:
    return jax.lax.reduce_window(
        x,
        -jnp.inf,
        jax.lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, window, window, 1),
        padding="VALID",
    )


def _max_pool_fwd(x, window):
    y = _reduce_max(x, window)
    return y, (x, y)


def _first_max(x: jnp.ndarray, y: jnp.ndarray, window: int) -> jnp.ndarray:
    """Row-major position in its window of the first element equal to
    the window's max ``y``, from strided slices of ``x``.  In int8, so
    that the full-size copy the gradient reads is a quarter of an int32
    one: on one TPU v5e the vmapped CNN step of 8 clients x 32 samples
    took 1.29 ms so, 1.74 ms with int32."""
    if window * window > 127:
        raise ValueError(f"max_pool window {window} too large")
    b, _, _, c = x.shape
    ho, wo = y.shape[1], y.shape[2]
    first = jnp.full(y.shape, window * window, jnp.int8)
    for p in reversed(range(window * window)):
        dy, dx = divmod(p, window)
        xs = jax.lax.slice(
            x, (0, dy, dx, 0),
            (b, dy + (ho - 1) * window + 1, dx + (wo - 1) * window + 1, c),
            (1, window, window, 1))
        first = jnp.where(xs == y, jnp.int8(p), first)
    return first


def _upsample(a: jnp.ndarray, window: int, h: int, w: int, fill) -> jnp.ndarray:
    """Each element of ``a`` repeated over its ``window`` x ``window``
    block of an (h, w) map; rows and columns past the last whole window
    get ``fill``.  A max over a window of the base-dilated ``a``: XLA
    lowers this to one reduce-window, where a broadcast-and-reshape
    becomes a separate, slower relayout on the TPU."""
    ho, wo = a.shape[1], a.shape[2]
    return jax.lax.reduce_window(
        a, jnp.array(fill, a.dtype), jax.lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, 1, 1, 1),
        padding=((0, 0),
                 (window - 1, h - ho * window + window - 1),
                 (window - 1, w - wo * window + window - 1),
                 (0, 0)),
        base_dilation=(1, window, window, 1))


def _max_pool_bwd(window, res, g):
    x, y = res
    h, w = x.shape[1], x.shape[2]
    rows, cols = np.arange(h)[:, None], np.arange(w)[None, :]
    pos = ((rows % window) * window + cols % window).astype(np.int8)
    first = _upsample(_first_max(x, y, window), window, h, w, -1)
    dx = jnp.where(pos[None, :, :, None] == first,
                   _upsample(g, window, h, w, -jnp.inf), jnp.zeros((), g.dtype))
    return (dx,)


max_pool.defvjp(_max_pool_fwd, _max_pool_bwd)


def init_layernorm(dim: int) -> Dict:
    return {"scale": jnp.ones((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}


def apply_layernorm(p: Dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def init_rmsnorm(dim: int) -> Dict:
    return {"scale": jnp.ones((dim,), jnp.float32)}


def apply_rmsnorm(p: Dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(x.dtype)


def init_embedding(rng, vocab: int, dim: int) -> Dict:
    return {"table": jax.random.normal(rng, (vocab, dim), jnp.float32) * 0.02}


def apply_embedding(p: Dict, tokens: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return p["table"].astype(dtype)[tokens]


def count_params(params: PyTree) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def param_bits(params: PyTree, bits_per_param: int = 32) -> int:
    """Payload size z|N| for the comm model (eq. 7)."""
    return count_params(params) * bits_per_param


def tree_cast(params: PyTree, dtype) -> PyTree:
    return jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
