"""jit'd wrapper: pytree-level weighted aggregation through the kernel.

The kernel compiles to Mosaic on TPU and runs in interpret mode on CPU
(``repro.kernels.interpret_mode``).  ``aggregate_pytree`` flattens every
leaf, concatenates into one (K, N) stream (one kernel launch instead of
hundreds of tiny ones) and unflattens the result.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.aggregate import aggregate_flat

PyTree = Any


def aggregate_pytree(stacked: PyTree, weights: jnp.ndarray) -> PyTree:
    """stacked: pytree with leaves (K, ...); returns weighted sum."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    k = leaves[0].shape[0]
    shapes = [l.shape[1:] for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    dtypes = [l.dtype for l in leaves]
    common = jnp.result_type(*dtypes)
    flat = jnp.concatenate(
        [l.reshape(k, -1).astype(common) for l in leaves], axis=1
    )
    agg = aggregate_flat(flat, weights, interpret=interpret_mode())
    outs = []
    off = 0
    for shape, size, dt in zip(shapes, sizes, dtypes):
        outs.append(agg[off: off + size].reshape(shape).astype(dt))
        off += size
    return jax.tree_util.tree_unflatten(treedef, outs)
