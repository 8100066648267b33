"""Weighted model aggregation (paper eqs. 4 and 9).

Two layers:

  * ``weighted_average(stacked_params, weights)``: the core primitive —
    given a pytree whose leaves are stacked over a leading client axis
    and normalized weights, computes sum_k a_k * theta_k.  This is the
    compute hot-spot of the FL server (for a 123B-param model a single
    aggregation streams ~1 TB through HBM), so it is backed by the
    ``repro.kernels.aggregate`` Pallas kernel on TPU with a pure-jnp
    path elsewhere.

  * Orbit/global helpers mirroring the paper:
      - ``partial_aggregate``: the sink satellite's per-orbit partial
        global model  w_{K_l} = sum_{k in K_l} (m_k / m_{K_l}) w_k^I (9)
      - ``global_aggregate``: the GS's final model
        w^{t+1} = sum_k (m_k / m) w_k                                 (4)
      - ``noniid_weights``: label-histogram-aware weighting (the
        piggybacked data distribution of §IV-A): class-coverage-balanced
        weights so orbits holding rare classes are not drowned out.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import span

PyTree = Any


def weighted_average(
    stacked: PyTree, weights: jnp.ndarray, use_kernel: bool = False
) -> PyTree:
    """sum_k weights[k] * leaf[k] for every leaf (leading axis = clients).

    Args:
      stacked: pytree with leaves of shape (K, ...).
      weights: (K,) nonnegative weights; will be normalized to sum to 1.
      use_kernel: route through the Pallas aggregation kernel (TPU).

    Runs in a ``repro.aggregate`` profiler span whose ``bytes`` stat is
    the size of the stacked leaves it reads.
    """
    leaves = jax.tree_util.tree_leaves(stacked)
    nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
    with span("aggregate", bytes=nbytes):
        w = weights / jnp.sum(weights)
        if use_kernel:
            from repro.kernels import aggregate_ops

            return aggregate_ops.aggregate_pytree(stacked, w)

        def leaf(x: jnp.ndarray) -> jnp.ndarray:
            return jnp.tensordot(
                w.astype(jnp.float32), x.astype(jnp.float32), axes=1
            ).astype(x.dtype)

        return jax.tree_util.tree_map(leaf, stacked)


def partial_aggregate(
    stacked: PyTree, sample_counts: Sequence[int], use_kernel: bool = False
) -> PyTree:
    """Eq. (9): sink satellite's partial global model for one orbit."""
    m = jnp.asarray(sample_counts, jnp.float32)
    return weighted_average(stacked, m, use_kernel=use_kernel)


def global_aggregate(
    stacked: PyTree,
    sample_counts: Sequence[int],
    histograms: Optional[np.ndarray] = None,
    noniid_alpha: float = 0.0,
    use_kernel: bool = False,
) -> PyTree:
    """Eq. (4) with optional non-IID correction.

    Args:
      stacked: stacked partial (or client) models, leading axis K.
      sample_counts: m_k (or m_{K_l} for orbit partials).
      histograms: (K, num_classes) label histograms piggybacked during
        model propagation. If given and noniid_alpha > 0, weights are
        blended between data-size weighting and class-coverage-balanced
        weighting.
      noniid_alpha: 0 = pure eq. (4); 1 = fully class-balanced.
    """
    m = jnp.asarray(sample_counts, jnp.float32)
    w = m / jnp.sum(m)
    if histograms is not None and noniid_alpha > 0.0:
        w_bal = jnp.asarray(noniid_weights(np.asarray(histograms)), jnp.float32)
        w = (1.0 - noniid_alpha) * w + noniid_alpha * w_bal
        w = w / jnp.sum(w)
    return weighted_average(stacked, w, use_kernel=use_kernel)


def noniid_weights(histograms: np.ndarray) -> np.ndarray:
    """Class-coverage-balanced weights from piggybacked label histograms.

    Each class's total mass is split equally among the contributors that
    hold it; a contributor's weight is its summed class shares.  Orbits
    holding classes nobody else has therefore keep their influence even
    when their m_k is small — the paper's motivation for uploading the
    data distribution with the partial model.
    """
    h = np.asarray(histograms, np.float64)
    class_tot = h.sum(axis=0, keepdims=True)       # (1, C)
    share = np.divide(h, class_tot, out=np.zeros_like(h), where=class_tot > 0)
    w = share.sum(axis=1)
    s = w.sum()
    if s <= 0:
        return np.full(h.shape[0], 1.0 / h.shape[0])
    return w / s


def stack_pytrees(trees: Sequence[PyTree]) -> PyTree:
    """Stack a list of identically-structured pytrees along a new axis 0."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def index_pytree(stacked: PyTree, i: int) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x[i], stacked)
