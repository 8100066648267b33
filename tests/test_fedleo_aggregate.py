"""Pallas aggregation on real model pytrees: ``make_fedleo_aggregate``
kernel path vs reference, and the staleness weighting (ISSUE 10).

The kernel path flattens every replicated leaf into one (R, N) stream
through ``kernels.aggregate_flat`` (interpret mode on CPU).  Parity is
checked against BOTH the in-module reference path and the per-leaf
fp32 ``aggregate_flat_ref`` ground truth, over ragged leaf shapes
(conv kernels, biases, dense mats), bf16/f32/mixed dtypes, zero-weight
replicas, and staleness-discounted weights.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.aggregate_ref import aggregate_flat_ref
from repro.models.cnn import init_cnn
from repro.optim import get_optimizer
from repro.train.fedleo_step import make_fedleo_aggregate, staleness_weights
from repro.train.steps import TrainState

R = 4


def _stacked_cnn_state(dtype=jnp.float32, seed=0):
    """A real CNN TrainState with the leading orbit-replica axis R:
    ragged leaves (4-D conv kernels, 1-D biases, 2-D dense mats)."""
    params = init_cnn(jax.random.PRNGKey(seed), (28, 28, 1), 10,
                      widths=(8, 16), hidden=32)
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), R)

    def stack(p):
        return jnp.stack([
            p + 0.01 * jax.random.normal(keys[i], p.shape, p.dtype)
            .astype(p.dtype) for i in range(R)
        ])

    stacked = jax.tree_util.tree_map(stack, params)
    opt = get_optimizer("sgd", 0.05)
    return TrainState(
        params=stacked, opt_state=opt.init(stacked),
        step=jnp.zeros((), jnp.int32),
    )


def _max_err(a: TrainState, b: TrainState) -> float:
    errs = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(
            x.astype(jnp.float32) - y.astype(jnp.float32)
        ))) if x.ndim else abs(float(x) - float(y)),
        a, b,
    )
    return max(jax.tree_util.tree_leaves(errs), default=0.0)


def _bf16_ulp(x) -> np.ndarray:
    """One unit in the last place of each element of a bf16 array: bf16
    keeps 8 significant bits to f32's 24, so its spacing is 2**16 times
    f32's."""
    return np.spacing(np.abs(np.asarray(x, np.float32))) * 2.0 ** 16


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, 1e-6),
    (jnp.bfloat16, 1e-6),
])
def test_kernel_matches_reference_on_real_pytree(dtype, tol):
    state = _stacked_cnn_state(dtype)
    w = jnp.array([1.0, 2.0, 3.0, 4.0])
    ref = make_fedleo_aggregate(use_kernel=False)(state, w)
    ker = make_fedleo_aggregate(use_kernel=True)(state, w)
    if dtype == jnp.float32:
        assert _max_err(ref, ker) <= tol
    for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(ker.params)):
        # dtypes survive the kernel round-trip
        assert a.dtype == b.dtype
        if dtype == jnp.bfloat16:
            # Both paths sum in f32, but the kernel's fused loop rounds
            # each multiply-add once where the reference rounds the
            # product and the sum apart.  Where that last-bit f32
            # difference straddles a bf16 rounding boundary, the bf16
            # output moves by one ulp, so bf16 is held to one output ulp.
            err = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
            assert np.all(err <= tol + _bf16_ulp(a))


def test_kernel_matches_flat_ref_ground_truth():
    """Each replicated param leaf must equal the fp32 per-leaf
    ``aggregate_flat_ref`` ground truth broadcast back over R."""
    state = _stacked_cnn_state(jnp.float32)
    w = jnp.array([0.5, 1.5, 2.0, 1.0])
    wn = w / jnp.sum(w)
    out = make_fedleo_aggregate(use_kernel=True)(state, w)
    for leaf, agg in zip(jax.tree_util.tree_leaves(state.params),
                         jax.tree_util.tree_leaves(out.params)):
        gt = aggregate_flat_ref(leaf.reshape(R, -1), wn)
        np.testing.assert_allclose(
            np.asarray(agg[0].reshape(-1), np.float32),
            np.asarray(gt, np.float32), atol=1e-6,
        )
        # every replica row carries the same aggregated model
        np.testing.assert_array_equal(np.asarray(agg[0]),
                                      np.asarray(agg[-1]))


def test_zero_weight_replica_excluded():
    state = _stacked_cnn_state(jnp.float32)
    w = jnp.array([1.0, 0.0, 1.0, 1.0])
    for use_kernel in (False, True):
        out = make_fedleo_aggregate(use_kernel=use_kernel)(state, w)
        # perturb replica 1 only: a zero-weight client must not move
        # the aggregate
        poisoned = jax.tree_util.tree_map(
            lambda x: x.at[1].mul(100.0) if x.ndim else x, state.params
        )
        out2 = make_fedleo_aggregate(use_kernel=use_kernel)(
            TrainState(params=poisoned, opt_state=state.opt_state,
                       step=state.step), w,
        )
        assert _max_err(out, out2) == 0.0


def test_scalar_and_step_leaves_pass_through():
    state = _stacked_cnn_state(jnp.float32)
    w = jnp.ones(R)
    for use_kernel in (False, True):
        out = make_fedleo_aggregate(use_kernel=use_kernel)(state, w)
        assert int(out.step) == int(state.step)


def test_mixed_dtype_tree_parity():
    state = _stacked_cnn_state(jnp.float32)
    # make one param leaf bf16: exercises the common-dtype concat path
    leaves, treedef = jax.tree_util.tree_flatten(state.params)
    leaves[0] = leaves[0].astype(jnp.bfloat16)
    params = jax.tree_util.tree_unflatten(treedef, leaves)
    state = TrainState(params=params, opt_state=state.opt_state,
                       step=state.step)
    w = jnp.array([1.0, 2.0, 3.0, 4.0])
    ref = make_fedleo_aggregate(use_kernel=False)(state, w)
    ker = make_fedleo_aggregate(use_kernel=True)(state, w)
    assert _max_err(ref, ker) <= 1e-2   # bf16 output rounding
    assert jax.tree_util.tree_leaves(ker.params)[0].dtype == jnp.bfloat16


class TestStalenessWeights:
    def test_zero_staleness_is_identity(self):
        w = jnp.array([1.0, 2.0, 3.0])
        out = staleness_weights(w, jnp.zeros(3))
        np.testing.assert_allclose(np.asarray(out), np.asarray(w))

    def test_staler_is_discounted_monotonically(self):
        w = jnp.ones(3)
        out = staleness_weights(w, jnp.array([0.0, 3600.0, 7200.0]))
        assert out[0] > out[1] > out[2] > 0

    def test_one_hour_at_default_power(self):
        out = staleness_weights(jnp.ones(1), jnp.array([3600.0]))
        assert float(out[0]) == pytest.approx(2.0 ** -0.5)

    def test_aggregate_accepts_staleness(self):
        state = _stacked_cnn_state(jnp.float32)
        w = jnp.ones(R)
        stale = jnp.array([0.0, 0.0, 7200.0, 7200.0])
        plain = make_fedleo_aggregate()(state, w)
        disc = make_fedleo_aggregate()(state, w, stale)
        assert _max_err(plain, disc) > 0.0    # discount moved the mean
        ker = make_fedleo_aggregate(use_kernel=True)(state, w, stale)
        assert _max_err(disc, ker) <= 1e-6
