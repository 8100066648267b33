"""``nn.max_pool``'s own gradient against ``lax.reduce_window``'s.

``nn.max_pool`` routes each window's cotangent to the first element, in
row-major order, that equals the window's max, without a
select-and-scatter. These tests hold it to the plain ``reduce_window``
max-pool, whose autodiff gradient is a ``select_and_scatter_add``:
forward and gradient equal element for element, ties included.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fltask import cross_entropy_loss
from repro.models import cnn, nn


def plain_max_pool(x, window=2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, window, window, 1),
        padding="VALID",
    )


def _normal(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _tied(shape):
    # values on a coarse grid after a ReLU: many windows hold their max
    # more than once, and many are all zero
    return jax.nn.relu(jnp.round(_normal(0, shape) * 2.0) / 2.0)


def _first_of_three():
    # a window [[1, 3], [3, 3]]: the max 3 three times
    return jnp.array([[1.0, 3.0], [3.0, 3.0]]).reshape(1, 2, 2, 1)


def _relu_zeroed(shape):
    # the first channel is negative everywhere, so the ReLU before the
    # pool zeroes each of its windows
    x = _normal(0, shape)
    return x.at[..., 0].set(-jnp.abs(x[..., 0]) - 0.5)


# name: (input, window, how the pool is applied)
CASES = {
    "random_28x28x32": (lambda: _normal(0, (4, 28, 28, 32)), 2, "pool"),
    "random_14x14x64": (lambda: _normal(0, (4, 14, 14, 64)), 2, "pool"),
    "ties_28x28x32": (lambda: _tied((4, 28, 28, 32)), 2, "pool"),
    "first_of_three": (_first_of_three, 2, "pool"),
    "relu_zeroed": (lambda: _relu_zeroed((2, 8, 8, 4)), 2, "relu_pool"),
    "odd_7x7": (lambda: _tied((2, 7, 7, 3)), 2, "pool"),
    "odd_9x8_window3": (lambda: _tied((2, 9, 8, 5)), 3, "pool"),
    # per-client inputs under vmap, as `_local_train_vmapped` runs them
    "vmapped_clients": (lambda: _tied((3, 2, 14, 14, 8)), 2, "vmap_pool"),
}


def _applied(pool, how):
    if how == "relu_pool":
        return lambda a: pool(jax.nn.relu(a))
    if how == "vmap_pool":
        return jax.vmap(pool)
    return pool


@pytest.mark.parametrize("case", sorted(CASES))
def test_max_pool_equals_reduce_window(case):
    make, window, how = CASES[case]
    x = make()
    pool = _applied(lambda a: nn.max_pool(a, window), how)
    plain = _applied(lambda a: plain_max_pool(a, window), how)
    y, vjp = jax.vjp(pool, x)
    y0, vjp0 = jax.vjp(plain, x)
    g = _normal(1, y0.shape)
    dx, = vjp(g)
    dx0, = vjp0(g)
    assert y.shape == y0.shape and y.dtype == y0.dtype
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    assert dx.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx0))
    if case == "first_of_three":
        np.testing.assert_array_equal(np.asarray(dx).ravel(),
                                      [0.0, float(g.ravel()[0]), 0.0, 0.0])
    if case == "relu_zeroed":
        assert not np.asarray(dx[..., 0]).any()
        assert np.asarray(dx[..., 1:]).any()
    if case.startswith("odd_"):
        ho, wo = y.shape[1], y.shape[2]
        assert (ho, wo) == (x.shape[1] // window, x.shape[2] // window)
        assert not np.asarray(dx[:, ho * window:]).any()
        assert not np.asarray(dx[:, :, wo * window:]).any()


def _vmapped_cnn_grad_text():
    """StableHLO of the vmapped per-client CNN gradient at K = 8 clients
    of B = 32 samples."""
    k, b = 8, 32
    params = jax.vmap(cnn.init_cnn)(jax.random.split(jax.random.PRNGKey(0), k))
    x = jnp.zeros((k, b, 28, 28, 1), jnp.float32)
    y = jnp.zeros((k, b), jnp.int32)

    def loss(p, xb, yb):
        return cross_entropy_loss(cnn.apply_cnn(p, xb), yb)

    return jax.jit(jax.vmap(jax.grad(loss))).lower(params, x, y).as_text()


def test_vmapped_cnn_grad_has_no_select_and_scatter(monkeypatch):
    text = _vmapped_cnn_grad_text()
    assert text.count("select_and_scatter") == 0
    assert text.count("stablehlo.convolution") == 5
    # the same program with the plain pool has one per pool
    monkeypatch.setattr(nn, "max_pool", plain_max_pool)
    assert _vmapped_cnn_grad_text().count("select_and_scatter") == 2
