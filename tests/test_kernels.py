"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in interpret mode on CPU (the kernels target TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.aggregate import aggregate_flat
from repro.kernels.aggregate_ref import aggregate_flat_ref
from repro.kernels.flash import flash_attention
from repro.kernels.flash_ref import flash_attention_ref
from repro.kernels.ssd import ssd_scan
from repro.kernels.ssd_ref import ssd_naive, ssd_ref


# --- aggregate -------------------------------------------------------------------
@pytest.mark.parametrize("k,n", [(2, 64), (5, 1000), (8, 40000), (3, 17),
                                 (40, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aggregate_sweep(k, n, dtype):
    rng = np.random.default_rng(k * n)
    x = jnp.asarray(rng.standard_normal((k, n)), dtype)
    w = jnp.asarray(rng.random(k), jnp.float32)
    w = w / w.sum()
    out = aggregate_flat(x, w, block_n=4096, interpret=True)
    ref = aggregate_flat_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_aggregate_pytree_wrapper():
    from repro.kernels.aggregate_ops import aggregate_pytree

    rng = np.random.default_rng(0)
    tree = {
        "a": jnp.asarray(rng.standard_normal((3, 8, 4)), jnp.float32),
        "b": [jnp.asarray(rng.standard_normal((3, 5)), jnp.float32)],
    }
    w = jnp.asarray([0.5, 0.25, 0.25], jnp.float32)
    out = aggregate_pytree(tree, w)
    np.testing.assert_allclose(
        out["a"], np.einsum("k,kij->ij", np.asarray(w), tree["a"]),
        rtol=1e-5,
    )
    assert out["b"][0].shape == (5,)


@pytest.mark.parametrize("k", [5, 8])
def test_aggregate_pytree_jit_matches_eager_bitwise(k):
    """One jitted program gives the eager composition's bits (concatenate,
    the kernel, per-leaf slices) on ragged leaves of mixed dtypes."""
    from repro.kernels.aggregate_ops import aggregate_pytree

    rng = np.random.default_rng(k)
    shapes = [(3, 3, 5), (130,), (), (7, 17), (4097,)]
    tree = [jnp.asarray(rng.standard_normal((k,) + s), jnp.float32)
            for s in shapes]
    tree.append(jnp.asarray(rng.standard_normal((k, 33)), jnp.bfloat16))
    w = jnp.asarray(rng.random(k), jnp.float32)
    w = w / jnp.sum(w)
    jitted = aggregate_pytree(tree, w)
    eager = aggregate_pytree.__wrapped__(tree, w)
    for a, b in zip(jitted, eager):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# --- flash attention --------------------------------------------------------------
@pytest.mark.parametrize(
    "b,s,h,g,d,causal,window",
    [
        (1, 128, 4, 2, 32, True, None),
        (2, 256, 8, 2, 64, True, None),
        (1, 128, 4, 4, 32, True, 64),      # sliding window
        (1, 256, 4, 1, 32, False, None),   # MQA, bidirectional
        (2, 128, 2, 2, 128, True, None),   # MHA, wide head
    ],
)
def test_flash_sweep(b, s, h, g, d, causal, window):
    rng = np.random.default_rng(s + h)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, g, d)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, g, d)) * 0.5, jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3),
                                       (jnp.bfloat16, 3e-2)])
def test_flash_dtypes(dtype, tol):
    rng = np.random.default_rng(7)
    b, s, h, g, d = 1, 128, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)) * 0.5, dtype)
    k = jnp.asarray(rng.standard_normal((b, s, g, d)) * 0.5, dtype)
    v = jnp.asarray(rng.standard_normal((b, s, g, d)) * 0.5, dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_soft_cap():
    rng = np.random.default_rng(8)
    b, s, h, g, d = 1, 128, 2, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, g, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, g, d)), jnp.float32)
    out = flash_attention(q, k, v, logit_soft_cap=20.0, block_q=64,
                          block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, logit_soft_cap=20.0)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


# --- SSD scan -----------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,s,h,p,g,n,chunk",
    [
        (1, 64, 2, 8, 1, 8, 16),
        (2, 128, 4, 16, 2, 8, 32),
        (1, 256, 4, 32, 4, 16, 64),
        (1, 128, 8, 16, 1, 32, 128),   # single chunk == full seq
    ],
)
def test_ssd_sweep(b, s, h, p, g, n, chunk):
    rng = np.random.default_rng(s + n)
    x = jnp.asarray(rng.standard_normal((b, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.random((b, s, h)) * 0.5 + 0.1, jnp.float32)
    A = -jnp.asarray(rng.random(h) * 0.5 + 0.1, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.5, jnp.float32)
    truth = ssd_naive(x, dt, A, Bm, Cm)
    ref = ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    kern = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    np.testing.assert_allclose(ref, truth, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(kern, truth, rtol=2e-3, atol=2e-3)


def test_ssd_decode_matches_chunked():
    """Sequential decode steps == chunked scan on the same sequence."""
    from repro.models.mamba2 import ssd_chunked, ssd_decode_step

    rng = np.random.default_rng(9)
    b, s, h, p, g, n = 1, 32, 2, 8, 1, 8
    x = jnp.asarray(rng.standard_normal((b, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.random((b, s, h)) * 0.5 + 0.1, jnp.float32)
    A = -jnp.asarray(rng.random(h) * 0.5 + 0.1, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.5, jnp.float32)
    y_chunked, final = ssd_chunked(x, dt, A, Bm, Cm, chunk=8)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        y, state = ssd_decode_step(
            x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], state
        )
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_seq, y_chunked, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(state, final, rtol=2e-3, atol=2e-3)


def test_chunked_attention_vs_dense():
    """The XLA flash-style path used by the dry-run matches dense attn."""
    from repro.models.layers import (
        _attn_mask, attention_scores, chunked_attention,
    )

    rng = np.random.default_rng(11)
    b, s, h, g, d = 2, 256, 8, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, g, d)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, g, d)) * 0.5, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    for causal, win in [(True, None), (True, 64), (False, None)]:
        ref = attention_scores(q, k, v, _attn_mask(pos, pos, causal, win),
                               h // g)
        out = chunked_attention(q, k, v, h // g, causal=causal, window=win,
                                q_chunk=64, k_chunk=64)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
