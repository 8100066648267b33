"""The plain reference: per-client training, the FL aggregations, and the
numbers that decide ``correct``.

Nothing here imports the program.  Layers are single ``jax.lax`` calls in
float32, or in the control's lower precision, at the matmul precision of
the caller's ``jax.default_matmul_precision``: the configuration's stated
``matmul_precision``, or the highest where it states none; every client
trains alone (no vmap); the weighted means are taken in float64 on the host.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def conv(x, w, b, dtype):
    """Stride-1 SAME convolution, NHWC x HWIO."""
    y = jax.lax.conv_general_dilated(
        x, w.astype(dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b.astype(dtype)


def dense(x, w, b, dtype):
    return jnp.dot(x, w.astype(dtype)) + b.astype(dtype)


def conv_transpose2(x, w, b, dtype):
    """2x2 transposed convolution of stride 2, NHWC x HWIO: input pixel
    (i, j) writes ``x[i, j] @ w[1 - a, 1 - b]`` to output pixel
    (2i + a, 2j + b), the taps flipped as ``jax.lax.conv_transpose`` with
    SAME padding places them."""
    n, h, wd, _ = x.shape
    y = jnp.einsum("nhwc,abco->nhawbo", x, w[::-1, ::-1].astype(dtype))
    return y.reshape(n, 2 * h, 2 * wd, -1) + b.astype(dtype)


def max_pool2(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy over every position of ``labels``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked.astype(F32))


# --- optimizers ----------------------------------------------------------------
def _sgd(lr):
    def init(p):
        return ()

    def update(p, g, s):
        return jax.tree_util.tree_map(lambda a, d: a - lr * d, p, g), s

    return init, update


def _adam(lr, b1=0.9, b2=0.999, eps=1e-8):
    """Kingma & Ba (arXiv:1412.6980), Algorithm 1: bias-corrected first and
    second moments, kept in the parameters' dtype."""
    def init(p):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
        return jnp.zeros((), jnp.int32), zeros, zeros

    def update(p, g, s):
        t, m, v = s
        t = t + 1
        m = jax.tree_util.tree_map(lambda a, d: b1 * a + (1 - b1) * d, m, g)
        v = jax.tree_util.tree_map(lambda a, d: b2 * a + (1 - b2) * d * d, v, g)
        c1 = 1 - b1 ** t.astype(F32)
        c2 = 1 - b2 ** t.astype(F32)

        def step(a, mi, vi):
            mhat = mi / c1.astype(a.dtype)
            vhat = vi / c2.astype(a.dtype)
            return a - lr * mhat / (jnp.sqrt(vhat) + eps)

        return jax.tree_util.tree_map(step, p, m, v), (t, m, v)

    return init, update


OPTIMIZERS = {"sgd": _sgd, "adam": _adam}


def make_client_trainer(apply: Callable, cfg: dict, dtype=F32) -> Callable:
    """``train(params, x, y, rng) -> params``: ``executed_epochs`` epochs of
    mini-batch training on one client, in ``dtype``.  Each epoch draws its
    batch order as ``jax.random.permutation`` of that epoch's key, the keys
    being ``jax.random.split(rng, executed_epochs)``; clients smaller than a
    batch take full-batch steps."""
    init, update = OPTIMIZERS[cfg["optimizer"]](cfg["learning_rate"])
    epochs = cfg["executed_epochs"]

    def loss(p, xb, yb):
        return cross_entropy(apply(p, xb, dtype), yb)

    def train(params, x, y, rng):
        m = x.shape[0]
        b = min(cfg["batch_size"], m)
        nb = max(1, m // b)
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)

        def epoch(carry, ekey):
            perm = jax.random.permutation(ekey, m)

            def step(carry, i):
                p, s = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, i * b, b)
                g = jax.grad(loss)(p, x[idx].astype(dtype), y[idx])
                return update(p, g, s), None

            carry, _ = jax.lax.scan(step, carry, jnp.arange(nb))
            return carry, None

        (params, _), _ = jax.lax.scan(
            epoch, (params, init(params)), jax.random.split(rng, epochs))
        return params

    return jax.jit(train)


# --- the round ----------------------------------------------------------------------
@dataclasses.dataclass
class Runtime:
    """What the reference holds for one run: the clients' data as the
    program trains on it (padded cyclically to the largest client), their
    true sizes and label histograms, a trainer, and the strategy's key."""

    xs: np.ndarray
    ys: np.ndarray
    counts: np.ndarray
    histograms: np.ndarray
    train: Callable
    key: jax.Array
    noniid_alpha: float

    def next_key(self) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sub


def make_runtime(clients: Sequence, num_classes: int, trainer: Callable,
                 sim_seed: int, noniid_alpha: float) -> Runtime:
    m = max(len(x) for x, _ in clients)
    pad = [np.resize(np.arange(len(x)), m) for x, _ in clients]
    return Runtime(
        xs=np.stack([x[i] for (x, _), i in zip(clients, pad)]),
        ys=np.stack([y[i] for (_, y), i in zip(clients, pad)]),
        counts=np.asarray([len(x) for x, _ in clients], np.float64),
        histograms=np.stack([np.bincount(y.reshape(-1), minlength=num_classes)
                             for _, y in clients]).astype(np.float64),
        train=trainer,
        key=jax.random.PRNGKey(sim_seed),
        noniid_alpha=noniid_alpha,
    )


def to_host(tree) -> List[np.ndarray]:
    return [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(tree)]


def _mean(trees: Sequence[List[np.ndarray]], w: np.ndarray) -> List[np.ndarray]:
    w = np.asarray(w, np.float64) / np.sum(w)
    return [sum(wi * t[i] for wi, t in zip(w, trees))
            for i in range(len(trees[0]))]


def train_group(rt: Runtime, params, clients: Sequence[int]) -> list:
    """Each listed client trained alone from ``params``, with the key the
    strategy draws for the group split over its clients."""
    keys = jax.random.split(rt.next_key(), len(clients))
    return [to_host(rt.train(params, rt.xs[c], rt.ys[c], keys[i]))
            for i, c in enumerate(clients)]


def noniid_weights(h: np.ndarray) -> np.ndarray:
    """Class-coverage weights: each class's mass split equally among the
    groups that hold it; a group's weight is the sum of its shares."""
    tot = h.sum(axis=0, keepdims=True)
    share = np.divide(h, tot, out=np.zeros_like(h), where=tot > 0)
    w = share.sum(axis=1)
    return w / w.sum() if w.sum() > 0 else np.full(len(h), 1.0 / len(h))


def sync_round(rt: Runtime, params, treedef, groups: Sequence[Sequence[int]]):
    """Eq. 9 per group (the sink's sample-weighted mean), then eq. 4 at the
    ground station, blended with the class-coverage weights."""
    partials, m, h = [], [], []
    for g in groups:
        trained = train_group(rt, params, g)
        partials.append(_mean(trained, rt.counts[list(g)]))
        m.append(rt.counts[list(g)].sum())
        h.append(rt.histograms[list(g)].sum(axis=0))
    w = np.asarray(m) / np.sum(m)
    if rt.noniid_alpha > 0:
        w = (1 - rt.noniid_alpha) * w + rt.noniid_alpha * noniid_weights(
            np.stack(h))
    return _unflat(treedef, _mean(partials, w))


def _unflat(treedef, leaves: List[np.ndarray]):
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(a, F32) for a in leaves])


def eval_loss(apply: Callable, params, x: np.ndarray, y: np.ndarray,
              dtype=F32, block: int = 128) -> float:
    """Mean cross-entropy over the test samples, in blocks of ``block``."""
    f = jax.jit(lambda p, xb, yb: cross_entropy(apply(p, xb, dtype), yb)
                * xb.shape[0])
    total = sum(float(f(params, x[i:i + block], y[i:i + block]))
                for i in range(0, len(x), block))
    return total / len(x)


# --- the numbers compared --------------------------------------------------------
def _moving(ref_norms: np.ndarray) -> np.ndarray:
    """Leaves that move in the reference: a change under a thousandth of the
    median leaf's is round-off, as a bias under a softmax is."""
    return ref_norms >= 1e-3 * np.median(ref_norms)


def _per_leaf(gaps: np.ndarray, refs: List[np.ndarray]) -> float:
    """Worst moving leaf's gap over the larger of that leaf's and the median
    leaf's reference norm."""
    r = np.asarray([np.linalg.norm(a) for a in refs])
    keep = _moving(r)
    return float(np.max(gaps[keep] / np.maximum(r, np.median(r))[keep]))


def norm_gap(prog: List[np.ndarray], refs: List[np.ndarray]) -> float:
    """Gap between the program's and the reference's change norm, by
    ``_per_leaf``."""
    return _per_leaf(np.asarray([abs(np.linalg.norm(a) - np.linalg.norm(b))
                                 for a, b in zip(prog, refs)]), refs)


def dist_gap(prog: List[np.ndarray], refs: List[np.ndarray]) -> float:
    """Distance between the program's and the reference's change,
    ||program - reference||, by ``_per_leaf``: where the norms agree, as
    under Adam's first steps, it still sees a change in another direction."""
    return _per_leaf(np.asarray([np.linalg.norm(a - b)
                                 for a, b in zip(prog, refs)]), refs)


def readings(w0: List[np.ndarray], prog_params: Sequence[List[np.ndarray]],
             prog_losses: Sequence[float], ref_params: Sequence[List[np.ndarray]],
             ref_losses: Sequence[float],
             own_losses: Sequence[float]) -> Dict[str, float]:
    """The compared numbers after R rounds, from the start weights ``w0``:
    the eval loss's relative gap, worst over rounds, against the reference's
    replay (``loss_gap``) and against the reference's loss of the program's
    own weights (``eval_gap``, the evaluation alone); the first round's change
    (the server's first pseudo-gradient) and the change after R rounds, each
    as a gap of norms (``delta1_gap``, ``deltaR_gap``) and as a distance
    (``delta1_dist``, ``deltaR_dist``)."""
    d1p = [a - b for a, b in zip(prog_params[0], w0)]
    d1r = [a - b for a, b in zip(ref_params[0], w0)]
    dRp = [a - b for a, b in zip(prog_params[-1], w0)]
    dRr = [a - b for a, b in zip(ref_params[-1], w0)]
    return {
        "loss_gap": max(abs(p - r) / r for p, r in zip(prog_losses, ref_losses)),
        "eval_gap": max(abs(p - r) / r for p, r in zip(prog_losses, own_losses)),
        "delta1_gap": norm_gap(d1p, d1r),
        "deltaR_gap": norm_gap(dRp, dRr),
        "delta1_dist": dist_gap(d1p, d1r),
        "deltaR_dist": dist_gap(dRp, dRr),
    }
