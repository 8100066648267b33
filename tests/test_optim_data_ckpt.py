"""Optimizers, data pipeline, checkpointing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.ckpt import latest_step, restore_checkpoint, save_checkpoint
from repro.data import (
    label_histogram,
    make_classification_dataset,
    make_segmentation_dataset,
    make_token_dataset,
    partition_iid,
    partition_noniid_by_orbit,
)
from repro.data.partition import stack_client_arrays
from repro.optim import adafactor, adam, clip_by_global_norm, get_optimizer, \
    momentum, sgd
from repro.optim.optimizers import apply_updates


# --- optimizers ------------------------------------------------------------------
def _quadratic_converges(opt, steps=300):
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    state = opt.init(params)
    for _ in range(steps):
        g = jax.grad(loss)(params)
        updates, state = opt.update(g, state, params)
        params = apply_updates(params, updates)
    return float(loss(params))


@pytest.mark.parametrize("name,lr,steps", [
    ("sgd", 0.1, 300), ("momentum", 0.05, 300), ("adam", 0.1, 300),
    ("adafactor", 0.2, 800),    # relative-update clipping -> slower tail
])
def test_optimizers_converge_quadratic(name, lr, steps):
    assert _quadratic_converges(get_optimizer(name, lr), steps) < 1e-2


def test_adafactor_state_is_factored():
    opt = adafactor(0.01)
    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros(7)}
    state = opt.init(params)
    row, col = state.factored["w"]
    assert row.shape == (64,) and col.shape == (32,)
    assert state.factored["b"].shape == (7,)   # 1-D: full second moment


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 100.0)}
    clipped = clip_by_global_norm(g, 1.0)
    norm = float(jnp.linalg.norm(clipped["a"]))
    assert abs(norm - 1.0) < 1e-5
    small = {"a": jnp.full((4,), 0.01)}
    np.testing.assert_allclose(clip_by_global_norm(small, 1.0)["a"],
                               small["a"], rtol=1e-6)


# --- data ------------------------------------------------------------------------
def test_train_test_same_distribution():
    train = make_classification_dataset("mnist-like", 256, seed=0)
    test = make_classification_dataset("mnist-like", 256, seed=99)
    # same class patterns: per-class means of train/test must correlate
    for c in range(3):
        mtr = train.x[train.y == c].mean(0).ravel()
        mte = test.x[test.y == c].mean(0).ravel()
        r = np.corrcoef(mtr, mte)[0, 1]
        assert r > 0.5, f"class {c} corr {r}"


def test_classification_data_is_the_same_in_every_process():
    """The data depends on its seeds alone, not on the process's salted
    str hashes."""
    import subprocess
    import sys

    code = ("from repro.data import make_classification_dataset as m; "
            "print(repr(float(m('mnist-like', 8, seed=3).x.sum())))")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = {
        subprocess.run(
            [sys.executable, "-c", code], cwd=root, check=True,
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, PYTHONHASHSEED=str(salt), PYTHONPATH="src",
                     JAX_PLATFORMS="cpu"),
        ).stdout
        for salt in (1, 2)
    }
    assert len(outs) == 1


def test_noniid_partition_matches_paper():
    """§V-A: 2 orbits -> 4 classes; 3 orbits -> remaining 6 classes."""
    ds = make_classification_dataset("mnist-like", 2000, seed=1)
    clients = partition_noniid_by_orbit(ds, 5, 8)
    assert len(clients) == 40
    for c in clients:
        classes = set(np.unique(c.data.y).tolist())
        if c.plane < 2:
            assert classes <= {0, 1, 2, 3}
        else:
            assert classes <= {4, 5, 6, 7, 8, 9}
    total = sum(cl.num_samples for cl in clients)
    assert total == 2000


def test_iid_partition_even():
    ds = make_classification_dataset("mnist-like", 400, seed=2)
    clients = partition_iid(ds, 5, 8)
    sizes = [c.num_samples for c in clients]
    assert max(sizes) - min(sizes) <= 1
    hist = label_histogram(clients[0].data)
    assert (hist > 0).sum() >= 5   # each client sees most classes


def test_stack_client_arrays_padding():
    ds = make_classification_dataset("mnist-like", 101, seed=3)
    clients = partition_iid(ds, 2, 2)
    xs, ys, counts = stack_client_arrays(clients)
    assert xs.shape[0] == 4
    assert xs.shape[1] == max(counts)
    assert counts.sum() == 101


def test_segmentation_dataset():
    ds = make_segmentation_dataset(num_samples=8, size=32, seed=0)
    assert ds.x.shape == (8, 32, 32, 3)
    assert ds.y.shape == (8, 32, 32)
    assert set(np.unique(ds.y)) <= {0, 1}
    frac = ds.y.mean()
    assert 0.01 < frac < 0.5   # roads present but sparse


def test_token_dataset_structure():
    ds = make_token_dataset(num_sequences=8, seq_len=64, vocab_size=128,
                            seed=0)
    assert ds.x.shape == (8, 64)
    assert ds.x.max() < 128
    # Markov structure: repeat-token rate above uniform chance
    repeats = (ds.x[:, 1:] == ds.x[:, :-1]).mean()
    assert repeats > 2.0 / 128


# --- checkpointing --------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
        "opt": [jnp.ones(4, jnp.float32), jnp.zeros((), jnp.int32)],
    }
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 10, tree)
    save_checkpoint(d, 20, tree)
    assert latest_step(d) == 20
    restored = restore_checkpoint(d, 10, tree)
    np.testing.assert_array_equal(restored["params"]["w"],
                                  tree["params"]["w"])
    np.testing.assert_array_equal(restored["opt"][0], tree["opt"][0])
