"""The plain reference against the program, on the CPU at small sizes: its
Adam against ``repro.optim.adam``, each configuration's forward pass
against the program's model on seeded random weights, and what the
compared numbers see."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as ref
from bench.cell import BENCH, load_json, load_module, matmul_precision
from bench.tests.conftest import TINY


def test_adam_matches_the_programs_adam_over_steps():
    from repro.optim import adam
    from repro.optim.optimizers import apply_updates

    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    p = {"w": jax.random.normal(keys[0], (5, 3)),
         "b": jax.random.normal(keys[1], (3,))}
    init, update = ref.OPTIMIZERS["adam"](1e-3)
    prog = adam(1e-3)
    q, s, ps = p, init(p), prog.init(p)
    for i, k in enumerate(keys[2:]):
        # gradients of mixed scales and signs, some far under eps's reach
        g = jax.tree_util.tree_map(
            lambda a: jax.random.normal(k, a.shape) * 10.0 ** -(i % 4), p)
        q, s = update(q, g, s)
        u, ps = prog.update(g, ps, p)
        p = apply_updates(p, u)
    for a, b in zip(jax.tree_util.tree_leaves(q), jax.tree_util.tree_leaves(p)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    assert int(s[0]) == 6


def test_adam_keeps_its_state_in_the_parameters_dtype():
    init, update = ref.OPTIMIZERS["adam"](1e-3)
    p = {"w": jnp.ones((4,), jnp.bfloat16)}
    q, (t, m, v) = update(p, {"w": jnp.full((4,), 0.5, jnp.bfloat16)}, init(p))
    assert q["w"].dtype == m["w"].dtype == v["w"].dtype == jnp.bfloat16


@pytest.mark.parametrize("name", ["cnn-mnist", "unet-deepglobe"])
def test_reference_forward_equals_the_programs_model(name):
    path = BENCH / "configs" / f"{name}.json"
    cfg, mod = load_json(path), load_module(path.with_suffix(".py"))
    cfg.update(TINY[cfg["model"]])
    params = mod.init_params(cfg, jax.random.PRNGKey(1))
    # nonzero biases too, so that every term of each layer is compared
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = treedef.unflatten([a + 0.05 * jax.random.normal(k, a.shape)
                                for a, k in zip(leaves, keys)])
    x = jax.random.normal(jax.random.PRNGKey(3), (4, *cfg["input_shape"]))
    apply, _ = mod.program_model(cfg)
    with jax.default_matmul_precision("highest"):
        got = apply(params, x)
        want = mod.reference_apply(params, x, jnp.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_distance_sees_a_change_the_norms_miss():
    # the program's change of the second leaf points another way, with the
    # same norm: the norm gap reads nothing, the distance reads it whole
    refs = [np.ones(4), np.array([1.0, 0.0]), np.full(3, 2.0)]
    prog = [refs[0], np.array([0.0, 1.0]), refs[2]]
    assert ref.norm_gap(prog, refs) == 0.0
    # over the larger of the leaf's norm (1) and the median leaf's (2)
    assert ref.dist_gap(prog, refs) == pytest.approx(np.sqrt(2.0) / 2.0)
    assert ref.dist_gap(refs, refs) == 0.0


@pytest.mark.parametrize("name,precision", [("cnn-mnist", "highest"),
                                            ("unet-deepglobe", "default")])
def test_reference_runs_at_the_configurations_matmul_precision(name, precision):
    # a file that states none keeps the reference at the highest precision
    assert matmul_precision(load_json(BENCH / "configs" / f"{name}.json")) \
        == precision
