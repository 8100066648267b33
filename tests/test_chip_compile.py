"""The main path's Pallas kernels, compiled for a described TPU v5e at
real widths.

Nothing runs: the TPU compiler that ships with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(tiles not aligned to the hardware layout, primitives Mosaic cannot
lower), which interpret mode never shows.  Each test asserts that the
Mosaic kernel is in the compiled program.  The topology is described
inside a module fixture, so that only the test worker that runs this
file loads the TPU compiler.
"""
import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.aggregate import aggregate_flat
from repro.kernels.flash import flash_attention
from repro.kernels.ssd import ssd_scan
from repro.models.cnn import init_cnn

CNN_PARAMS = 421_642        # the paper's deep CNN at its full width


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _persistent_cache_off():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    with _persistent_cache_off():
        return jax.jit(fn).lower(*args).compile().as_text()


def test_cnn_width_is_the_papers():
    shapes = jax.eval_shape(init_cnn, jax.random.PRNGKey(0))
    n = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert n == CNN_PARAMS


@pytest.mark.parametrize("k,n,dtype", [
    (8, CNN_PARAMS, jnp.float32),    # one plane's clients at the sink
    (5, CNN_PARAMS, jnp.float32),    # the five plane partials at the GS
    (5, 1000, jnp.bfloat16),         # ragged N below one block
])
def test_aggregate_flat_compiles(one_chip, k, n, dtype):
    x = jax.ShapeDtypeStruct((k, n), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(aggregate_flat, x, w)


def test_flash_attention_compiles(one_chip):
    cfg = get_config("phi3-medium-14b")
    seq, d = 1024, cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((1, seq, cfg.num_heads, d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, cfg.num_kv_heads, d), jnp.bfloat16,
                              sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(flash_attention, q, kv, kv)


def test_ssd_scan_compiles(one_chip):
    cfg = get_config("mamba2-780m")
    ssm = cfg.ssm
    heads = ssm.expand * cfg.d_model // ssm.head_dim      # 48
    b, s = 1, 4 * ssm.chunk_size

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    x = sds((b, s, heads, ssm.head_dim))
    dt = sds((b, s, heads))
    a = sds((heads,))
    bc = sds((b, s, ssm.num_groups, ssm.state_dim))
    assert "tpu_custom_call" in _compiled_text(
        lambda *args: ssd_scan(*args, chunk=ssm.chunk_size), x, dt, a, bc, bc
    )
