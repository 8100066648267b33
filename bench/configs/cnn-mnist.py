"""cnn-mnist: the paper's deep CNN on MNIST-like data, split non-IID by orbit.

Holds what belongs to this configuration alone: its data from the seed, its
weights from the seed (in the layout the program's ``apply_cnn`` reads), the
program's model functions, the plain reference forward pass, and the FLOP
count of one sample.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref

PATTERN_KEY = 1234       # fixes the class fields: the same "world" for every seed


def forward_flops_per_sample(cfg: dict) -> int:
    """Forward FLOPs of one sample, by the file's ``flop_convention``."""
    h, w, c = cfg["input_shape"]
    k2 = cfg["kernel_size"] ** 2
    flops = 0
    for out in cfg["widths"]:
        flops += 2 * h * w * out * c * k2          # SAME conv: every tap
        h, w, c = h // cfg["pool"], w // cfg["pool"], out
    flops += 2 * h * w * c * cfg["hidden"]
    flops += 2 * cfg["hidden"] * cfg["num_classes"]
    return flops


def client_sizes(cfg: dict, num_clients: int) -> list:
    return [cfg["train_samples"] // num_clients] * num_clients


def _class_fields(cfg: dict) -> jax.Array:
    """(classes, H, W, C) smooth fields, one per class, fixed by PATTERN_KEY."""
    h, w, c = cfg["input_shape"]
    n = cfg["num_classes"]
    k = jax.random.split(jax.random.PRNGKey(PATTERN_KEY), 3)
    f = jax.random.uniform(k[0], (n, c, 2), minval=1.0, maxval=5.0)
    ph = jax.random.uniform(k[1], (n, c, 2), maxval=2 * np.pi)
    amp = jax.random.uniform(k[2], (n, c), minval=0.6, maxval=1.0)
    yy, xx = jnp.meshgrid(jnp.linspace(0, 1, h), jnp.linspace(0, 1, w),
                          indexing="ij")
    field = amp[:, :, None, None] * (
        jnp.sin(2 * np.pi * f[:, :, 0, None, None] * xx + ph[:, :, 0, None, None])
        * jnp.cos(2 * np.pi * f[:, :, 1, None, None] * yy + ph[:, :, 1, None, None])
    )
    return jnp.transpose(field, (0, 2, 3, 1))


def _labels(cfg: dict, num_planes: int, sats_per_plane: int) -> np.ndarray:
    """(clients, m) labels before shuffling: the first
    ``first_group_plane_share`` of the orbits hold classes
    [0, first_group_classes), the others the rest, balanced within each
    shard.  The same for every seed."""
    m = cfg["train_samples"] // (num_planes * sats_per_plane)
    first = np.arange(cfg["first_group_classes"])
    second = np.arange(cfg["first_group_classes"], cfg["num_classes"])
    rows = []
    n_first = round(cfg["first_group_plane_share"] * num_planes)
    for p in range(num_planes):
        classes = first if p < n_first else second
        rows += [np.resize(classes, m)] * sats_per_plane
    return np.stack(rows).astype(np.int32)


def make_data(cfg: dict, seed_key: jax.Array, num_planes: int,
              sats_per_plane: int):
    """Clients' (x, y) in plane-major order and the test set, on the host.

    The shuffles and the noise come from ``seed_key``; sizes and class
    counts do not depend on it.
    """
    labels = _labels(cfg, num_planes, sats_per_plane)
    n_test = cfg["test_samples"]
    test_labels = np.resize(np.arange(cfg["num_classes"]), n_test).astype(np.int32)

    @jax.jit
    def draw(key, labels, test_labels):
        k_perm, k_noise, k_tperm, k_tnoise = jax.random.split(key, 4)
        fields = _class_fields(cfg)
        perm = jax.vmap(jax.random.permutation)(
            jax.random.split(k_perm, labels.shape[0]), labels)
        x = fields[perm] + 0.35 * jax.random.normal(
            k_noise, perm.shape + fields.shape[1:])
        ty = jax.random.permutation(k_tperm, test_labels)
        tx = fields[ty] + 0.35 * jax.random.normal(
            k_tnoise, ty.shape + fields.shape[1:])
        return x, perm, tx, ty

    x, y, tx, ty = jax.device_get(draw(seed_key, labels, test_labels))
    clients = [(x[i], y[i]) for i in range(x.shape[0])]
    return clients, (tx, ty)


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Uniform fan-in init, in the program's CNN parameter layout."""
    h, w, c = cfg["input_shape"]
    ks = cfg["kernel_size"]
    keys = jax.random.split(key, len(cfg["widths"]) + 2)

    def u(k, shape, fan_in):
        s = float(np.sqrt(1.0 / fan_in))
        return jax.random.uniform(k, shape, jnp.float32, -s, s)

    params = {"conv": []}
    for i, out in enumerate(cfg["widths"]):
        params["conv"].append({"w": u(keys[i], (ks, ks, c, out), c * ks * ks),
                               "b": jnp.zeros((out,), jnp.float32)})
        h, w, c = h // cfg["pool"], w // cfg["pool"], out
    flat = h * w * c
    params["fc1"] = {"w": u(keys[-2], (flat, cfg["hidden"]), flat),
                     "b": jnp.zeros((cfg["hidden"],), jnp.float32)}
    params["fc2"] = {"w": u(keys[-1], (cfg["hidden"], cfg["num_classes"]),
                            cfg["hidden"]),
                     "b": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return params


def program_model(cfg: dict):
    """The system under test: the program's CNN and loss."""
    from repro.core.fltask import cross_entropy_loss
    from repro.models.cnn import apply_cnn

    return apply_cnn, cross_entropy_loss


def reference_apply(params: dict, x: jax.Array, dtype) -> jax.Array:
    """Plain forward pass: SAME convolutions, ReLU, 2x2 max-pool, two dense
    layers, computed in ``dtype`` (float32 at the highest precision, or the
    control's lower precision)."""
    x = x.astype(dtype)
    for p in params["conv"]:
        x = jax.nn.relu(ref.conv(x, p["w"], p["b"], dtype))
        x = ref.max_pool2(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(ref.dense(x, params["fc1"]["w"], params["fc1"]["b"], dtype))
    return ref.dense(x, params["fc2"]["w"], params["fc2"]["b"], dtype)
