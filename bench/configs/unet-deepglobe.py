"""unet-deepglobe: the paper's U-Net on DeepGlobe-like road tiles, split
non-IID by orbit.

Holds what belongs to this configuration alone: its tiles and road masks
from the seed, its weights from the seed (in the layout the program's
``apply_unet`` reads), the program's model functions, the plain reference
forward pass, and the FLOP count of one tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref


def _levels(cfg: dict) -> list:
    """Output channels of each encoder block."""
    return [cfg["base"] * 2 ** d for d in range(cfg["depth"])]


def forward_flops_per_sample(cfg: dict) -> int:
    """Forward FLOPs of one tile, by the file's ``flop_convention``."""
    h, w, c = cfg["input_shape"]
    k2 = cfg["kernel_size"] ** 2
    flops = 0
    for out in _levels(cfg):
        flops += 2 * h * w * k2 * (c * out + out * out)   # two SAME convs
        h, w, c = h // 2, w // 2, out
    flops += 2 * h * w * k2 * c * 2 * c                   # bottleneck
    c *= 2
    for out in reversed(_levels(cfg)):
        flops += 2 * h * w * 4 * c * out                  # 2x2 transposed
        h, w = 2 * h, 2 * w
        flops += 2 * h * w * k2 * 2 * out * out           # after the concat
        c = out
    flops += 2 * h * w * c * cfg["num_classes"]           # 1x1 head
    return flops


def _road_counts(cfg: dict, num_planes: int, sats_per_plane: int):
    """Roads per tile of each client (plane-major) and of each test tile:
    the first ``first_group_plane_share`` of the orbits see the first entry
    of ``roads_per_tile``, the others the second; the test tiles hold the
    same share of the first, spread evenly through the set.  The same for
    every seed."""
    m = cfg["train_samples"] // (num_planes * sats_per_plane)
    dense, sparse = cfg["roads_per_tile"]
    n_first = round(cfg["first_group_plane_share"] * num_planes)
    train = np.repeat([dense if p < n_first else sparse
                       for p in range(num_planes)], sats_per_plane * m)
    n_test = cfg["test_samples"]
    n_dense = round(cfg["first_group_plane_share"] * n_test)
    i = np.arange(n_test)
    test = np.where((i + 1) * n_dense // n_test > i * n_dense // n_test,
                    dense, sparse)
    return train.astype(np.int32), test.astype(np.int32)


def _tiles(key: jax.Array, roads: jax.Array, cfg: dict):
    """(tiles, road masks) for tiles with ``roads`` straight roads each:
    a smooth ground texture, roads as grey bands of random direction,
    position and width, and pixel noise."""
    h, w, _ = cfg["input_shape"]
    n, r = roads.shape[0], max(cfg["roads_per_tile"])
    k = jax.random.split(key, 7)
    yy, xx = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    theta = jax.random.uniform(k[0], (n, r, 1, 1), maxval=np.pi)
    py = jax.random.uniform(k[1], (n, r, 1, 1), minval=0.2, maxval=0.8) * h
    px = jax.random.uniform(k[2], (n, r, 1, 1), minval=0.2, maxval=0.8) * w
    width = jax.random.uniform(k[3], (n, r, 1, 1), minval=2.0, maxval=6.0) \
        * h / 128
    dist = jnp.abs((xx - px) * jnp.sin(theta) - (yy - py) * jnp.cos(theta))
    live = jnp.arange(r)[None, :, None, None] < roads[:, None, None, None]
    mask = jnp.any(live & (dist < width / 2), axis=1)
    freq = jax.random.uniform(k[4], (n, 2, 1, 1), minval=0.5, maxval=3.0)
    phase = jax.random.uniform(k[5], (n, 2, 1, 1), maxval=2 * np.pi)
    field = jnp.sin(2 * np.pi * freq[:, 0] * xx / w + phase[:, 0]) \
        * jnp.cos(2 * np.pi * freq[:, 1] * yy / h + phase[:, 1])
    ground = jnp.asarray([0.30, 0.38, 0.22]) + 0.12 * field[..., None]
    road = jnp.asarray([0.58, 0.56, 0.54])
    x = jnp.where(mask[..., None], road, ground) \
        + 0.08 * jax.random.normal(k[6], (n, h, w, 3))
    return x.astype(jnp.float32), mask.astype(jnp.int32)


def make_data(cfg: dict, seed_key: jax.Array, num_planes: int,
              sats_per_plane: int):
    """Clients' (tiles, masks) in plane-major order and the test set, on
    the host.  Road directions, positions and widths, textures and noise
    come from ``seed_key``; sizes and roads per tile do not depend on it."""
    train_roads, test_roads = _road_counts(cfg, num_planes, sats_per_plane)

    @jax.jit
    def draw(key, train_roads, test_roads):
        k_train, k_test = jax.random.split(key)
        return _tiles(k_train, train_roads, cfg) + _tiles(k_test, test_roads,
                                                          cfg)

    x, y, tx, ty = jax.device_get(draw(seed_key, train_roads, test_roads))
    n = num_planes * sats_per_plane
    x = x.reshape((n, -1) + x.shape[1:])
    y = y.reshape((n, -1) + y.shape[1:])
    return [(x[i], y[i]) for i in range(n)], (tx, ty)


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Uniform fan-in init, in the program's U-Net parameter layout."""
    keys = iter(jax.random.split(key, 4 * cfg["depth"] + 2))
    ks = cfg["kernel_size"]

    def conv(cin, cout, k=ks):
        s = float(np.sqrt(1.0 / (cin * k * k)))
        return {"w": jax.random.uniform(next(keys), (k, k, cin, cout),
                                        jnp.float32, -s, s),
                "b": jnp.zeros((cout,), jnp.float32)}

    params = {"down": [], "up": []}
    c = cfg["input_shape"][-1]
    for out in _levels(cfg):
        params["down"].append({"c1": conv(c, out), "c2": conv(out, out)})
        c = out
    params["bottleneck"] = {"c1": conv(c, 2 * c)}
    c *= 2
    for out in reversed(_levels(cfg)):
        params["up"].append({"t": conv(c, out, 2), "c1": conv(2 * out, out)})
        c = out
    params["head"] = conv(c, cfg["num_classes"], 1)
    return params


def program_model(cfg: dict):
    """The system under test: the program's U-Net and per-pixel loss."""
    from repro.core.fltask import cross_entropy_loss
    from repro.models.cnn import apply_unet

    return apply_unet, cross_entropy_loss


def reference_apply(params: dict, x: jax.Array, dtype) -> jax.Array:
    """Plain forward pass: two SAME convolutions with ReLU per encoder
    block, 2x2 max-pools, one convolution in the bottleneck, and per decoder
    block a 2x2 transposed convolution, the skip concatenated after it and
    one convolution; a 1x1 head.  Computed in ``dtype`` (float32 at the
    highest precision, or the control's lower precision)."""
    def conv(p, x):
        return ref.conv(x, p["w"], p["b"], dtype)

    x = x.astype(dtype)
    skips = []
    for blk in params["down"]:
        x = jax.nn.relu(conv(blk["c1"], x))
        x = jax.nn.relu(conv(blk["c2"], x))
        skips.append(x)
        x = ref.max_pool2(x)
    x = jax.nn.relu(conv(params["bottleneck"]["c1"], x))
    for blk, skip in zip(params["up"], reversed(skips)):
        x = ref.conv_transpose2(x, blk["t"]["w"], blk["t"]["b"], dtype)
        x = jax.nn.relu(conv(blk["c1"], jnp.concatenate([x, skip], axis=-1)))
    return conv(params["head"], x)
