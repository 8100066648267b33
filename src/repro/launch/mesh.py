"""Production meshes (TPU v5e).

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE any jax
import; smoke tests and benches see the single real CPU device).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_fedleo_mesh(*, num_orbits: int = 4, multi_pod: bool = False):
    """Mesh for the FedLEO hierarchical training step (DESIGN.md §3).

    The leading ``orbit`` axis carries the per-orbit model replicas
    (paper: orbital planes); gradient sync during local steps stays
    inside ("data", "model"); the scheduled sink->GS aggregation is the
    only collective crossing ``orbit``.  On the multi-pod mesh the orbit
    axis is the pod axis (2 orbits of 256 chips); single-pod it splits
    the data axis (num_orbits x (16/num_orbits) x 16).
    """
    if multi_pod:
        shape = (2, 16, 16)
    else:
        assert 16 % num_orbits == 0, "orbit count must divide the data axis"
        shape = (num_orbits, 16 // num_orbits, 16)
    return jax.make_mesh(
        shape, ("orbit", "data", "model"), axis_types=(AxisType.Auto,) * 3
    )


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes carrying the global batch (and the FSDP param dim)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
