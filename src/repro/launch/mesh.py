"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialisation.

Topology (TPU v5e pods):
  single-pod: (data=16, model=16)            = 256 chips
  multi-pod:  (pod=2, data=16, model=16)     = 512 chips

The `pod` axis is pure data parallelism: gradients cross pods once per
step (training); serving shards request batches across pods with no
cross-pod collectives.  Scaling to N pods adds no new collective patterns.
"""
from __future__ import annotations

from typing import Tuple

import jax

# the chip the production meshes stand for (a key of roofline.PEAKS)
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """Tiny mesh over the actually-available devices (CI-scale tests)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except `model`)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def tp_axis(mesh) -> str:
    return "model"
