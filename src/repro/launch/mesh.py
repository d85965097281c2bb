"""Production meshes.

``make_production_mesh()`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS before any jax import to fake 512 host devices.

Mesh geometry (TPU v5e target): 16x16 = 256 chips per pod; the multi-pod
mesh adds a leading "pod" axis (2 pods = 512 chips).  Axis meaning:
  pod    slow inter-pod links (DCN) — data parallelism only
  data   intra-pod ICI — data parallelism / FSDP
  model  intra-pod ICI — tensor/expert parallelism
"""
from __future__ import annotations

import math

import jax


def make_mesh(shape, axes, devices=None):
    n = math.prod(shape)
    devices = devices if devices is not None else jax.devices()
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)} "
                         "(did you set XLA_FLAGS before importing jax?)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Single-host debugging mesh (1 device)."""
    return make_mesh((1, model), ("data", "model"))
