"""Production mesh construction.

Single pod : (16, 16)    axes ('data', 'model')   = 256 chips (v5e pod)
Multi pod  : (2, 16, 16) axes ('pod', 'data', 'model') = 512 chips

Axis roles:
  pod   — pure data parallelism across pods (slow DCN links; gradients
          reduced hierarchically, parameters NOT sharded across pods)
  data  — FSDP: batch AND parameter/optimizer sharding (fast ICI)
  model — TP/EP/SP: attention heads & FFN width, experts, long-seq caches

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import numpy as np

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    dev = np.array(devices[:need]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


def make_mesh(shape, axes):
    """A mesh over the first prod(shape) devices, e.g. (1, 1) on one chip
    or (2, 4) on 8 host devices."""
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise ValueError(
            f"mesh {tuple(shape)} over axes {tuple(axes)} needs {need} "
            f"devices, but {jax.default_backend()} has {len(devices)}")
    dev = np.array(devices[:need]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


def dp_axes(mesh) -> tuple:
    """Axes that shard the batch (pure DP + FSDP)."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def fsdp_axis(mesh) -> str:
    """Axis that shards parameters/optimizer state (within-pod only)."""
    return "data"


def axis_size(mesh, name) -> int:
    return mesh.shape[name]
