"""Seeded weights, made the same way for the system under test and for the
plain references.

Every Gaussian weight is named by a *role* (``attn.wq.w``, ``embed.table``)
and a layer index. Its mean is ``scale * truncated_normal(-2, 2)`` drawn
from a key folded from the run's seed, the role and the layer; its
variance is ``sigma**2``. The system module lays these out in the
program's parameter tree (``{'mu', 'srm'}`` leaves); a reference asks for
the same role and layer and gets ``(mu, var)``. Neither takes weights
from the other.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """Key of a run's seed (any whole number below 2**64)."""
    return jax.random.PRNGKey(seed)


def role_key(key, role: str, layer: int = 0):
    key = jax.random.fold_in(key, zlib.crc32(role.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(key, layer)


def weight_mean(key, role: str, layer: int, shape, scale: float):
    """Mean of one Gaussian weight: fp32, truncated at two deviations; a
    bias (role ending ``.b``) has mean 0."""
    if role.endswith(".b"):
        return jnp.zeros(tuple(shape), jnp.float32)
    return scale * jax.random.truncated_normal(
        role_key(key, role, layer), -2.0, 2.0, tuple(shape), jnp.float32)


def fan_in_scale(role: str, shape) -> float:
    """Scale of a weight's mean: 1 for embedding tables, else 1/sqrt(fan
    in), the fan in being every axis but the last (a conv's kh*kw*cin)."""
    if role.startswith("embed"):
        return 1.0
    fan_in = 1
    for n in shape[:-1]:
        fan_in *= int(n)
    return fan_in ** -0.5


def logit_sample_key(seed: int):
    """Base key of a classifier's logit samples (paper Eq. 11); request i
    folds in i."""
    return role_key(seed_key(seed), "logit_samples")
