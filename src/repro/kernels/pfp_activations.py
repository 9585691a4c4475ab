"""Fused moment-matched activation Pallas kernels (VPU elementwise).

The paper observes (Fig. 6, Table 4) that "trivial" operators like ReLU
become hot under PFP: Eq. 8/9 needs erf + exp per element, twice. On TPU
these are VPU transcendentals; the kernel fuses the mean and SRM outputs so
(mu, var) tiles are read from HBM once and both outputs are written once —
the joint-operator principle applied to the elementwise case.

GELU/SiLU use unrolled Gauss–Hermite quadrature: NODES fused multiply-adds
per element with compile-time constants — no (.., nodes) intermediate is
materialized, which keeps VMEM pressure at 2 tiles in / 2 tiles out.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.gaussian import VAR_EPS

_SQRT_2 = math.sqrt(2.0)
_SQRT_2PI = math.sqrt(2.0 * math.pi)

# erf(x) ~= x * P(x^2) / Q(x^2) on [-4, 4], the fp32 rational approximation
# XLA and Eigen use for ``erf`` (within a few ulp; |x| > 4 rounds to +-1 in
# fp32). Mosaic has no lowering for ``lax.erf``, so the kernels evaluate
# this polynomial pair themselves: only mul/add/div/clip, all VPU ops.
_ERF_ALPHA = (-2.72614225801306e-10, 2.77068142495902e-08,
              -2.10102402082508e-06, -5.69250639462346e-05,
              -7.34990630326855e-04, -2.95459980854025e-03,
              -1.60960333262415e-02)
_ERF_BETA = (-1.45660718464996e-05, -2.13374055278905e-04,
             -1.68282697438203e-03, -7.37332916720468e-03,
             -1.42647390514189e-02)


def erf(x):
    """fp32 erf from operations every Pallas TPU kernel can lower."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.full_like(x2, _ERF_ALPHA[0])
    for c in _ERF_ALPHA[1:]:
        p = p * x2 + c
    q = jnp.full_like(x2, _ERF_BETA[0])
    for c in _ERF_BETA[1:]:
        q = q * x2 + c
    # At the clamp the ratio overshoots 1 by an ulp: keep erf in [-1, 1].
    return jnp.clip(x * p / q, -1.0, 1.0)


def _relu_moments(mu, var):
    safe_var = jnp.maximum(var, VAR_EPS)
    std = jnp.sqrt(safe_var)
    cdf = 0.5 * (1.0 + erf(mu / (std * _SQRT_2)))
    pdf = std * jnp.exp(-0.5 * jnp.square(mu) / safe_var) / _SQRT_2PI
    mean_out = mu * cdf + pdf                                   # Eq. (8)
    srm_out = (safe_var + jnp.square(mu)) * cdf + mu * pdf      # Eq. (9)
    det = var <= VAR_EPS
    det_mean = jnp.maximum(mu, 0.0)
    mean_out = jnp.where(det, det_mean, mean_out)
    srm_out = jnp.where(det, jnp.square(det_mean), jnp.maximum(srm_out, 0.0))
    return mean_out, srm_out


def _make_gh_moments(fn, num_nodes: int):
    nodes, weights = np.polynomial.hermite.hermgauss(num_nodes)
    weights = weights / math.sqrt(math.pi)

    def moments(mu, var):
        scale = jnp.sqrt(jnp.maximum(var, 0.0)) * _SQRT_2
        acc_m = jnp.zeros_like(mu)
        acc_s = jnp.zeros_like(mu)
        for xi, wi in zip(nodes, weights):  # unrolled: NODES FMAs on the VPU
            fx = fn(mu + scale * float(xi))
            acc_m = acc_m + float(wi) * fx
            acc_s = acc_s + float(wi) * jnp.square(fx)
        return acc_m, acc_s

    return moments


# In-kernel moment-matching bodies: fn(mu, var) -> (mean, srm) in fp32.
# Shared with the fused norm kernels (pfp_norms.py activation epilogues).
MOMENT_FNS = {
    "relu": _relu_moments,
    "gelu": _make_gh_moments(jax.nn.gelu, 8),
    "silu": _make_gh_moments(jax.nn.silu, 8),
    "tanh": _make_gh_moments(jnp.tanh, 8),
    "sigmoid": _make_gh_moments(jax.nn.sigmoid, 8),
}


def _make_kernel(kind: str):
    def kernel(mu_ref, var_ref, mu_out_ref, srm_out_ref):
        m, s = MOMENT_FNS[kind](
            mu_ref[...].astype(jnp.float32), var_ref[...].astype(jnp.float32)
        )
        mu_out_ref[...] = m
        srm_out_ref[...] = s

    return kernel


_KERNELS = {kind: _make_kernel(kind) for kind in MOMENT_FNS}


def _glu_product_kernel(mu_a_ref, srm_a_ref, mu_b_ref, srm_b_ref,
                        mu_out_ref, srm_out_ref):
    """Exact product of independent Gaussians in SRM representation.

    The representation-contract payoff (paper §5): two elementwise
    multiplies per element, one fused HBM round-trip for both outputs.
    """
    mu_out_ref[...] = (mu_a_ref[...].astype(jnp.float32)
                       * mu_b_ref[...].astype(jnp.float32))
    srm_out_ref[...] = (srm_a_ref[...].astype(jnp.float32)
                        * srm_b_ref[...].astype(jnp.float32))


@functools.partial(
    jax.jit, static_argnames=("kind", "block_rows", "block_cols", "interpret")
)
def pfp_activation_pallas(
    mu,
    var,
    *,
    kind: str = "relu",
    block_rows: int = 256,
    block_cols: int = 512,
    interpret: bool = False,
):
    """Fused (mu, var) -> (mu, srm) activation. Expects 2D padded input."""
    m, n = mu.shape
    bm, bn = min(block_rows, m), min(block_cols, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    fn = pl.pallas_call(
        _KERNELS[kind],
        grid=(m // bm, n // bn),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        interpret=interpret,
    )
    return fn(mu, var)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "block_cols", "interpret")
)
def pfp_glu_pallas(
    mu_a,
    srm_a,
    mu_b,
    srm_b,
    *,
    block_rows: int = 256,
    block_cols: int = 512,
    interpret: bool = False,
):
    """Fused SRM gated product: (mu, srm) x (mu, srm) -> (mu, srm), 2D padded."""
    m, n = mu_a.shape
    bm, bn = min(block_rows, m), min(block_cols, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    fn = pl.pallas_call(
        _glu_product_kernel,
        grid=(m // bm, n // bn),
        in_specs=[spec] * 4,
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        interpret=interpret,
    )
    return fn(mu_a, srm_a, mu_b, srm_b)
