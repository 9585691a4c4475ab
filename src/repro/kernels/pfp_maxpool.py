"""Specialized vectorized PFP max-pool kernel (k=2, stride 2) — Clark maxes.

TPU adaptation of the paper's §6.2 "Vectorized Max Pool k=2": instead of a
generic reduction (slow in TVM and untunable, Table 3), the wrapper slices
the NHWC input into its four 2x2 phases once (XLA strided slices), and the
kernel runs a pure-elementwise tournament of three Clark pairwise maxes —
fully VPU-vectorized with zero shuffles inside the kernel.

Consumes VAR, emits VAR (paper: pooling layers keep variances).

(block_rows, block_cols) tile the flattened (N*Ho*Wo, C) phase arrays;
the autotuner (repro.tuning) overrides the defaults through
`ops.pfp_maxpool2d`'s schedule argument.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.gaussian import VAR_EPS
from repro.kernels.pfp_activations import erf

_SQRT_2 = math.sqrt(2.0)
_SQRT_2PI = math.sqrt(2.0 * math.pi)


def _clark(mu_a, var_a, mu_b, var_b):
    theta = jnp.sqrt(jnp.maximum(var_a + var_b, VAR_EPS))
    alpha = (mu_a - mu_b) / theta
    cdf_a = 0.5 * (1.0 + erf(alpha / _SQRT_2))
    cdf_b = 1.0 - cdf_a
    pdf = jnp.exp(-0.5 * jnp.square(alpha)) / _SQRT_2PI
    mean = mu_a * cdf_a + mu_b * cdf_b + theta * pdf
    srm = (
        (jnp.square(mu_a) + var_a) * cdf_a
        + (jnp.square(mu_b) + var_b) * cdf_b
        + (mu_a + mu_b) * theta * pdf
    )
    det = (var_a + var_b) <= VAR_EPS
    det_mean = jnp.maximum(mu_a, mu_b)
    mean = jnp.where(det, det_mean, mean)
    var = jnp.where(det, 0.0, jnp.maximum(srm - jnp.square(mean), 0.0))
    return mean, var


def _pool_kernel(m00, v00, m01, v01, m10, v10, m11, v11, mu_out, var_out):
    # Tournament: reduce the two W-phases, then the two H-phases.
    mw0, vw0 = _clark(m00[...], v00[...], m01[...], v01[...])
    mw1, vw1 = _clark(m10[...], v10[...], m11[...], v11[...])
    mean, var = _clark(mw0, vw0, mw1, vw1)
    mu_out[...] = mean
    var_out[...] = var


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def pfp_maxpool2d_pallas(mu, var, *, block_rows: int = 256,
                         block_cols: int = 128, interpret: bool = False):
    """2x2/2 PFP max pool on NHWC (mu, var). Returns NHoWoC (mu, var)."""
    n, h, w, c = mu.shape
    assert h % 2 == 0 and w % 2 == 0, (h, w)
    ho, wo = h // 2, w // 2

    def phases(a):
        return (
            a[:, 0::2, 0::2, :], a[:, 0::2, 1::2, :],
            a[:, 1::2, 0::2, :], a[:, 1::2, 1::2, :],
        )

    def flat(a):
        return a.reshape(n * ho * wo, c)

    rows = n * ho * wo
    args = [flat(p).astype(jnp.float32) for pair in zip(phases(mu), phases(var)) for p in pair]

    bm = min(block_rows, rows)
    bn = min(block_cols, c)
    # Pad to block multiples (tiny images in the paper's models).
    pm = (-rows) % bm
    pn = (-c) % bn
    if pm or pn:
        args = [jnp.pad(a, ((0, pm), (0, pn))) for a in args]
    rows_p, c_p = rows + pm, c + pn

    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    fn = pl.pallas_call(
        _pool_kernel,
        grid=(rows_p // bm, c_p // bn),
        in_specs=[spec] * 8,
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, c_p), jnp.float32),
            jax.ShapeDtypeStruct((rows_p, c_p), jnp.float32),
        ],
        interpret=interpret,
    )
    mu_o, var_o = fn(*args)
    mu_o = mu_o[:rows, :c].reshape(n, ho, wo, c)
    var_o = var_o[:rows, :c].reshape(n, ho, wo, c)
    return mu_o, var_o
