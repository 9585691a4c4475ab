"""Pieces the references share: matmuls at a stated precision (or with
operands rounded to fp8, the control's precision), the Gaussian moment
rules, and the sampled-logit uncertainty of paper Eq. 1-3 and 11."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "bf16")
# Largest finite value of IEEE-style e4m3 (``reduce_precision`` keeps the
# top exponent for inf), so a tensor scaled to it never overflows.
FP8_MAX = 240.0
VAR_EPS = 1e-12          # floor of a variance that feeds sqrt or a division
ENT_EPS = 1e-12          # inside the log of an entropy


class Numerics:
    """How a reference computes its products.

    ``precision`` names the matmul precision of float32 operands:
    ``highest`` is full float32; ``high`` is three bf16 passes (each
    operand split into a bf16 high part and a bf16 remainder, the
    remainder-times-remainder product dropped); ``bf16`` one pass on
    operands rounded to bf16. The passes are written out, each product
    taken in full float32 on operands that bf16 holds exactly, so that they
    mean the same on every backend. ``fp8`` rounds both operands of
    every product to float8 e4m3 first (subnormals flushed to zero), each
    tensor scaled by its largest magnitude (the control of a bfloat16
    configuration).

    Rounding is ``lax.reduce_precision``, not a cast there and back: the
    TPU compiler may drop an f32 -> bf16 -> f32 round trip as excess
    precision (three written-out passes then read like one), and it keeps
    ``reduce_precision`` on every backend."""

    def __init__(self, precision: str = "highest", fp8: bool = False):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.passes = {"highest": 0, "high": 3, "bf16": 1}[precision]
        self.precision = jax.lax.Precision.HIGHEST
        self.fp8 = fp8

    def q(self, x):
        if not self.fp8:
            return x
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                        mantissa_bits=3) * scale

    def product(self, fn, a, b):
        """``fn(a, b, precision)`` (a bilinear op: matmul, conv) at this
        numerics."""
        a, b = self.q(a), self.q(b)
        p = self.precision
        if not self.passes:
            return fn(a, b, p)
        bf = lambda x: jax.lax.reduce_precision(  # noqa: E731
            x, exponent_bits=8, mantissa_bits=7)
        ah, bh = bf(a), bf(b)
        if self.passes == 1:
            return fn(ah, bh, p)
        return fn(ah, bh, p) + (fn(ah, bf(b - bh), p) + fn(bf(a - ah), bh, p))

    def einsum(self, spec, a, b):
        return self.product(lambda x, y, p: jnp.einsum(
            spec, x, y, precision=p, preferred_element_type=jnp.float32),
            a, b)


def dense(num: Numerics, x_mu, x_var, w_mu, w_var, spec="...k,kn->...n",
          formulation: str = "var"):
    """Gaussian dense layer (paper Eq. 4): mean x_mu w_mu. Variance in the
    mean/variance form (Eq. 7) x_var (w_mu^2 + w_var) + x_mu^2 w_var, or
    in the second-raw-moment form (Eq. 12) E[x^2] E[w^2] - x_mu^2 w_mu^2.
    ``x_var`` None is a deterministic input (Eq. 13: x^2 w_var)."""
    mu = num.einsum(spec, x_mu, w_mu)
    if x_var is None:
        return mu, num.einsum(spec, jnp.square(x_mu), w_var)
    if formulation == "srm":
        w2 = jnp.square(w_mu)
        x2 = jnp.square(x_mu)
        return mu, num.einsum(spec, x2 + x_var, w2 + w_var) - \
            num.einsum(spec, x2, w2)
    var = num.einsum(spec, jnp.square(x_mu), w_var)
    return mu, var + num.einsum(spec, x_var, jnp.square(w_mu) + w_var)


def normal_cdf(x):
    return 0.5 * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def normal_pdf(x):
    return jnp.exp(-0.5 * jnp.square(x)) / math.sqrt(2.0 * math.pi)


def gauss_hermite(f, mu, var, nodes: int = 8):
    """E[f(X)], E[f(X)^2] of X ~ N(mu, var) by Gauss-Hermite quadrature
    with ``nodes`` nodes (the PFP rule for smooth activations)."""
    x, w = np.polynomial.hermite.hermgauss(nodes)
    x = jnp.asarray(x, jnp.float32)
    w = jnp.asarray(w / math.sqrt(math.pi), jnp.float32)
    pts = mu[..., None] + jnp.sqrt(2.0 * jnp.maximum(var, 0.0))[..., None] * x
    fx = f(pts)
    return jnp.sum(fx * w, -1), jnp.sum(jnp.square(fx) * w, -1)


def relu(mu, var):
    """Exact mean and variance of ReLU(X), X ~ N(mu, var) (paper Eq. 8, 9);
    a point mass where var <= VAR_EPS."""
    v = jnp.maximum(var, VAR_EPS)
    s = jnp.sqrt(v)
    cdf = normal_cdf(mu / s)
    pdf = s * normal_pdf(mu / s)
    m = mu * cdf + pdf
    srm = (v + jnp.square(mu)) * cdf + mu * pdf
    det = var <= VAR_EPS
    m = jnp.where(det, jnp.maximum(mu, 0.0), m)
    srm = jnp.where(det, jnp.square(jnp.maximum(mu, 0.0)),
                    jnp.maximum(srm, 0.0))
    return m, srm - jnp.square(m)


def clark_max(m1, v1, m2, v2):
    """Mean and variance of max(X, Y) for independent Gaussians (Clark
    1961); the larger mean where both are point masses."""
    t2 = v1 + v2
    t = jnp.sqrt(jnp.maximum(t2, VAR_EPS))
    a = (m1 - m2) / t
    c1, c2, p = normal_cdf(a), normal_cdf(-a), normal_pdf(a)
    m = m1 * c1 + m2 * c2 + t * p
    srm = (jnp.square(m1) + v1) * c1 + (jnp.square(m2) + v2) * c2 + \
        (m1 + m2) * t * p
    det = t2 <= VAR_EPS
    m = jnp.where(det, jnp.maximum(m1, m2), m)
    srm = jnp.where(det, jnp.square(m), srm)
    return m, jnp.maximum(srm - jnp.square(m), 0.0)


def sampled_uncertainty(mean, var, eps):
    """Paper Eq. 11 then Eq. 1-3: logits mean + eps sqrt(var) for the
    given standard-normal ``eps`` (N, ..., V); returns the argmax of the
    mean probabilities, the entropy of the mean and the mutual
    information."""
    samples = mean + eps * jnp.sqrt(jnp.maximum(var, 0.0))
    p = jax.nn.softmax(samples, -1)
    pm = jnp.mean(p, 0)
    ent = lambda q: -jnp.sum(q * jnp.log(q + ENT_EPS), -1)  # noqa: E731
    total = ent(pm)
    return jnp.argmax(pm, -1), total, total - jnp.mean(ent(p), 0)


def rel_err(got, want) -> float:
    """Largest absolute difference over the reference's largest magnitude."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) /
                 max(float(np.max(np.abs(want))), 1e-30))
