"""Plain float32 PFP forward of the paper's LeNet-5: conv 5x5 (SAME) ->
ReLU -> 2x2 max-pool -> conv 5x5 (SAME) -> ReLU -> 2x2 max-pool ->
flatten (NHWC order) -> dense -> ReLU -> dense -> ReLU -> dense, every
weight and bias Gaussian.

The moment rules: convs and dense layers by paper Eq. 4 + 7 (the first
conv sees deterministic pixels, Eq. 13); ReLU by its exact moments (Eq.
8, 9); max-pool as the paper's tournament of Clark maxes, pairs along the
width first, then along the height; biases add their mean and variance.
Then the logits are sampled (Eq. 11) with the run's normals and reduced to
entropy and mutual information (Eq. 1-3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as wlib
from bench.reference import common

ROLES = ("conv0", "conv1", "dense0", "dense1", "dense2")


def role_shapes(cfg: dict) -> dict:
    k, cin = cfg["kernel_size"], cfg["in_channels"]
    c0, c1 = cfg["conv_channels"]
    w0, w1 = cfg["dense_widths"]
    flat = (cfg["image_size"] // 4) ** 2 * c1
    shapes = {"conv0": (k, k, cin, c0), "conv1": (k, k, c0, c1),
              "dense0": (flat, w0), "dense1": (w0, w1),
              "dense2": (w1, cfg["num_classes"])}
    out = {}
    for r, s in shapes.items():
        out[f"{r}.w"] = s
        out[f"{r}.b"] = (s[-1],)
    return out


def load(cfg: dict, seed: int) -> dict:
    """role -> (mean, variance) of every weight and bias, from the seed."""
    key = wlib.seed_key(seed)
    var = cfg["sigma_init"] ** 2
    out = {}
    for role, shape in role_shapes(cfg).items():
        mu = wlib.weight_mean(key, role, 0, shape,
                              wlib.fan_in_scale(role, shape))
        out[role] = (mu, jnp.full(shape, var, jnp.float32))
    return out


def _pool(mu, var):
    def pairs(a, axis):
        n = a.shape[axis]
        a = a.reshape(a.shape[:axis] + (n // 2, 2) + a.shape[axis + 1:])
        return (jax.lax.index_in_dim(a, 0, axis + 1, False),
                jax.lax.index_in_dim(a, 1, axis + 1, False))
    for axis in (2, 1):                           # width, then height
        (m1, m2), (v1, v2) = pairs(mu, axis), pairs(var, axis)
        mu, var = common.clark_max(m1, v1, m2, v2)
    return mu, var


@functools.partial(jax.jit, static_argnames=("precision", "formulation"))
def forward(w, x, *, precision="highest", formulation="srm"):
    """Logit (mean, variance) of images ``x`` (B, H, W, C); the variances
    of convs and dense layers in ``formulation`` ('srm': paper Eq. 12,
    the paper's operator; 'var': Eq. 7)."""
    num = common.Numerics(precision)

    def conv(a_mu, a_var, role):
        w_mu, w_var = w[f"{role}.w"]
        b_mu, b_var = w[f"{role}.b"]

        def c(a, k):
            return num.product(lambda x, y, p: jax.lax.conv_general_dilated(
                x, y, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=p,
                preferred_element_type=jnp.float32), a, k)
        mu = c(a_mu, w_mu)
        if a_var is None:                         # pixels: Eq. 13
            var = c(jnp.square(a_mu), w_var)
        elif formulation == "srm":
            w2, a2 = jnp.square(w_mu), jnp.square(a_mu)
            var = c(a2 + a_var, w2 + w_var) - c(a2, w2)
        else:
            var = c(jnp.square(a_mu), w_var) + \
                c(a_var, jnp.square(w_mu) + w_var)
        return mu + b_mu, var + b_var

    def dense(a_mu, a_var, role):
        mu, var = common.dense(num, a_mu, a_var, *w[f"{role}.w"],
                               formulation=formulation)
        b_mu, b_var = w[f"{role}.b"]
        return mu + b_mu, var + b_var

    h = conv(x, None, "conv0")
    h = _pool(*common.relu(*h))
    h = conv(*h, "conv1")
    h = _pool(*common.relu(*h))
    h = (h[0].reshape(h[0].shape[0], -1), h[1].reshape(h[1].shape[0], -1))
    h = common.relu(*dense(*h, "dense0"))
    h = common.relu(*dense(*h, "dense1"))
    return dense(*h, "dense2")


@functools.partial(jax.jit, static_argnames=("samples",))
def uncertainty(mean, var, key, idx, *, samples):
    """Prediction, entropy and MI of request ``idx``'s logits under the
    normals its sampling key gives: normal(fold_in(key, idx),
    (samples,) + mean.shape)."""
    eps = jax.random.normal(jax.random.fold_in(key, idx),
                            (samples,) + mean.shape, jnp.float32)
    return common.sampled_uncertainty(mean, var, eps)


def compare(cfg: dict, seed: int, sample_key, served, *,
            control: str = None) -> dict:
    """Checks over ``served`` = [(idx, images, (mean, var, pred, entropy,
    mi))]: the largest error of logit mean and variance over the
    reference's largest magnitude, and the largest absolute error of
    entropy and MI (nats) under the same normals. The reference runs at
    ``highest``; ``control`` names a precision, and the reference at that
    precision then stands in the program's place."""
    w = load(cfg, seed)
    ref_prec = "highest"                  # the reference: full float32
    n = cfg["num_logit_samples"]
    errs = {"mean_err": 0.0, "var_err": 0.0, "entropy_gap": 0.0,
            "mi_gap": 0.0}
    for idx, x, out in served:
        x = jnp.asarray(x)
        r_mu, r_var = forward(w, x, precision=ref_prec)
        _, r_ent, r_mi = uncertainty(r_mu, r_var, sample_key, idx,
                                     samples=n)
        if control:
            c_mu, c_var = forward(w, x, precision=control)
            _, c_ent, c_mi = uncertainty(c_mu, c_var, sample_key, idx,
                                         samples=n)
            out = (c_mu, c_var, None, c_ent, c_mi)
        mu, var, _, ent, mi = out
        errs["mean_err"] = max(errs["mean_err"], common.rel_err(mu, r_mu))
        errs["var_err"] = max(errs["var_err"], common.rel_err(var, r_var))
        errs["entropy_gap"] = max(errs["entropy_gap"], float(np.max(np.abs(
            np.asarray(ent) - np.asarray(r_ent)))))
        errs["mi_gap"] = max(errs["mi_gap"], float(np.max(np.abs(
            np.asarray(mi) - np.asarray(r_mi)))))
    return errs
