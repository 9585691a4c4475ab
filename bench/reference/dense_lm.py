"""Plain float32 PFP forward of a dense decoder LM (the granite family):
token embedding, then per layer RMSNorm -> GQA attention with RoPE ->
residual -> RMSNorm -> SiLU-GLU MLP -> residual, then RMSNorm and the LM
head. Every weight is Gaussian (mean, variance); activations carry a mean
and a variance.

The moment rules, stated here once:
  dense      paper Eq. 4 + 7 (``common.dense``), no cancellation form;
  RMSNorm    the normaliser 1/sqrt(mean(E[x^2]) + eps) is a deterministic
             per-token scalar (delta method);
  RoPE       a fixed rotation: var' = var1 cos^2 + var2 sin^2 per pair;
  attention  mean field: softmax of the score means (causal), output
             mean A v_mu and variance A^2 v_var;
  SiLU       8-node Gauss-Hermite moments; the GLU product of independent
             Gaussians: E[ab] = E[a]E[b], E[(ab)^2] = E[a^2]E[b^2];
  residual   means add, variances add.

It runs layer by layer over a few padded sequences at a time (causal
attention makes the right padding invisible), so it fits beside nothing
but one layer's weights. Departures from the published model: the
posterior variances are random-weight stand-ins (config ``sigma_init``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as wlib
from bench.reference import common

LAYER_ROLES = ("ln1.g", "attn.wq.w", "attn.wk.w", "attn.wv.w", "attn.wo.w",
               "ln2.g", "mlp.w_up.w", "mlp.w_gate.w", "mlp.w_down.w")


def role_shapes(cfg: dict) -> dict:
    d, f = cfg["d_model"], cfg["d_ff"]
    a = cfg["num_heads"] * cfg["head_dim"]
    kv = cfg["num_kv_heads"] * cfg["head_dim"]
    return {"embed.table": (cfg["vocab_size"], d), "ln1.g": (d,),
            "attn.wq.w": (d, a), "attn.wk.w": (d, kv), "attn.wv.w": (d, kv),
            "attn.wo.w": (a, d), "ln2.g": (d,), "mlp.w_up.w": (d, f),
            "mlp.w_gate.w": (d, f), "mlp.w_down.w": (f, d),
            "ln_f.g": (d,), "lm_head.w": (d, cfg["vocab_size"])}


@functools.partial(jax.jit, static_argnames=("role", "layer", "shape",
                                             "sigma"))
def _weight(key, *, role, layer, shape, sigma):
    if role.endswith(".g"):                       # norm gains
        return jnp.ones(shape, jnp.float32), None
    mu = wlib.weight_mean(key, role, layer, shape,
                          wlib.fan_in_scale(role, shape))
    return mu, jnp.full(shape, sigma ** 2, jnp.float32)


def load(cfg: dict, seed: int, role: str, layer: int = 0):
    """(mean, variance) of one role's weight in one layer, made on the
    device from the run's seed (``bench.weights``)."""
    return _weight(wlib.seed_key(seed), role=role, layer=layer,
                   shape=role_shapes(cfg)[role], sigma=cfg["sigma_init"])


def rmsnorm(mu, var, g, eps):
    n = jax.lax.rsqrt(jnp.mean(jnp.square(mu) + var, -1, keepdims=True)
                      + eps) * g
    return mu * n, var * jnp.square(n)


def rope(mu, var, positions, theta):
    """Rotate-half RoPE on (S, T, H, Dh) means and variances."""
    half = mu.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq      # (S, T, half)
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    m1, m2 = mu[..., :half], mu[..., half:]
    v1, v2 = var[..., :half], var[..., half:]
    c2, s2 = jnp.square(c), jnp.square(s)
    return (jnp.concatenate([m1 * c - m2 * s, m2 * c + m1 * s], -1),
            jnp.concatenate([v1 * c2 + v2 * s2, v2 * c2 + v1 * s2], -1))


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision",
                                             "fp8"))
def layer(x_mu, x_var, w, *, cfg_items, precision, fp8):
    """One decoder layer on (S, T, d) means and variances."""
    cfg = dict(cfg_items)
    num = common.Numerics(precision, fp8)
    s_, t_, _ = x_mu.shape
    h, kvh, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    g = h // kvh
    pos = jnp.broadcast_to(jnp.arange(t_), (s_, t_))

    def lin(a_mu, a_var, role):
        return common.dense(num, a_mu, a_var, *w[role])

    n_mu, n_var = rmsnorm(x_mu, x_var, w["ln1.g"][0], cfg["rms_norm_eps"])
    q = lin(n_mu, n_var, "attn.wq.w")
    k = lin(n_mu, n_var, "attn.wk.w")
    v = lin(n_mu, n_var, "attn.wv.w")
    q = rope(q[0].reshape(s_, t_, h, dh), q[1].reshape(s_, t_, h, dh), pos,
             cfg["rope_theta"])
    k = rope(k[0].reshape(s_, t_, kvh, dh), k[1].reshape(s_, t_, kvh, dh),
             pos, cfg["rope_theta"])
    v_mu = v[0].reshape(s_, t_, kvh, dh)
    v_var = v[1].reshape(s_, t_, kvh, dh)
    q_mu = q[0].reshape(s_, t_, kvh, g, dh)       # head i uses kv head i//g
    scores = num.einsum("sqkgd,spkd->skgqp", q_mu, k[0]) * dh ** -0.5
    causal = jnp.tril(jnp.ones((t_, t_), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    a = jax.nn.softmax(scores, -1)
    o_mu = num.einsum("skgqp,spkd->sqkgd", a, v_mu).reshape(s_, t_, h * dh)
    o_var = num.einsum("skgqp,spkd->sqkgd", jnp.square(a),
                       v_var).reshape(s_, t_, h * dh)
    o_mu, o_var = lin(o_mu, o_var, "attn.wo.w")
    x_mu, x_var = x_mu + o_mu, x_var + o_var

    n_mu, n_var = rmsnorm(x_mu, x_var, w["ln2.g"][0], cfg["rms_norm_eps"])
    up_mu, up_var = lin(n_mu, n_var, "mlp.w_up.w")
    gt_mu, gt_var = lin(n_mu, n_var, "mlp.w_gate.w")
    a_mu, a_srm = common.gauss_hermite(jax.nn.silu, gt_mu, gt_var)
    p_mu = a_mu * up_mu
    p_var = a_srm * (jnp.square(up_mu) + up_var) - jnp.square(p_mu)
    d_mu, d_var = lin(p_mu, p_var, "mlp.w_down.w")
    return x_mu + d_mu, x_var + d_var


@functools.partial(jax.jit, static_argnames=("eps", "precision", "fp8"))
def _head(x_mu, x_var, g, w_mu, w_var, *, eps, precision, fp8):
    num = common.Numerics(precision, fp8)
    n_mu, n_var = rmsnorm(x_mu, x_var, g, eps)
    return common.dense(num, n_mu, n_var, w_mu, w_var)


def logits(cfg: dict, seed: int, seqs, rows, *, precision="highest",
           fp8=False, batch: int = 2, pad_to: int = None):
    """Logit (mean, variance) of each ``seqs[i]`` (token arrays) at its
    positions ``rows[i]``: (R, V) arrays, R = sum of len(rows[i]).

    The sequences are right-padded to ``pad_to`` (default: the longest,
    rounded up to 256) and run ``batch`` at a time, one layer's weights
    on the device at once."""
    t = pad_to or -(-max(len(s) for s in seqs) // 256) * 256
    nb = -(-len(seqs) // batch)
    toks = np.zeros((nb * batch, t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    toks = toks.reshape(nb, batch, t)
    emb_mu, emb_var = load(cfg, seed, "embed.table")
    x = [(emb_mu[b], emb_var[b]) for b in jnp.asarray(toks)]
    del emb_mu, emb_var
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    for li in range(cfg["num_layers"]):
        w = {r: load(cfg, seed, r, li) for r in LAYER_ROLES}
        x = [layer(m, v, w, cfg_items=items, precision=precision, fp8=fp8)
             for m, v in x]
        del w
    flat = [(b, i) for b in range(nb) for i in range(batch)]
    sel_mu, sel_var = [], []
    for (b, i), r in zip(flat, rows):
        idx = jnp.asarray(np.asarray(r, np.int32))
        sel_mu.append(x[b][0][i][idx])
        sel_var.append(x[b][1][i][idx])
    n = sum(len(r) for r in rows)
    pad = -(-n // 256) * 256 - n
    mu = jnp.pad(jnp.concatenate(sel_mu), ((0, pad), (0, 0)))
    var = jnp.pad(jnp.concatenate(sel_var), ((0, pad), (0, 0)))
    del x
    g, _ = load(cfg, seed, "ln_f.g")
    w_mu, w_var = load(cfg, seed, "lm_head.w")
    out_mu, out_var = _head(mu, var, g, w_mu, w_var,
                            eps=cfg["rms_norm_eps"], precision=precision,
                            fp8=fp8)
    return out_mu[:n], out_var[:n]


@functools.partial(jax.jit, static_argnames=("samples",))
def _mi_rows(mean, var, seed_key, uids, tok_idx, *, samples):
    """MI of each row's logits under the standard normals the engine draws
    for token ``tok_idx`` of request ``uid`` (its uncertainty-sampling
    keys: fold_in(fold_in(key(seed), uid), t), split, first half, shape
    (samples, 1, V))."""
    def one(m, v, uid, t):
        k = jax.random.fold_in(jax.random.fold_in(seed_key, uid), t)
        eps = jax.random.normal(jax.random.split(k)[0],
                                (samples, 1, m.shape[-1]), jnp.float32)
        return common.sampled_uncertainty(m[None], v[None], eps)[2][0]
    return jax.lax.map(lambda a: one(*a), (mean, var, uids, tok_idx))


def served_mi(mean, var, engine_seed: int, uids, tok_idx, samples: int):
    return np.asarray(_mi_rows(mean, var, jax.random.PRNGKey(engine_seed),
                               jnp.asarray(uids, jnp.int32),
                               jnp.asarray(tok_idx, jnp.int32),
                               samples=samples))


def compare(cfg: dict, seed: int, engine_seed: int, samples: int, served,
            *, control: bool = False) -> dict:
    """The served-token checks over ``served`` = [(uid, prompt, generated,
    mi_trace)]: teacher-force each prompt with its served tokens through
    the reference and read, at every served position,

      logit_gap  how far the served token's reference logit lies below
                 the reference's best (0 when the argmax agrees);
      mi_gap     |served MI - reference MI| under the same normals.

    ``control`` puts the reference at fp8 in the program's place: its
    argmax is the served token and its MI the served MI."""
    seqs, rows, toks, mis, uids, tidx = [], [], [], [], [], []
    for uid, prompt, gen, mi in served:
        p = len(prompt)
        seqs.append(np.concatenate([prompt, np.asarray(gen[:-1], np.int32)]))
        rows.append(np.arange(p - 1, p + len(gen) - 1))
        toks += list(gen)
        mis += list(mi)
        uids += [uid & 0x7FFFFFFF] * len(gen)
        tidx += list(range(len(gen)))
    ref_mu, ref_var = logits(cfg, seed, seqs, rows)
    ref_mi = served_mi(ref_mu, ref_var, engine_seed, uids, tidx, samples)
    if control:
        c_mu, c_var = logits(cfg, seed, seqs, rows, fp8=True)
        toks = np.asarray(jnp.argmax(c_mu, -1))
        mis = served_mi(c_mu, c_var, engine_seed, uids, tidx, samples)
        del c_mu, c_var
    ref = np.asarray(ref_mu)
    gap = ref.max(-1) - ref[np.arange(len(toks)), np.asarray(toks)]
    return {"logit_gap": float(gap.max()),
            "mi_gap": float(np.max(np.abs(np.asarray(mis) - ref_mi))),
            "tokens": len(toks)}
