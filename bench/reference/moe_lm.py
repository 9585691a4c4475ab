"""Plain float32 PFP forward of a fine-grained MoE decoder LM
(deepseek-moe-16b at one chip's expert share): token embedding; the
leading dense layers as in ``dense_lm`` (their FFN ``dense_d_ff`` wide);
then per MoE layer RMSNorm -> MHA/GQA attention with RoPE -> residual ->
RMSNorm -> router -> routed experts + shared experts -> residual; then
RMSNorm and the LM head. Every weight is Gaussian (mean, variance);
activations carry a mean and a variance. The moment rules are
``dense_lm``'s.

The MoE layer, stated plainly: the router's softmax over all
``routed_experts`` experts of the normed mean picks the top ``top_k``;
their probabilities are the gates (renormalised to sum 1 only where
``norm_topk_prob``). Each held expert (global index ``expert_offset`` ..
``expert_offset + num_experts - 1``) is a SiLU-GLU computed on every
token, and its output enters a token's result times the token's gate for
it, zero where it is not among the token's top ``top_k``: mean times g,
variance times g^2. Nothing is dropped. The shared experts (one GLU
``d_ff * num_shared_experts`` wide) are added once.

It imports nothing from the program (``routed_gaps`` asks the system
module for the program's reading of one layer, as the served tokens come
from its engine), and runs layer by layer over a few
right-padded sequences at a time (causal attention and per-token routing
keep the padding invisible), with no kernel, cache or batching of
requests.

Departures from the published model:
  * the posterior variances are random-weight stand-ins (``sigma_init``);
  * routing is deterministic on the mean logits (the router never sees
    the variance), as in the program;
  * only the held experts' share of each MoE layer is computed: what the
    experts held on the deployment's other chips would add is left out,
    here as in the program, and the vocabulary is this chip's slice;
  * attention is mean field, as in ``dense_lm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as wlib
from bench.reference import common, dense_lm

ATTN_ROLES = ("ln1.g", "attn.wq.w", "attn.wk.w", "attn.wv.w", "attn.wo.w")
MOE_ROLES = ATTN_ROLES + ("ln2.g", "moe.router.w", "moe.shared.w_up.w",
                          "moe.shared.w_gate.w", "moe.shared.w_down.w")
EXPERT_ROLES = ("moe.experts.w_up", "moe.experts.w_gate",
                "moe.experts.w_down")


def role_shapes(cfg: dict) -> dict:
    """Shape of every role: ``dense_lm``'s for the embedding, attention,
    the leading dense layers (``head<i>.`` roles) and the head; then the
    MoE layers' router, shared experts and one routed expert."""
    d, f = cfg["d_model"], cfg["d_ff"]
    base = dense_lm.role_shapes(cfg)
    dense = dense_lm.role_shapes({**cfg, "d_ff": cfg.get("dense_d_ff") or f})
    out = dict(base)
    for i in range(cfg["first_dense_layers"]):
        for r in dense_lm.LAYER_ROLES:
            out[f"head{i}.{r}"] = dense[r]
    fs = f * cfg["num_shared_experts"]
    out.update({"moe.router.w": (d, cfg["routed_experts"]),
                "moe.shared.w_up.w": (d, fs), "moe.shared.w_gate.w": (d, fs),
                "moe.shared.w_down.w": (fs, d),
                "moe.experts.w_up": (d, f), "moe.experts.w_gate": (d, f),
                "moe.experts.w_down": (f, d)})
    return out


def load(cfg: dict, seed: int, role: str, layer: int = 0,
         expert: int = None):
    """(mean, variance) of one role's weight in one layer (and, for a
    routed expert, of the expert with that global index), made on the
    device from the run's seed as the system makes it."""
    shape = role_shapes(cfg)[role]
    if expert is not None:
        role = f"{role}.{expert}"
    return dense_lm._weight(wlib.seed_key(seed), role=role, layer=layer,
                            shape=shape, sigma=cfg["sigma_init"])


def held_experts(cfg: dict) -> list:
    return [cfg["expert_offset"] + e for e in range(cfg["num_experts"])]


def load_experts(cfg: dict, seed: int, layer: int) -> dict:
    """Each expert role's held experts in one MoE layer, stacked:
    {role: (means, variances)}."""
    experts = {}
    for r in EXPERT_ROLES:
        pairs = [load(cfg, seed, r, layer, e) for e in held_experts(cfg)]
        experts[r] = (jnp.stack([p[0] for p in pairs]),
                      jnp.stack([p[1] for p in pairs]))
        del pairs
    return experts


def _glu(lin, n_mu, n_var, up, gate, down):
    """SiLU-GLU of a normed input: ``dense_lm``'s rules."""
    up_mu, up_var = lin(n_mu, n_var, up)
    gt_mu, gt_var = lin(n_mu, n_var, gate)
    a_mu, a_srm = common.gauss_hermite(jax.nn.silu, gt_mu, gt_var)
    p_mu = a_mu * up_mu
    p_var = a_srm * (jnp.square(up_mu) + up_var) - jnp.square(p_mu)
    return lin(p_mu, p_var, down)


def _routed(num, cfg, n_mu, n_var, router, experts):
    """The held experts' part of an MoE layer's output on normed inputs:
    the router's top ``top_k`` of its softmax, each held expert on every
    token, entering a token's result times its gate (zero where not
    chosen): mean times g, variance times g^2."""

    def lin(a_mu, a_var, wt):
        return common.dense(num, a_mu, a_var, *wt)

    probs = jax.nn.softmax(num.einsum("...k,kn->...n", n_mu, router), -1)
    top_p, top_i = jax.lax.top_k(probs, cfg["top_k"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    r_mu = jnp.zeros_like(n_mu)
    r_var = jnp.zeros_like(n_var)
    for e in range(cfg["num_experts"]):
        gate = jnp.sum(jnp.where(top_i == cfg["expert_offset"] + e, top_p,
                                 0.0), -1)[..., None]
        e_mu, e_var = _glu(lin, n_mu, n_var,
                           *[tuple(a[e] for a in experts[r])
                             for r in EXPERT_ROLES])
        r_mu = r_mu + gate * e_mu
        r_var = r_var + jnp.square(gate) * e_var
    return r_mu, r_var


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision",
                                             "fp8"))
def routed(n_mu, n_var, router, experts, *, cfg_items, precision, fp8):
    """:func:`_routed` on (R, d) normed inputs, ``router`` the router's
    weight mean (d, routed_experts)."""
    return _routed(common.Numerics(precision, fp8), dict(cfg_items), n_mu,
                   n_var, router, experts)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision",
                                             "fp8"))
def moe_layer(x_mu, x_var, w, experts, *, cfg_items, precision, fp8):
    """One MoE decoder layer on (S, T, d) means and variances; ``experts``
    maps each expert role to the held experts' stacked (mean, variance).
    Returns the layer's output and its MoE input (the normed mean and
    variance after attention)."""
    cfg = dict(cfg_items)
    num = common.Numerics(precision, fp8)
    s_, t_, _ = x_mu.shape
    h, kvh, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    g = h // kvh
    pos = jnp.broadcast_to(jnp.arange(t_), (s_, t_))

    def lin(a_mu, a_var, wt):
        return common.dense(num, a_mu, a_var, *wt)

    n_mu, n_var = dense_lm.rmsnorm(x_mu, x_var, w["ln1.g"][0],
                                   cfg["rms_norm_eps"])
    q = lin(n_mu, n_var, w["attn.wq.w"])
    k = lin(n_mu, n_var, w["attn.wk.w"])
    v = lin(n_mu, n_var, w["attn.wv.w"])
    q = dense_lm.rope(q[0].reshape(s_, t_, h, dh),
                      q[1].reshape(s_, t_, h, dh), pos, cfg["rope_theta"])
    k = dense_lm.rope(k[0].reshape(s_, t_, kvh, dh),
                      k[1].reshape(s_, t_, kvh, dh), pos, cfg["rope_theta"])
    v_mu = v[0].reshape(s_, t_, kvh, dh)
    v_var = v[1].reshape(s_, t_, kvh, dh)
    q_mu = q[0].reshape(s_, t_, kvh, g, dh)
    scores = num.einsum("sqkgd,spkd->skgqp", q_mu, k[0]) * dh ** -0.5
    causal = jnp.tril(jnp.ones((t_, t_), bool))
    a = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o_mu = num.einsum("skgqp,spkd->sqkgd", a, v_mu).reshape(s_, t_, h * dh)
    o_var = num.einsum("skgqp,spkd->sqkgd", jnp.square(a),
                       v_var).reshape(s_, t_, h * dh)
    o_mu, o_var = lin(o_mu, o_var, w["attn.wo.w"])
    x_mu, x_var = x_mu + o_mu, x_var + o_var

    n_mu, n_var = dense_lm.rmsnorm(x_mu, x_var, w["ln2.g"][0],
                                   cfg["rms_norm_eps"])
    r_mu, r_var = _routed(num, cfg, n_mu, n_var, w["moe.router.w"][0],
                          experts)
    s_mu, s_var = _glu(lin, n_mu, n_var, w["moe.shared.w_up.w"],
                       w["moe.shared.w_gate.w"], w["moe.shared.w_down.w"])
    return x_mu + r_mu + s_mu, x_var + r_var + s_var, n_mu, n_var


def logits(cfg: dict, seed: int, seqs, rows, *, precision="highest",
           fp8=False, batch: int = 2, pad_to: int = None,
           moe_input: bool = False):
    """Logit (mean, variance) of each ``seqs[i]`` (token arrays) at its
    positions ``rows[i]``: (R, V) arrays, R = sum of len(rows[i]).
    ``moe_input`` also returns the first MoE layer's input at those rows
    (its normed mean and variance after attention, (R, d_model)).

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
    for i in range(cfg["first_dense_layers"]):
        w = {r: load(cfg, seed, f"head{i}.{r}") for r in dense_lm.LAYER_ROLES}
        x = [dense_lm.layer(m, v, w, cfg_items=items, precision=precision,
                            fp8=fp8) for m, v in x]
        del w
    flat = [(b, i) for b in range(nb) for i in range(batch)]

    idx = [jnp.asarray(np.asarray(r, np.int32)) for r in rows]

    def select(pairs):
        """Each sequence's rows of per-batch (mean, variance) pairs,
        concatenated."""
        return tuple(jnp.concatenate([pairs[b][k][i][j] for (b, i), j in
                                      zip(flat, idx)]) for k in (0, 1))

    first_moe = None
    for li in range(cfg["num_layers"] - cfg["first_dense_layers"]):
        w = {r: load(cfg, seed, r, li) for r in MOE_ROLES}
        experts = load_experts(cfg, seed, li)
        out = [moe_layer(m, v, w, experts, cfg_items=items,
                         precision=precision, fp8=fp8) for m, v in x]
        if li == 0 and moe_input:
            first_moe = select([o[2:] for o in out])
        x = [o[:2] for o in out]
        del w, experts, out
    n = sum(len(r) for r in rows)
    pad = -(-n // 256) * 256 - n
    mu, var = select(x)
    mu = jnp.pad(mu, ((0, pad), (0, 0)))
    var = jnp.pad(var, ((0, pad), (0, 0)))
    del x
    g, _ = load(cfg, seed, "ln_f.g")
    w_mu, w_var = load(cfg, seed, "lm_head.w")
    out_mu, out_var = dense_lm._head(mu, var, g, w_mu, w_var,
                                     eps=cfg["rms_norm_eps"],
                                     precision=precision, fp8=fp8)
    if moe_input:
        return out_mu[:n], out_var[:n], first_moe
    return out_mu[:n], out_var[:n]


def routed_gaps(cfg: dict, seed: int, n_mu, n_var, *,
                control: bool = False) -> dict:
    """The first MoE layer's routed part (its held experts, gated) on the
    given MoE inputs, rounded to the program's compute dtype so that both
    sides route the same rows, as the program computes it
    (``bench/systems/moe_serving.py`` ``routed_probe``) against this
    reference: ``routed_gap`` and ``routed_var_gap``, the largest error of
    the mean and of the variance over the reference's largest magnitude.
    ``control`` puts this reference at fp8 in the program's place."""
    dt = jnp.dtype(cfg["compute_dtype"])
    n_mu = jnp.asarray(n_mu).astype(dt).astype(jnp.float32)
    n_var = jnp.asarray(n_var).astype(dt).astype(jnp.float32)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    router, _ = load(cfg, seed, "moe.router.w", 0)
    experts = load_experts(cfg, seed, 0)
    r_mu, r_var = routed(n_mu, n_var, router, experts, cfg_items=items,
                         precision="highest", fp8=False)
    if control:
        p_mu, p_var = routed(n_mu, n_var, router, experts, cfg_items=items,
                             precision="highest", fp8=True)
    else:
        from bench.systems import moe_serving

        del experts
        p_mu, p_var = moe_serving.routed_probe(cfg, seed, n_mu, n_var)
    return {"routed_gap": common.rel_err(p_mu, r_mu),
            "routed_var_gap": common.rel_err(p_var, r_var)}


def compare(cfg: dict, seed: int, engine_seed: int, samples: int, served,
            *, control: bool = False) -> dict:
    """``dense_lm.compare``'s checks against this reference: teacher-force
    each served request's prompt and tokens and read, at every served
    position, ``logit_gap`` (the served token's reference logit below the
    reference's best) and ``mi_gap`` (|served MI - reference MI| under the
    same normals); and :func:`routed_gaps` on the first MoE layer's
    inputs at the served positions, which the served tokens and MI alone
    hardly show: the routed part is a few percent of the layer's output
    (gates of about 0.05, about 1.5 held assignments a token).
    ``control`` puts this reference at fp8 in the program's place."""
    seqs, rows, toks, mis, uids, tidx = [], [], [], [], [], []
    for uid, prompt, gen, mi in served:
        p = len(prompt)
        seqs.append(np.concatenate([prompt, np.asarray(gen[:-1], np.int32)]))
        rows.append(np.arange(p - 1, p + len(gen) - 1))
        toks += list(gen)
        mis += list(mi)
        uids += [uid & 0x7FFFFFFF] * len(gen)
        tidx += list(range(len(gen)))
    ref_mu, ref_var, (n_mu, n_var) = logits(cfg, seed, seqs, rows,
                                            moe_input=True)
    ref_mi = dense_lm.served_mi(ref_mu, ref_var, engine_seed, uids, tidx,
                                samples)
    if control:
        c_mu, c_var = logits(cfg, seed, seqs, rows, fp8=True)
        toks = np.asarray(jnp.argmax(c_mu, -1))
        mis = dense_lm.served_mi(c_mu, c_var, engine_seed, uids, tidx,
                                 samples)
        del c_mu, c_var
    ref = np.asarray(ref_mu)
    gap = ref.max(-1) - ref[np.arange(len(toks)), np.asarray(toks)]
    return {"logit_gap": float(gap.max()),
            "mi_gap": float(np.max(np.abs(np.asarray(mis) - ref_mi))),
            **routed_gaps(cfg, seed, n_mu, n_var, control=control),
            "tokens": len(toks)}
