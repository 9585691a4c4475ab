"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the bit-level specification the kernels are tested against
(interpret=True on CPU, sweeping shapes and dtypes). They are themselves
thin compositions of `repro.core.pfp_math`, which is validated against
Monte-Carlo sampling in tests/test_pfp_vs_monte_carlo.py — so the chain is
kernel -> oracle -> sampled ground truth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import pfp_math
from repro.core.dispatch import map_row_tiles


# -- pfp_dense ---------------------------------------------------------------
def pfp_dense_ref(mu_x, srm_x, mu_w, srm_w):
    """Joint PFP dense (SRM formulation, Eq. 4 + Eq. 12). fp32 accumulate."""
    f32 = jnp.float32
    mu = jnp.dot(mu_x.astype(f32), mu_w.astype(f32))
    var = jnp.dot(srm_x.astype(f32), srm_w.astype(f32)) - jnp.dot(
        jnp.square(mu_x.astype(f32)), jnp.square(mu_w.astype(f32))
    )
    return mu, var


def pfp_dense_first_layer_ref(x, mu_w, var_w):
    """First-layer simplification (Eq. 13): deterministic inputs."""
    f32 = jnp.float32
    mu = jnp.dot(x.astype(f32), mu_w.astype(f32))
    var = jnp.dot(jnp.square(x.astype(f32)), var_w.astype(f32))
    return mu, var


def pfp_dense_var_ref(mu_x, var_x, mu_w, var_w):
    """Joint PFP dense, Eq. 7 'var' formulation: four contractions over
    (mu, var) operands. fp32 accumulate."""
    f32 = jnp.float32
    mx, vx = mu_x.astype(f32), var_x.astype(f32)
    mw, vw = mu_w.astype(f32), var_w.astype(f32)
    mu = jnp.dot(mx, mw)
    var = (jnp.dot(vx, jnp.square(mw)) + jnp.dot(jnp.square(mx), vw)
           + jnp.dot(vx, vw))
    return mu, var


# -- pfp_moe (batched-expert dense) ------------------------------------------
# The vmapped per-expert chain IS the oracle the grid-level kernel is
# accepted against (ISSUE 10): vmap over the shared leading expert axis.
pfp_dense_batched_ref = jax.vmap(pfp_dense_ref)
pfp_dense_batched_first_layer_ref = jax.vmap(pfp_dense_first_layer_ref)
pfp_dense_batched_var_ref = jax.vmap(pfp_dense_var_ref)


def pfp_dense_grouped_ref(mu_x, srm_x, mu_w, srm_w, tile_expert, num_tiles,
                          block_m: int):
    """Grouped joint PFP dense (the grouped kernel's oracle): row tile i
    of ``block_m`` rows of (M, K) runs Eq. 12 against expert
    ``tile_expert[i]``'s (K, N) weights, cast to the rows' dtype; tiles
    from ``num_tiles`` on are zero. Returns (mean, var), each (M, N)."""
    return map_row_tiles(
        lambda xm, xs, e: pfp_dense_ref(xm, xs, mu_w[e].astype(xm.dtype),
                                        srm_w[e].astype(xm.dtype)),
        (mu_x, srm_x), tile_expert, num_tiles, block_m)


# -- pfp_activations ---------------------------------------------------------
def pfp_relu_ref(mu, var):
    return pfp_math.relu_moments(mu.astype(jnp.float32), var.astype(jnp.float32))


def pfp_gelu_ref(mu, var, num_nodes: int = 8):
    return pfp_math.gelu_moments(
        mu.astype(jnp.float32), var.astype(jnp.float32), num_nodes
    )


def pfp_silu_ref(mu, var, num_nodes: int = 8):
    return pfp_math.silu_moments(
        mu.astype(jnp.float32), var.astype(jnp.float32), num_nodes
    )


def pfp_tanh_ref(mu, var, num_nodes: int = 8):
    return pfp_math.tanh_moments(
        mu.astype(jnp.float32), var.astype(jnp.float32), num_nodes
    )


def pfp_sigmoid_ref(mu, var, num_nodes: int = 8):
    return pfp_math.sigmoid_moments(
        mu.astype(jnp.float32), var.astype(jnp.float32), num_nodes
    )


# -- pfp_norms ---------------------------------------------------------------
def _var_srm(mu, second, rep):
    if rep == "var":
        return second, second + jnp.square(mu)
    return second - jnp.square(mu), second


def pfp_rmsnorm_ref(mu, second, gain, *, rep="var", eps=1e-6):
    """Delta-method RMSNorm oracle: (mean, var) out. Rows x features."""
    f32 = jnp.float32
    mu, second = mu.astype(f32), second.astype(f32)
    var, srm = _var_srm(mu, second, rep)
    norm = jax.lax.rsqrt(jnp.mean(srm, axis=-1, keepdims=True) + eps)
    scale = norm * gain.astype(f32)
    return mu * scale, var * jnp.square(scale)


def pfp_layernorm_ref(mu, second, gain, bias, *, rep="var", eps=1e-6):
    """Delta-method LayerNorm oracle: (mean, var) out. Rows x features."""
    f32 = jnp.float32
    mu, second = mu.astype(f32), second.astype(f32)
    var, srm = _var_srm(mu, second, rep)
    mu_tok = jnp.mean(mu, axis=-1, keepdims=True)
    spread = jnp.mean(var + jnp.square(mu - mu_tok), axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(spread + eps) * gain.astype(f32)
    return (mu - mu_tok) * scale + bias.astype(f32), var * jnp.square(scale)


# -- pfp_glu -----------------------------------------------------------------
def pfp_glu_ref(mu_a, srm_a, mu_b, srm_b):
    """Exact SRM product of independent Gaussians: (mean, srm) out."""
    f32 = jnp.float32
    return (mu_a.astype(f32) * mu_b.astype(f32),
            srm_a.astype(f32) * srm_b.astype(f32))


# -- pfp_maxpool -------------------------------------------------------------
def pfp_maxpool2d_ref(mu, var):
    """2x2/stride-2 PFP max pool on NHWC via Clark tournament (VAR->VAR)."""
    n, h, w, c = mu.shape
    mu00, mu01 = mu[:, :, 0::2, :], mu[:, :, 1::2, :]
    v00, v01 = var[:, :, 0::2, :], var[:, :, 1::2, :]
    m_w, s_w = pfp_math.clark_max_moments(mu00, v00, mu01, v01)
    v_w = jnp.maximum(s_w - jnp.square(m_w), 0.0)
    m0, m1 = m_w[:, 0::2], m_w[:, 1::2]
    v0, v1 = v_w[:, 0::2], v_w[:, 1::2]
    m, s = pfp_math.clark_max_moments(m0, v0, m1, v1)
    return m, jnp.maximum(s - jnp.square(m), 0.0)


# -- pfp_attention -----------------------------------------------------------
def pfp_attention_ref(q_mu, k_mu, v_mu, v_var, scale, causal=True):
    """Mean-field PFP attention oracle over (B, H, T, D)."""
    f32 = jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q_mu.astype(f32), k_mu.astype(f32)) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        idx_q = jnp.arange(tq)[:, None] + (tk - tq)  # right-aligned causal
        mask = idx_q >= jnp.arange(tk)[None, :]
        s = jnp.where(mask, s, jnp.finfo(f32).min)
    p = jax.nn.softmax(s, axis=-1)
    if causal:
        # A query with no key at or before it (tq > tk) attends to
        # nothing and outputs (0, 0), as the kernels do.
        p = p * jnp.any(mask, axis=-1, keepdims=True)
    out_mu = jnp.einsum("bhqk,bhkd->bhqd", p, v_mu.astype(f32))
    out_var = jnp.einsum("bhqk,bhkd->bhqd", jnp.square(p), v_var.astype(f32))
    return out_mu, out_var


def pfp_attention_cache_ref(q_mu, k_mu, v_mu, v_var, q_start, kv_len, scale,
                            causal=True, window=None):
    """KV-cache attention oracle over (B, H, Tq, D) x (B, H, Tk, D).

    q_start/kv_len: (B,) int32 — query row i of batch b sits at absolute
    position q_start[b] + i; key j is real iff j < kv_len[b]. The masking
    definition is shared with the Pallas kernels (core/masking.py).
    """
    from repro.core.masking import attention_valid_mask, mask_scores

    f32 = jnp.float32
    tq, tk = q_mu.shape[2], k_mu.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q_mu.astype(f32), k_mu.astype(f32)) * scale
    q_idx = q_start[:, None] + jnp.arange(tq, dtype=jnp.int32)      # (B, Tq)
    mask = attention_valid_mask(
        q_idx[:, :, None], jnp.arange(tk, dtype=jnp.int32)[None, None, :],
        causal=causal, window=window, kv_len=kv_len[:, None, None])
    p = jax.nn.softmax(mask_scores(s, mask[:, None]), axis=-1)
    out_mu = jnp.einsum("bhqk,bhkd->bhqd", p, v_mu.astype(f32))
    out_var = jnp.einsum("bhqk,bhkd->bhqd", jnp.square(p), v_var.astype(f32))
    return out_mu, out_var


def gather_kv_pages(pages, page_table):
    """(NP, Hkv, ps, D) x (B, P) -> contiguous (B, Hkv, P*ps, D)."""
    b, p = page_table.shape
    np_, hkv, ps, d = pages.shape
    flat = jnp.take(pages, page_table.reshape(-1), axis=0)
    return flat.reshape(b, p, hkv, ps, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, p * ps, d)


def pfp_attention_paged_ref(q_mu, k_pages, v_pages, vv_pages, page_table,
                            q_start, kv_len, scale, causal=True, window=None):
    """Paged KV-cache attention oracle: gather pages, then the cache oracle.

    q (B, H, Tq, D) x pages (NP, Hkv, ps, D) with page_table (B, P); K/V
    heads are repeated up to H here (the Pallas kernel instead maps query
    heads onto shared page tiles in its BlockSpec index map).
    """
    group = q_mu.shape[1] // k_pages.shape[1]
    k, vm, vv = (gather_kv_pages(a, page_table)
                 for a in (k_pages, v_pages, vv_pages))
    if group > 1:
        k, vm, vv = (jnp.repeat(a, group, axis=1) for a in (k, vm, vv))
    return pfp_attention_cache_ref(q_mu, k, vm, vv, q_start, kv_len, scale,
                                   causal=causal, window=window)
