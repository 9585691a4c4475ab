"""Flash-style PFP attention Pallas kernels (mean-field, joint mu/var pass).

One online-softmax sweep produces BOTH attention outputs:

    out_mu  = softmax(q mu_k^T) @ mu_v
    out_var = softmax(q mu_k^T)^2 @ var_v

The square of the attention probabilities shares the same running max m and
normalizer l as the probabilities themselves: if p = exp(s - m)/l then
p^2 = exp(2(s - m))/l^2, so the variance accumulator is rescaled by
alpha^2 = exp(2(m_old - m_new)) where the mean accumulator uses alpha, and
is divided by l^2 at the end. This is the joint-operator principle applied
to attention: mu_v and var_v tiles ride the same K-loop, and the score tile
s is computed once for both paths.

Three entry points share that accumulator core (``_accumulate`` /
``_finalize``) and the one masking definition in ``core/masking.py``:

  pfp_attention_pallas        full-sequence self attention; right-aligned
                              index causality (decode-friendly), static
                              valid length.
  pfp_attention_cache_pallas  KV-cache attention: per-batch scalar query
                              start + valid cache length arrive via TPU
                              scalar prefetch, so each batch row decodes at
                              its own position (continuous batching) with a
                              dynamic ``tk_valid`` — no XLA fallback.
  pfp_attention_paged_pallas  paged KV-cache attention: K/V/var live in a
                              global page pool and a scalar-prefetched page
                              table drives the KV BlockSpec index map, so
                              each K-step DMAs one page — pages are never
                              gathered into a contiguous buffer. Per-page
                              valid-length masking comes from the same
                              per-batch cache length.

Grid: (B*H, Tq/bq, Tk/bk) with the Tk axis sequential; fp32 accumulators
(m, l broadcast over 128 lanes; acc_mu, acc_var of shape (bq, d)) in VMEM.
(block_q, block_k) default to 128x128; the autotuner (repro.tuning)
overrides them per shape via the ``ops.pfp_attention*`` schedule arguments —
masking is by absolute index, so block choice never changes results. For
the paged kernel block_k IS the page size (one page per K-step).

Two further tuned axes (repro.tuning OP_AXES):

  * ``dims``     — Mosaic dimension_semantics for the (batch*head, Tq)
    grid axes ('parallel' / 'arbitrary'; Tk stays 'arbitrary' — it
    carries the accumulators). Compiler annotation only, never results.
  * ``prefetch`` — paged kernel only: scalar-prefetch DEPTH. Each K-step
    DMAs ``prefetch`` logical pages (each via its own table-indirect
    BlockSpec, so physically scattered pages still stream) and the body
    consumes them in logical page order — the accumulator update sequence
    is identical to depth 1, so results are bit-equal while the DMA
    pipeline sees ``prefetch`` pages of lookahead per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.masking import NEG_INF, attention_valid_mask, mask_scores
from repro.kernels.pfp_dense import _compiler_params

_LANES = 128


# ---------------------------------------------------------------------------
# Shared online-softmax accumulator core
# ---------------------------------------------------------------------------
def _accumulate(s, valid, v_mu_ref, v_var_ref,
                m_ref, l_ref, acc_mu_ref, acc_var_ref):
    """One K-block update of the joint (mu, var) online softmax."""
    s = mask_scores(s, valid)
    m_prev = m_ref[:, :1]                                # (bq, 1)
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)           # (bq, 1)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)                     # (bq, 1)
    p = jnp.exp(s - m_next)                              # (bq, bk)
    p = jnp.where(valid, p, 0.0)
    l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)

    v_mu = v_mu_ref[0].astype(jnp.float32)               # (bk, d)
    v_var = v_var_ref[0].astype(jnp.float32)
    acc_mu_ref[...] = acc_mu_ref[...] * alpha + jnp.dot(
        p, v_mu, preferred_element_type=jnp.float32
    )
    acc_var_ref[...] = acc_var_ref[...] * jnp.square(alpha) + jnp.dot(
        jnp.square(p), v_var, preferred_element_type=jnp.float32
    )

    m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)


def _init_accumulators(m_ref, l_ref, acc_mu_ref, acc_var_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_mu_ref[...] = jnp.zeros_like(acc_mu_ref)
    acc_var_ref[...] = jnp.zeros_like(acc_var_ref)


def _finalize(out_mu_ref, out_var_ref, m_ref, l_ref, acc_mu_ref, acc_var_ref):
    # Any row with >= 1 valid key has l >= 1 (its max scores exp(0)); l == 0
    # only for fully-masked rows (e.g. kv_len == 0 slots parked in a batched
    # prefill), whose accumulators are zero. The clamp must survive
    # squaring in fp32 — 1e-30 would underflow l^2 to 0 and turn those dead
    # rows into 0/0 = NaN instead of 0.
    l = jnp.maximum(l_ref[:, :1], 1e-18)
    out_mu_ref[0] = acc_mu_ref[...] / l
    out_var_ref[0] = acc_var_ref[...] / jnp.square(l)


def _score_tile(q_ref, k_ref, scale):
    q = q_ref[0].astype(jnp.float32)                     # (bq, d)
    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                            # (bq, bk)


# ---------------------------------------------------------------------------
# Full-sequence kernel (static valid length, right-aligned causality)
# ---------------------------------------------------------------------------
def _attn_kernel(
    q_ref, k_ref, v_mu_ref, v_var_ref,
    out_mu_ref, out_var_ref,
    m_ref, l_ref, acc_mu_ref, acc_var_ref,
    *, scale: float, bq: int, bk: int, tq: int, tk: int, tk_valid: int,
    causal: bool, nk: int,
):
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        _init_accumulators(m_ref, l_ref, acc_mu_ref, acc_var_ref)

    s = _score_tile(q_ref, k_ref, scale)
    k_idx = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    q_idx = (
        qi * bq
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        + (tk_valid - tq)                                # right-aligned
    )
    valid = attention_valid_mask(q_idx, k_idx, causal=causal,
                                 kv_len=tk_valid)
    _accumulate(s, valid, v_mu_ref, v_var_ref,
                m_ref, l_ref, acc_mu_ref, acc_var_ref)

    @pl.when(kb == nk - 1)
    def _done():
        _finalize(out_mu_ref, out_var_ref, m_ref, l_ref,
                  acc_mu_ref, acc_var_ref)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "block_q", "block_k", "dims",
                     "interpret"),
)
def pfp_attention_pallas(
    q_mu,
    k_mu,
    v_mu,
    v_var,
    *,
    scale: float,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    dims: str = "parallel",
    interpret: bool = False,
):
    """(B, H, Tq, D) x (B, Hkv, Tk, D) -> mean/var (B, H, Tq, D), fp32.

    Grouped-query friendly: K/V may carry fewer heads (H % Hkv == 0). The
    query->kv-head mapping happens in the KV BlockSpec index map (head
    order is kv-major: h = kv * group + g), so grouped K/V are never
    materialized at H heads — each kernel instance DMAs the shared tile.
    """
    b, h, tq, d = q_mu.shape
    hkv = k_mu.shape[1]
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    tk = k_mu.shape[2]
    bq = min(block_q, tq)
    bk = min(block_k, tk)

    tq_p = tq + ((-tq) % bq)
    tk_p = tk + ((-tk) % bk)
    q_mu = _pad_t(q_mu, tq_p)
    k_mu, v_mu, v_var = (_pad_t(a, tk_p) for a in (k_mu, v_mu, v_var))

    bh = b * h
    q_mu = q_mu.reshape(bh, tq_p, d)
    k_mu = k_mu.reshape(b * hkv, tk_p, d)
    v_mu = v_mu.reshape(b * hkv, tk_p, d)
    v_var = v_var.reshape(b * hkv, tk_p, d)
    nk = tk_p // bk

    q_spec = pl.BlockSpec((1, bq, d), lambda bh_, i, k_: (bh_, i, 0))
    # bh_ = b*H + h with H = Hkv*group  =>  bh_ // group = b*Hkv + h//group.
    kv_spec = pl.BlockSpec((1, bk, d), lambda bh_, i, k_: (bh_ // group, k_, 0))
    out_spec = pl.BlockSpec((1, bq, d), lambda bh_, i, k_: (bh_, i, 0))

    kernel = functools.partial(
        _attn_kernel,
        scale=scale, bq=bq, bk=bk, tq=tq, tk=tk_p, tk_valid=tk,
        causal=causal, nk=nk,
    )
    common = dict(
        grid=(bh, tq_p // bq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, kv_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq_p, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, tq_p, d), jnp.float32),
        ],
        scratch_shapes=_attn_scratch(bq, d),
        interpret=interpret,
        compiler_params=_compiler_params((dims, dims, "arbitrary")),
    )
    fn = pl.pallas_call(kernel, **common)
    out_mu, out_var = fn(q_mu, k_mu, v_mu, v_var)
    out_mu = out_mu.reshape(b, h, tq_p, d)[:, :, :tq]
    out_var = out_var.reshape(b, h, tq_p, d)[:, :, :tq]
    return out_mu, out_var


# ---------------------------------------------------------------------------
# KV-cache kernel: per-batch (q_start, kv_len) scalars, optional window
# ---------------------------------------------------------------------------
def _cache_attn_kernel(
    q_start_ref, kv_len_ref,
    q_ref, k_ref, v_mu_ref, v_var_ref,
    out_mu_ref, out_var_ref,
    m_ref, l_ref, acc_mu_ref, acc_var_ref,
    *, scale: float, bq: int, bk: int, heads: int, causal: bool,
    window, nk: int,
):
    """Shared body of the cache + paged kernels.

    Query row r of grid step (bh, qi) sits at absolute position
    ``q_start[b] + qi*bq + r`` (the cache-insert contract: a cache caller's
    positions are contiguous from their per-batch start). Key j of K-step
    kb sits at absolute position ``kb*bk + j`` and is real iff below the
    per-batch valid cache length — which for the paged variant is exactly
    per-page valid-length masking, since each K-step is one page.
    """
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    b = bh // heads

    @pl.when(kb == 0)
    def _init():
        _init_accumulators(m_ref, l_ref, acc_mu_ref, acc_var_ref)

    s = _score_tile(q_ref, k_ref, scale)
    k_idx = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    q_idx = (q_start_ref[b] + qi * bq
             + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    valid = attention_valid_mask(q_idx, k_idx, causal=causal, window=window,
                                 kv_len=kv_len_ref[b])
    _accumulate(s, valid, v_mu_ref, v_var_ref,
                m_ref, l_ref, acc_mu_ref, acc_var_ref)

    @pl.when(kb == nk - 1)
    def _done():
        _finalize(out_mu_ref, out_var_ref, m_ref, l_ref,
                  acc_mu_ref, acc_var_ref)


def _paged_attn_kernel(q_start_ref, kv_len_ref, table_ref, q_ref, *rest,
                       scale: float, bq: int, bk: int, heads: int,
                       causal: bool, window, nk: int, depth: int):
    """Depth-generic paged body: each grid K-step carries ``depth``
    logical pages (one table-indirect BlockSpec each — pages stay
    physically scattered) and replays the cache kernel's accumulator
    update once per page in logical order, so any depth is bit-identical
    to depth 1. The page table itself steers only the index maps."""
    del table_ref
    k_refs = rest[0:depth]
    vmu_refs = rest[depth:2 * depth]
    vvar_refs = rest[2 * depth:3 * depth]
    (out_mu_ref, out_var_ref,
     m_ref, l_ref, acc_mu_ref, acc_var_ref) = rest[3 * depth:]

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    b = bh // heads

    @pl.when(kb == 0)
    def _init():
        _init_accumulators(m_ref, l_ref, acc_mu_ref, acc_var_ref)

    for j in range(depth):
        s = _score_tile(q_ref, k_refs[j], scale)
        k_idx = ((kb * depth + j) * bk
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
        q_idx = (q_start_ref[b] + qi * bq
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
        valid = attention_valid_mask(q_idx, k_idx, causal=causal,
                                     window=window, kv_len=kv_len_ref[b])
        _accumulate(s, valid, vmu_refs[j], vvar_refs[j],
                    m_ref, l_ref, acc_mu_ref, acc_var_ref)

    @pl.when(kb == nk - 1)
    def _done():
        _finalize(out_mu_ref, out_var_ref, m_ref, l_ref,
                  acc_mu_ref, acc_var_ref)


def _grid_spec(num_scalars, grid, in_specs, out_specs, bq, d):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalars,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=_attn_scratch(bq, d),
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "block_q", "block_k",
                     "dims", "interpret"),
)
def pfp_attention_cache_pallas(
    q_mu, k_mu, v_mu, v_var, q_start, kv_len,
    *,
    scale: float,
    causal: bool = True,
    window=None,
    block_q: int = 128,
    block_k: int = 128,
    dims: str = "parallel",
    interpret: bool = False,
):
    """KV-cache attention with per-batch dynamic valid lengths.

    q (B, H, Tq, D) x cache (B, Hkv, S, D); q_start/kv_len (B,) int32 are
    scalar-prefetched: query row i of batch b sits at absolute position
    q_start[b] + i, keys at absolute index j are real iff j < kv_len[b].
    This is the decode/windowed-decode path that previously fell back to
    the chunked XLA core (`tk_valid` was compile-time static here).
    """
    b, h, tq, d = q_mu.shape
    hkv = k_mu.shape[1]
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    tk = k_mu.shape[2]
    bq = min(block_q, tq)
    bk = min(block_k, tk)

    tq_p = tq + ((-tq) % bq)
    tk_p = tk + ((-tk) % bk)
    q_mu = _pad_t(q_mu, tq_p)
    k_mu, v_mu, v_var = (_pad_t(a, tk_p) for a in (k_mu, v_mu, v_var))

    bh = b * h
    q_mu = q_mu.reshape(bh, tq_p, d)
    k_mu = k_mu.reshape(b * hkv, tk_p, d)
    v_mu = v_mu.reshape(b * hkv, tk_p, d)
    v_var = v_var.reshape(b * hkv, tk_p, d)
    nk = tk_p // bk

    q_spec = pl.BlockSpec((1, bq, d), lambda bh_, i, k_, qs, kl: (bh_, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d),
                           lambda bh_, i, k_, qs, kl: (bh_ // group, k_, 0))
    out_spec = pl.BlockSpec((1, bq, d), lambda bh_, i, k_, qs, kl: (bh_, i, 0))

    kernel = functools.partial(
        _cache_attn_kernel,
        scale=scale, bq=bq, bk=bk, heads=h, causal=causal, window=window,
        nk=nk,
    )
    common = dict(
        grid_spec=_grid_spec(2, (bh, tq_p // bq, nk),
                             [q_spec, kv_spec, kv_spec, kv_spec],
                             [out_spec, out_spec], bq, d),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq_p, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, tq_p, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params((dims, dims, "arbitrary")),
    )
    fn = pl.pallas_call(kernel, **common)
    out_mu, out_var = fn(q_start.astype(jnp.int32), kv_len.astype(jnp.int32),
                         q_mu, k_mu, v_mu, v_var)
    out_mu = out_mu.reshape(b, h, tq_p, d)[:, :, :tq]
    out_var = out_var.reshape(b, h, tq_p, d)[:, :, :tq]
    return out_mu, out_var


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "block_q", "prefetch",
                     "dims", "interpret"),
)
def pfp_attention_paged_pallas(
    q_mu, k_pages, v_pages, vv_pages, page_table, q_start, kv_len,
    *,
    scale: float,
    causal: bool = True,
    window=None,
    block_q: int = 128,
    prefetch: int = 1,
    dims: str = "parallel",
    interpret: bool = False,
):
    """Paged KV-cache attention: page-table-indirect K/V DMA.

    q (B, H, Tq, D) x pages (NP, Hkv, page_size, D); page_table (B, P)
    int32 maps batch b's j-th logical page to a physical page row. The
    table is scalar-prefetched and consumed by the KV BlockSpec index
    maps, so each K-step DMAs its pages straight from the pool — the pool
    is never gathered into a per-batch contiguous cache. block_k IS the
    page size; kv_len gives per-batch valid length, i.e. per-page valid
    row counts. ``prefetch`` logical pages ride each K-step (P is padded
    to a multiple with physical page 0 as a trash target — those pages
    sit at absolute key positions >= kv_len, so masking zeroes them).
    """
    b, h, tq, d = q_mu.shape
    np_, hkv, ps, _ = k_pages.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    p = page_table.shape[1]
    depth = max(1, min(int(prefetch), p))
    bq = min(block_q, tq)
    tq_p = tq + ((-tq) % bq)
    q_mu = _pad_t(q_mu, tq_p)

    p_pad = p + ((-p) % depth)
    if p_pad != p:
        page_table = jnp.pad(page_table, ((0, 0), (0, p_pad - p)))

    bh = b * h
    q_mu = q_mu.reshape(bh, tq_p, d)
    # Page p's head j lives at flat row p*Hkv + j (the reshape is a view).
    k_pages = k_pages.reshape(np_ * hkv, ps, d)
    v_pages = v_pages.reshape(np_ * hkv, ps, d)
    vv_pages = vv_pages.reshape(np_ * hkv, ps, d)

    q_spec = pl.BlockSpec((1, bq, d),
                          lambda bh_, i, k_, qs, kl, tab: (bh_, i, 0))

    def kv_spec(j):
        return pl.BlockSpec(
            (1, ps, d),
            lambda bh_, i, k_, qs, kl, tab: (
                tab[bh_ // h, k_ * depth + j] * hkv + (bh_ % h) // group,
                0, 0))

    out_spec = pl.BlockSpec((1, bq, d),
                            lambda bh_, i, k_, qs, kl, tab: (bh_, i, 0))

    nk = p_pad // depth
    kernel = functools.partial(
        _paged_attn_kernel,
        scale=scale, bq=bq, bk=ps, heads=h, causal=causal, window=window,
        nk=nk, depth=depth,
    )
    kv_specs = ([kv_spec(j) for j in range(depth)] * 3)
    common = dict(
        grid_spec=_grid_spec(3, (bh, tq_p // bq, nk),
                             [q_spec] + kv_specs,
                             [out_spec, out_spec], bq, d),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq_p, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, tq_p, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params((dims, dims, "arbitrary")),
    )
    fn = pl.pallas_call(kernel, **common)
    kv_args = ([k_pages] * depth + [v_pages] * depth + [vv_pages] * depth)
    out_mu, out_var = fn(q_start.astype(jnp.int32), kv_len.astype(jnp.int32),
                         page_table.astype(jnp.int32),
                         q_mu, *kv_args)
    out_mu = out_mu.reshape(b, h, tq_p, d)[:, :, :tq]
    out_var = out_var.reshape(b, h, tq_p, d)[:, :, :tq]
    return out_mu, out_var


def _pad_t(a, t_to):
    pad = t_to - a.shape[2]
    if pad:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return a


def _attn_scratch(bq, d):
    return [pltpu.VMEM(shape, jnp.float32)
            for shape in ((bq, _LANES), (bq, _LANES), (bq, d), (bq, d))]
