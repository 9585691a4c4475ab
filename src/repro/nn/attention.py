"""Multi-head attention: GQA/MQA, local windows, cross-attention, KV cache.

Execution modes:
  DETERMINISTIC / SVI : standard softmax attention on (sampled) weights.
  PFP                 : mean-field attention (DESIGN.md §4) — probabilities
      from score means (optionally probit-corrected), mean out = A @ mu_v,
      var out = A^2 @ var_v. The KV cache stores (mu_k, mu_v, var_v) so
      value uncertainty survives across decode steps.

Two KV-cache layouts share one decode math:

  KVCache      contiguous per-sequence buffers (B, Hkv, S, Dh) — training,
               prefill and non-engine decode; also the degenerate
               one-page-per-slot case of the paged layout.
  PagedKVCache a global pool of fixed-size pages (NP, Hkv, page_size, Dh)
               shared by every sequence; a per-batch ``page_table`` (B, P)
               maps logical page j of batch b to a pool row. The serving
               engine's page-pool state manager owns the table.

Grouped-query attention keeps K/V at ``num_kv_heads`` and groups queries;
all einsums are grouped (no materialized KV repetition).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import dispatch, pfp_math
from repro.core.gaussian import GaussianTensor, VAR, is_gaussian
from repro.core.masking import NEG_INF, attention_valid_mask, mask_scores
from repro.nn.layers import dense_apply, dense_init, rope_angles, rope_apply
from repro.nn.module import Context


class KVCache(NamedTuple):
    k_mu: jax.Array   # (B, Hkv, S, Dh)
    v_mu: jax.Array   # (B, Hkv, S, Dh)
    v_var: jax.Array  # (B, Hkv, S, Dh) — zeros outside PFP mode


class PagedKVCache(NamedTuple):
    """Paged Gaussian KV cache: page-pool decode layout.

    Leaves are GLOBAL page pools of shape (num_pages, Hkv, page_size, Dh)
    shared by all sequences; which pages belong to which sequence lives
    outside the pytree in an int32 ``page_table`` (B, P) threaded through
    decode inputs (all layers share one table; each layer owns its own
    pool buffers). Contract: page 0 is reserved as the trash page — cache
    inserts at positions >= ``cache_len`` (a prefill window's right
    padding, a parked lockstep slot) are redirected there, so they can
    never alias a live sequence's pages.
    """
    k_mu: jax.Array   # (NP, Hkv, page_size, Dh)
    v_mu: jax.Array   # (NP, Hkv, page_size, Dh)
    v_var: jax.Array  # (NP, Hkv, page_size, Dh)


def attention_init(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, *, sigma_init=1e-4, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim,
                         sigma_init=sigma_init, dtype=dtype),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim,
                         sigma_init=sigma_init, dtype=dtype),
        "wv": dense_init(ks[2], d_model, num_kv_heads * head_dim,
                         sigma_init=sigma_init, dtype=dtype),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model,
                         sigma_init=sigma_init, dtype=dtype),
    }


def _split_heads(x, num_heads: int, head_dim: int):
    if is_gaussian(x):
        return GaussianTensor(
            _split_heads(x.mean, num_heads, head_dim),
            _split_heads(x.second, num_heads, head_dim),
            x.rep,
        )
    b, t, _ = x.shape
    return x.reshape(b, t, num_heads, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    if is_gaussian(x):
        return GaussianTensor(_merge_heads(x.mean), _merge_heads(x.second), x.rep)
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _gather_pages(pages, page_table):
    """(NP, Hkv, ps, D) x (B, P) -> contiguous (B, Hkv, P*ps, D) view of a
    paged pool — the gather-based path; the Pallas kernel instead DMAs
    pages in place via its scalar-prefetched index map."""
    from repro.kernels.ref import gather_kv_pages  # lazy: keep nn importable
    #                                                without the kernels pkg

    return gather_kv_pages(pages, page_table)


def attention_apply(
    params,
    x,
    ctx: Context,
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    positions: jax.Array,              # (B, Tq) absolute positions
    causal: bool = True,
    window: Optional[int] = None,
    rope_theta: Optional[float] = 1e4, # None = no rotary (e.g. cross attn)
    cross_kv=None,                     # (B, S, d_model) overrides self K/V
    cache=None,                        # KVCache | PagedKVCache: append at
    #                                    `positions`
    cache_len: Optional[jax.Array] = None,  # valid entries in cache
    page_table: Optional[jax.Array] = None,  # (B, P) int32, PagedKVCache only
    write_start: Optional[jax.Array] = None,  # (B,) int32: first position this
    #                                  pass may WRITE (PagedKVCache only) —
    #                                  rows below it are prefix pages shared
    #                                  copy-on-write with other sequences
    standard_positions: bool = False,  # static: positions are 0..Tq-1 arange
):
    """Returns (output, new_cache|None). x: (B, Tq, d_model) or Gaussian."""
    scale = head_dim ** -0.5
    group = num_heads // num_kv_heads

    q = _split_heads(dense_apply(params["wq"], x, ctx), num_heads, head_dim)
    kv_src = cross_kv if cross_kv is not None else x
    k = _split_heads(dense_apply(params["wk"], kv_src, ctx), num_kv_heads, head_dim)
    v = _split_heads(dense_apply(params["wv"], kv_src, ctx), num_kv_heads, head_dim)

    if rope_theta is not None:
        cos, sin = rope_angles(positions, head_dim, rope_theta)  # (B, T, Dh/2)
        cos, sin = cos[:, None], sin[:, None]                    # (B, 1, T, ...)
        q = rope_apply(q, cos, sin)
        if cross_kv is None:
            k = rope_apply(k, cos, sin)

    pfp = is_gaussian(q)
    k_mu = k.mean if pfp else k
    v_mu = v.mean if pfp else v
    v_var = v.var if pfp else jnp.zeros_like(v_mu)

    new_cache = None
    paged = isinstance(cache, PagedKVCache)
    kv_len = None  # (B,) per-batch valid cache length (cache paths only)
    if paged:
        if page_table is None or cache_len is None:
            raise ValueError("PagedKVCache requires page_table and cache_len")
        ps = cache.k_mu.shape[2]
        kv_len = cache_len
        # Insert new K/V rows at each token's (page, row) destination:
        # page_table[b, pos // ps] row pos % ps. Rows at positions >=
        # cache_len — a static prefill window's right padding, a parked
        # lockstep slot — are redirected to the reserved trash page 0, so
        # a lockstep pass over the shared pool can never write another
        # sequence's pages (the paged analogue of select-merge). Rows
        # BELOW ``write_start`` are redirected the same way: they are a
        # re-fed window's overlap with a copy-on-write-shared prompt
        # prefix — the shared pages already hold the identical k/v rows,
        # and writing through would force a pointless private copy.
        # Speculative verify leans on the same two redirects: a chunked
        # verify window writes its K drafted rows through this path, and
        # rejected rows need no explicit rollback — the engine simply does
        # not advance ``cache_len`` past the accepted prefix, so the next
        # pass masks the stale rows out of attention and re-feeds their
        # positions (overwriting them in place, or trash-redirecting via
        # the same ``writable`` test if they fall outside the window).
        writable = positions < cache_len[:, None]
        if write_start is not None:
            writable = jnp.logical_and(writable,
                                       positions >= write_start[:, None])
        dest_page = jnp.where(
            writable,
            jnp.take_along_axis(page_table, positions // ps, axis=1), 0)
        dest_row = positions % ps

        def _insert_pages(buf, new):
            # new (B, Hkv, Tq, Dh) -> rows (B, Tq, Hkv, Dh) scattered to
            # buf[(B, Tq) pages, :, (B, Tq) rows].
            vals = new.astype(buf.dtype).transpose(0, 2, 1, 3)
            return buf.at[dest_page, :, dest_row].set(vals)

        cache = PagedKVCache(_insert_pages(cache.k_mu, k_mu),
                             _insert_pages(cache.v_mu, v_mu),
                             _insert_pages(cache.v_var, v_var))
        new_cache = cache
        k_pos = k_valid = None  # derived after the gather (XLA path only)
    elif cache is not None:
        # Insert the new K/V rows at each batch element's own offset
        # (positions[b, 0] — continuous-batching slots sit at independent
        # positions; lockstep callers simply pass equal offsets).
        # Decode (Tq=1): pin the updated cache to the input-cache sharding —
        # a single-token dynamic-update-slice otherwise makes GSPMD
        # replicate the whole cache inside the layer scan. Prefill (full
        # Tq): keep the natural (heads x dim)-sharded layout; forcing
        # seq-sharding there costs a full reshard copy per layer.
        from repro.nn.pjit_hints import constrain_kv

        pin = (lambda a: constrain_kv(a)) if positions.shape[1] == 1 \
            else (lambda a: a)
        starts = positions[:, 0]

        def _insert(buf, new):
            upd = jax.vmap(lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(
                c, n, s, axis=1))(buf, new.astype(buf.dtype), starts)
            return pin(upd)

        cache = KVCache(_insert(cache.k_mu, k_mu),
                        _insert(cache.v_mu, v_mu),
                        _insert(cache.v_var, v_var))
        new_cache = cache
        k_mu, v_mu, v_var = cache.k_mu, cache.v_mu, cache.v_var
        s = k_mu.shape[2]
        kv_len = (cache_len if cache_len is not None
                  else positions[:, -1] + 1)
        k_pos = jnp.broadcast_to(jnp.arange(s), (positions.shape[0], s))
        k_valid = k_pos < kv_len[:, None]
    else:
        s = k_mu.shape[2]
        if cross_kv is not None:
            k_pos = jnp.broadcast_to(jnp.arange(s), (positions.shape[0], s))
            k_valid = None
            causal = False
        else:
            k_pos = positions
            k_valid = None

    # Grouped-query core: q (B, Hkv, G, Tq, Dh) x k/v (B, Hkv, Tk, Dh).
    def _group(arr):
        b, h, t, d = arr.shape
        return arr.reshape(b, num_kv_heads, group, t, d)

    q_mu = _group(q.mean if pfp else q)
    q_var = _group(q.var) if (pfp and ctx.attention_mode ==
                              "variance_corrected") else None

    # Registry fast paths: mean-field PFP attention lowers to the
    # flash-style Pallas kernels via the impl-dispatch registry.
    #   * cache paths (contiguous or paged) always qualify: per-batch
    #     query starts + valid lengths (and sliding windows) are native to
    #     the cache/paged kernels' scalar-prefetch masking, and the cache
    #     insert contract guarantees positions are contiguous from each
    #     batch row's start — no `standard_positions` promise needed;
    #   * the cache-free path keeps the original conditions: cases the
    #     index-based mask cannot express stay on the chunked XLA core
    #     below (probit-corrected scores, windows, and causal masking
    #     under caller-remapped position ids).
    use_kernel = (pfp and dispatch.resolve_impl(ctx.impl) == "kernel"
                  and q_var is None)
    if use_kernel and cache is not None:
        q_start = positions[:, 0]
        if paged:
            out_mu, out_var = _attention_paged_registry(
                q_mu, cache, page_table, q_start, kv_len, group=group,
                scale=scale, causal=causal, window=window, impl=ctx.impl)
        else:
            out_mu, out_var = _attention_cache_registry(
                q_mu, k_mu, v_mu, v_var, q_start, kv_len, group=group,
                scale=scale, causal=causal, window=window, impl=ctx.impl)
    elif (use_kernel and cache is None and window is None
          and k_valid is None and (standard_positions or not causal)):
        out_mu, out_var = _attention_registry(
            q_mu, k_mu, v_mu, v_var, group=group, scale=scale, causal=causal,
            impl=ctx.impl)
    else:
        if pfp and dispatch.resolve_impl(ctx.impl) == "kernel":
            # The kernel stack was asked for, but this case (probit-
            # corrected scores, a cache-free window or remapped causal
            # positions) has no kernel: count it, don't hide it.
            dispatch.note_fallback("attention:xla_core")
        if paged:
            # Gather the pool pages into the contiguous layout, then run
            # the exact same chunked core as the contiguous cache path —
            # paged XLA decode is bit-for-bit the contiguous decode.
            k_mu, v_mu, v_var = (_gather_pages(a, page_table) for a in cache)
            s = k_mu.shape[2]
            k_pos = jnp.broadcast_to(jnp.arange(s), (positions.shape[0], s))
            k_valid = k_pos < kv_len[:, None]
        out_mu, out_var = _attention_core(
            q_mu, q_var, k_mu, v_mu, v_var if pfp else None,
            q_pos=positions, k_pos=k_pos, k_valid=k_valid,
            causal=causal, window=window, scale=scale,
            chunk_size=_QUERY_CHUNK,
        )
    b = out_mu.shape[0]
    out_mu = out_mu.reshape(b, num_heads, -1, head_dim)
    if pfp:
        out_var = out_var.reshape(b, num_heads, -1, head_dim)
        out = GaussianTensor(out_mu, out_var, VAR)
    else:
        out = out_mu

    out = _merge_heads(out)
    out = dense_apply(params["wo"], out, ctx)
    return out, new_cache


# Query-block size for the chunked (flash-style at XLA level) path: the
# (bq, Tk) score tile is the peak attention memory, never (Tq, Tk).
_QUERY_CHUNK = 1024


def _attention_registry(q_mu, k_mu, v_mu, v_var, *, group, scale, causal,
                        impl):
    """Dispatch grouped attention through the registry op.

    Queries collapse their (Hkv, G) grouping into kv-major full heads; K/V
    stay at Hkv heads — the registry op is GQA-aware and the Pallas kernel
    maps query head -> shared KV tile in its BlockSpec index map, so no
    repeated KV buffer is materialized.
    """
    b, hkv, g, tq, dh = q_mu.shape
    qf = q_mu.reshape(b, hkv * g, tq, dh)
    out_mu, out_var = dispatch.pfp_attention(
        qf, k_mu, v_mu, v_var, scale=scale, causal=causal, impl=impl)
    return (out_mu.reshape(b, hkv, g, tq, dh),
            out_var.reshape(b, hkv, g, tq, dh))


def _attention_cache_registry(q_mu, k_mu, v_mu, v_var, q_start, kv_len, *,
                              group, scale, causal, window, impl):
    """Contiguous KV-cache decode through the registry 'attention_cache'
    op: per-batch query starts and valid lengths ride scalar prefetch, so
    the previous chunked-XLA `tk_valid` fallback is gone."""
    b, hkv, g, tq, dh = q_mu.shape
    qf = q_mu.reshape(b, hkv * g, tq, dh)
    out_mu, out_var = dispatch.pfp_attention_cache(
        qf, k_mu, v_mu, v_var, q_start, kv_len, scale=scale, causal=causal,
        window=window, impl=impl)
    return (out_mu.reshape(b, hkv, g, tq, dh),
            out_var.reshape(b, hkv, g, tq, dh))


def _attention_paged_registry(q_mu, cache, page_table, q_start, kv_len, *,
                              group, scale, causal, window, impl):
    """Paged KV-cache decode through the registry 'attention_paged' op:
    the page table drives the kernel's KV DMA, no contiguous gather."""
    b, hkv, g, tq, dh = q_mu.shape
    qf = q_mu.reshape(b, hkv * g, tq, dh)
    out_mu, out_var = dispatch.pfp_attention_paged(
        qf, cache.k_mu, cache.v_mu, cache.v_var, page_table, q_start, kv_len,
        scale=scale, causal=causal, window=window, impl=impl)
    return (out_mu.reshape(b, hkv, g, tq, dh),
            out_var.reshape(b, hkv, g, tq, dh))


def _attention_core(q_mu, q_var, k_mu, v_mu, v_var, *, q_pos, k_pos,
                    k_valid, causal, window, scale, chunk_size):
    """Grouped masked softmax attention with joint mean/var outputs.

    q_mu: (B, Hkv, G, Tq, D); k/v: (B, Hkv, Tk, D); q_pos: (B, Tq);
    k_pos: (B, Tk); k_valid: (B, Tk) bool or None. Long queries are
    processed in blocks of `chunk_size` via lax.scan so the materialized
    score tile is (bq, Tk) — the XLA-graph analogue of the Pallas flash
    kernel (kernels/pfp_attention.py), used by the pjit'd programs.
    Returns (out_mu, out_var[PFP] | None).
    """
    tq = q_mu.shape[3]

    def block(args):
        qb_mu, qb_var, qb_pos = args  # (B,Hkv,G,bq,D), (B,bq)
        scores = jnp.einsum("bhgqd,bhkd->bhgqk", qb_mu, k_mu) * scale
        if qb_var is not None:
            score_var = (
                jnp.einsum("bhgqd,bhkd->bhgqk", qb_var, jnp.square(k_mu))
            ) * (scale * scale)
            scores = pfp_math.probit_corrected_logits(scores, score_var)
        mask = attention_valid_mask(qb_pos[..., :, None], k_pos[..., None, :],
                                    causal=causal,
                                    window=window if window else None)
        if k_valid is not None:
            mask = jnp.logical_and(mask, k_valid[..., None, :])
        scores = mask_scores(scores, mask[:, None, None])
        probs = jax.nn.softmax(scores, axis=-1)
        o_mu = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v_mu)
        o_var = (jnp.einsum("bhgqk,bhkd->bhgqd", jnp.square(probs), v_var)
                 if v_var is not None else None)
        return o_mu, o_var

    if tq <= chunk_size or tq % chunk_size != 0:
        return block((q_mu, q_var, q_pos))

    nb = tq // chunk_size

    def to_blocks(a, axis):
        a = a.reshape(a.shape[:axis] + (nb, chunk_size) + a.shape[axis + 1:])
        return jnp.moveaxis(a, axis, 0)

    xs = (
        to_blocks(q_mu, 3),
        to_blocks(q_var, 3) if q_var is not None else jnp.zeros((nb,)),
        to_blocks(q_pos, 1),
    )

    # Remat the per-block attention: backward recomputes the (bq, Tk) score
    # tile instead of saving probs for every block (O(Tq*Tk) -> O(bq*Tk)).
    block_ckpt = jax.checkpoint(block)

    def body(_, x):
        qb_mu, qb_var, qb_pos = x
        if q_var is None:
            qb_var = None
        return None, block_ckpt((qb_mu, qb_var, qb_pos))

    _, (o_mu, o_var) = jax.lax.scan(body, None, xs)
    # (nb, B, Hkv, G, bq, D) -> (B, Hkv, G, Tq, D)
    o_mu = jnp.moveaxis(o_mu, 0, 3).reshape(q_mu.shape)
    if o_var is not None:
        o_var = jnp.moveaxis(o_var, 0, 3).reshape(q_mu.shape)
    return o_mu, o_var


def init_kv_cache(batch: int, num_kv_heads: int, max_len: int, head_dim: int,
                  dtype=jnp.float32) -> KVCache:
    shape = (batch, num_kv_heads, max_len, head_dim)
    return KVCache(
        jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    )


def init_paged_kv_cache(num_pages: int, num_kv_heads: int, page_size: int,
                        head_dim: int, dtype=jnp.float32) -> PagedKVCache:
    """Zeroed page pool. ``num_pages`` INCLUDES the reserved trash page 0;
    a contiguous (B, Hkv, S, D) cache is the degenerate layout with one
    page per sequence of page_size == S and an identity page table."""
    shape = (num_pages, num_kv_heads, page_size, head_dim)
    return PagedKVCache(
        jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    )
