"""Decoder-LM assembly for every assigned architecture family.

One definition covers: dense transformers (granite/yi/gemma/internlm2),
MoE (deepseek-moe, llama4-scout), VLM backbones (llama-3.2-vision),
hybrids (recurrentgemma), audio decoders (musicgen) and SSMs (mamba2).

Layers are stacked into *super-block groups* (cfg.pattern) and scanned with
``jax.lax.scan`` so a 100-layer model lowers to O(1)-size HLO — the
multi-pod dry-run depends on this. Layers that do not tile evenly form an
unscanned tail.

The same definition serves three programs:
  forward()      train/eval full-sequence pass (optionally remat'd)
  prefill()      full-sequence pass that also fills decode state
  decode_step()  single-token step against stacked per-layer state
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.gaussian import GaussianTensor, VAR, is_gaussian
from repro.core.modes import Mode
from repro.nn.attention import (KVCache, PagedKVCache, attention_apply,
                                attention_init, init_kv_cache,
                                init_paged_kv_cache)
from repro.nn.layers import (NORMS, dense_apply, dense_init, embedding_apply,
                             embedding_init, residual_add,
                             sinusoidal_embedding)
from repro.nn.mlp import mlp_apply, mlp_init
from repro.nn.moe import merge_aux, moe_apply, moe_init, zero_aux
from repro.nn.pjit_hints import constrain
from repro.nn.module import Context
from repro.nn.recurrent import (RecurrentState, init_recurrent_state,
                                rglru_block_apply, rglru_init)
from repro.nn.ssm import SSMState, init_ssm_state, mamba2_apply, mamba2_init


# ---------------------------------------------------------------------------
# Block init/apply
# ---------------------------------------------------------------------------
def _block_init(kind: str, cfg: ModelConfig, key, d_ff: int = 0):
    """``d_ff`` (0 = ``cfg.d_ff``) is the FFN width of a dense block."""
    norm_init_fn = NORMS[cfg.norm][0]
    ks = jax.random.split(key, 3)
    si = cfg.sigma_init
    if kind in ("attn", "cross"):
        return {
            "ln1": norm_init_fn(cfg.d_model),
            "attn": attention_init(ks[0], cfg.d_model, cfg.num_heads,
                                   cfg.num_kv_heads, cfg.head_dim,
                                   sigma_init=si),
            "ln2": norm_init_fn(cfg.d_model),
            "mlp": mlp_init(ks[1], cfg.d_model, d_ff or cfg.d_ff,
                            gated=cfg.gated_mlp, sigma_init=si),
        }
    if kind == "moe":
        return {
            "ln1": norm_init_fn(cfg.d_model),
            "attn": attention_init(ks[0], cfg.d_model, cfg.num_heads,
                                   cfg.num_kv_heads, cfg.head_dim,
                                   sigma_init=si),
            "ln2": norm_init_fn(cfg.d_model),
            "moe": moe_init(ks[1], cfg.d_model, cfg.d_ff, cfg.num_experts,
                            num_shared=cfg.num_shared_experts,
                            gated=cfg.gated_mlp, sigma_init=si,
                            router_width=cfg.router_width),
        }
    if kind == "rec":
        return {
            "ln1": norm_init_fn(cfg.d_model),
            "rec": rglru_init(ks[0], cfg.d_model, cfg.d_rnn or cfg.d_model,
                              sigma_init=si),
            "ln2": norm_init_fn(cfg.d_model),
            "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                            gated=cfg.gated_mlp, sigma_init=si),
        }
    if kind == "ssm":
        return {
            "ln1": norm_init_fn(cfg.d_model),
            "ssm": mamba2_init(ks[0], cfg.d_model, d_state=cfg.ssm_state,
                               expand=cfg.ssm_expand,
                               head_dim=cfg.ssm_head_dim, sigma_init=si),
        }
    raise ValueError(kind)


def moe_options(cfg: ModelConfig) -> dict:
    """``nn.moe.moe_apply``'s keyword arguments for ``cfg``'s MoE blocks:
    the expert share, the router and the gate rule."""
    return dict(num_experts=cfg.num_experts, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                activation=cfg.activation, dispatch_mode=cfg.moe_dispatch,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_experts=cfg.routed_experts,
                expert_offset=cfg.expert_offset)


def _block_apply(kind: str, params, x, ctx: Context, cfg: ModelConfig, *,
                 positions, image_emb=None, state=None, cache_len=None,
                 page_table=None, write_start=None,
                 standard_positions=False, moe_aux_loss=True, valid=None):
    """Returns (x, new_state, aux) — aux is the MoE aux dict
    (``nn.moe.zero_aux``), zeros for non-MoE blocks. ``valid`` (B, T) marks
    the rows that are not padding; the MoE block routes only those."""
    norm_apply = NORMS[cfg.norm][1]
    aux = zero_aux()
    new_state = None

    if kind in ("attn", "cross", "moe"):
        h = norm_apply(params["ln1"], x, ctx)
        attn_out, new_state = attention_apply(
            params["attn"], h, ctx,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, positions=positions,
            causal=(kind != "cross"),
            window=cfg.window or None if kind == "attn" else None,
            rope_theta=cfg.rope_theta if (cfg.positional == "rope"
                                          and kind != "cross") else None,
            cross_kv=image_emb if kind == "cross" else None,
            cache=state if kind != "cross" else None,
            cache_len=cache_len,
            page_table=page_table if kind != "cross" else None,
            write_start=write_start if kind != "cross" else None,
            standard_positions=standard_positions,
        )
        x = residual_add(x, attn_out)
        h = norm_apply(params["ln2"], x, ctx)
        if kind == "moe":
            # The inference path (no load-balance loss) serves dropless
            # over this program's expert share; training keeps capacity.
            ffn_out, aux = moe_apply(params["moe"], h, ctx,
                                     aux_loss=moe_aux_loss, valid=valid,
                                     **moe_options(cfg))
        else:
            ffn_out = mlp_apply(params["mlp"], h, ctx, activation=cfg.activation)
        x = residual_add(x, ffn_out)
        return x, new_state, aux

    if kind == "rec":
        h = norm_apply(params["ln1"], x, ctx)
        rec_out, new_state = rglru_block_apply(params["rec"], h, ctx, state=state)
        x = residual_add(x, rec_out)
        h = norm_apply(params["ln2"], x, ctx)
        ffn_out = mlp_apply(params["mlp"], h, ctx, activation=cfg.activation)
        x = residual_add(x, ffn_out)
        return x, new_state, aux

    if kind == "ssm":
        h = norm_apply(params["ln1"], x, ctx)
        ssm_out, new_state = mamba2_apply(
            params["ssm"], h, ctx, d_state=cfg.ssm_state,
            expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
            chunk=min(cfg.ssm_chunk, x.shape[1]), state=state)
        x = residual_add(x, ssm_out)
        return x, new_state, aux

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------
def _group_counts(cfg: ModelConfig):
    lpg = len(cfg.pattern)
    num_scanned = ((cfg.num_layers - cfg.first_dense_layers) // lpg)
    tail = cfg.num_layers - cfg.first_dense_layers - num_scanned * lpg
    return lpg, num_scanned, tail


def init_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 8)
    params: dict[str, Any] = {}
    if cfg.embed_inputs:
        params["embed"] = embedding_init(ks[0], cfg.vocab_size, cfg.d_model,
                                         sigma_init=cfg.sigma_init)
    # Leading unscanned layers (e.g. DeepSeekMoE's first dense-FFN layer).
    head_cfg = cfg
    for i in range(cfg.first_dense_layers):
        params[f"head{i}"] = _block_init("attn", cfg,
                                         jax.random.fold_in(ks[1], i),
                                         d_ff=cfg.dense_ffn_width)

    lpg, num_groups, tail = _group_counts(cfg)

    def one_group(k):
        kk = jax.random.split(k, lpg)
        return {f"b{i}": _block_init(cfg.pattern[i], cfg, kk[i])
                for i in range(lpg)}

    if num_groups:
        params["stack"] = jax.vmap(one_group)(jax.random.split(ks[2], num_groups))
    for i in range(tail):
        kind = cfg.pattern[i % lpg]
        params[f"tail{i}"] = _block_init(kind, cfg, jax.random.fold_in(ks[3], i))

    params["ln_f"] = NORMS[cfg.norm][0](cfg.d_model)
    params["lm_head"] = dense_init(ks[4], cfg.d_model, cfg.vocab_size,
                                   sigma_init=cfg.sigma_init)
    return params


# ---------------------------------------------------------------------------
# Forward (train / eval / prefill)
# ---------------------------------------------------------------------------
def _embed_inputs(params, cfg: ModelConfig, inputs, ctx: Context):
    """Token embedding or stub-frontend embeddings (audio/vlm)."""
    if cfg.embed_inputs:
        x = embedding_apply(params["embed"], inputs["tokens"], ctx)
        t = inputs["tokens"].shape[1]
        b = inputs["tokens"].shape[0]
    else:
        x = inputs["frame_embeddings"]
        b, t = x.shape[0], x.shape[1]
        if ctx.mode == Mode.PFP:
            x = GaussianTensor.deterministic(x)
    if ctx.compute_dtype is not None:
        x = x.astype(ctx.compute_dtype)
    if cfg.positional == "sinusoidal":
        pos_emb = sinusoidal_embedding(jnp.arange(t), cfg.d_model).astype(
            x.dtype)
        x = residual_add(x, jnp.broadcast_to(pos_emb, (b, t, cfg.d_model))) \
            if is_gaussian(x) else x + pos_emb
    # Whether positions are the default 0..T-1 arange is a *static* fact
    # (did the caller supply them?): the kernel-attention fast path masks
    # causally by index and is only valid for the default layout.
    standard_positions = "positions" not in inputs
    positions = inputs.get(
        "positions", jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t)))
    return x, positions, standard_positions


def forward(params, cfg: ModelConfig, inputs, ctx: Context, *,
            remat: bool = False, states=None, collect_states: bool = False,
            moe_aux_loss: bool = True):
    """Full-sequence pass.

    states/collect_states support the prefill program: pass initialized
    per-layer states and get back the filled ones alongside the output.
    Returns (logits, aux, new_states) — aux is the MoE aux dict reduced
    over layers (``nn.moe.zero_aux``, f32 scalars).
    ``moe_aux_loss=False`` is the aux-loss-free inference path: decode and
    prefill graphs never build the router's load-balance loss term, and
    MoE blocks route dropless, padding rows (positions at or past
    ``cache_len``) to no expert.
    """
    x, positions, standard_positions = _embed_inputs(params, cfg, inputs, ctx)
    x = constrain(x, "batch", "seq", "embed")
    image_emb = inputs.get("image_embeddings")
    if image_emb is not None and ctx.mode == Mode.PFP:
        image_emb = GaussianTensor.deterministic(image_emb)
    # Decode-state validity/indirection, shared by every layer: per-batch
    # valid cache length, (paged decode) the slot -> page-pool table, and
    # (prefix-shared paged decode) the first position a slot may write —
    # positions below it live in copy-on-write-shared prefix pages.
    cache_len = inputs.get("cache_len")
    page_table = inputs.get("page_table")
    write_start = inputs.get("write_start")
    valid = (positions < cache_len[:, None] if cache_len is not None
             else None)

    lpg, num_groups, tail = _group_counts(cfg)
    aux_total = zero_aux()
    _acc = merge_aux

    for i in range(cfg.first_dense_layers):
        st = None if states is None else states.get(f"head{i}")
        x, new_st, aux = _block_apply("attn", params[f"head{i}"], x,
                                      ctx.with_layer(1000 + i), cfg,
                                      positions=positions, state=st,
                                      cache_len=cache_len,
                                      page_table=page_table,
                                      write_start=write_start,
                                      standard_positions=standard_positions,
                                      moe_aux_loss=moe_aux_loss, valid=valid)
        aux_total = _acc(aux_total, aux)
        if collect_states and states is not None:
            states[f"head{i}"] = new_st

    new_stack_states = None
    if num_groups:
        def body(carry, xs):
            x, aux_acc = carry
            in_dtype = x.dtype
            x = constrain(x, "batch", "seq", "embed")
            if states is None:
                gp, gi = xs
                gst = {}
            else:
                gp, gst, gi = xs
            lctx = ctx.with_layer(gi)
            new_sts = {}
            for i in range(lpg):
                kind = cfg.pattern[i]
                st = gst.get(f"b{i}") if states is not None else None

                def run_block(x_, gp_i, st_, _kind=kind):
                    return _block_apply(
                        _kind, gp_i, x_, lctx, cfg,
                        positions=positions, image_emb=image_emb, state=st_,
                        cache_len=cache_len, page_table=page_table,
                        write_start=write_start,
                        standard_positions=standard_positions,
                        moe_aux_loss=moe_aux_loss, valid=valid)

                # Nested remat: per-layer checkpoints inside the remat'd
                # group bound the backward live-set to ONE layer.
                if remat:
                    run_block = jax.checkpoint(run_block)
                x, nst, aux = run_block(x, gp[f"b{i}"], st)
                aux_acc = _acc(aux_acc, aux)
                if st is not None:
                    new_sts[f"b{i}"] = nst
            x = x.astype(in_dtype)  # carry dtype stability across scan steps
            return (x, aux_acc), (new_sts if new_sts else None)

        body_fn = jax.checkpoint(body) if remat else body
        gidx = jnp.arange(num_groups)
        if states is None:
            xs = (params["stack"], gidx)
        else:
            xs = (params["stack"], states["stack"], gidx)
        (x, aux_total), scanned_states = jax.lax.scan(
            body_fn, (x, aux_total), xs)
        new_stack_states = scanned_states

    for i in range(tail):
        kind = cfg.pattern[i % lpg]
        st = None if states is None else states.get(f"tail{i}")
        x, new_st, aux = _block_apply(kind, params[f"tail{i}"], x,
                                      ctx.with_layer(2000 + i), cfg,
                                      positions=positions,
                                      image_emb=image_emb, state=st,
                                      cache_len=cache_len,
                                      page_table=page_table,
                                      write_start=write_start,
                                      standard_positions=standard_positions,
                                      moe_aux_loss=moe_aux_loss, valid=valid)
        aux_total = _acc(aux_total, aux)
        if collect_states and states is not None:
            states[f"tail{i}"] = new_st

    x = NORMS[cfg.norm][1](params["ln_f"], x, ctx)
    x = constrain(x, "batch", "seq", "embed")
    logits = dense_apply(params["lm_head"], x, ctx)
    logits = constrain(logits, "batch", "seq", "vocab")

    out_states = None
    if collect_states and states is not None:
        out_states = dict(states)
        if new_stack_states is not None:
            out_states["stack"] = new_stack_states
    return logits, aux_total, out_states


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------
def _state_for_kind(kind: str, cfg: ModelConfig, batch: int, max_len: int):
    if kind in ("attn", "moe"):
        return init_kv_cache(batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    if kind == "cross":
        return None  # cross K/V recomputed from image embeddings each step
    if kind == "rec":
        return init_recurrent_state(batch, cfg.d_rnn or cfg.d_model)
    if kind == "ssm":
        return init_ssm_state(batch, cfg.d_model, d_state=cfg.ssm_state,
                              expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim)
    raise ValueError(kind)


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    lpg, num_groups, tail = _group_counts(cfg)
    states: dict[str, Any] = {}
    for i in range(cfg.first_dense_layers):
        states[f"head{i}"] = _state_for_kind("attn", cfg, batch, max_len)

    if num_groups:
        def one(_):
            return {f"b{i}": _state_for_kind(cfg.pattern[i], cfg, batch, max_len)
                    for i in range(lpg)
                    if _state_for_kind(cfg.pattern[i], cfg, batch, max_len)
                    is not None}
        # Stack by broadcasting (all groups identical zero states).
        proto = one(None)
        states["stack"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (num_groups,) + a.shape), proto)
    for i in range(tail):
        st = _state_for_kind(cfg.pattern[i % lpg], cfg, batch, max_len)
        if st is not None:
            states[f"tail{i}"] = st
    return states


def init_paged_decode_state(cfg: ModelConfig, num_pages: int,
                            page_size: int) -> dict:
    """Paged decode state: every attention layer's KV cache is a global
    pool of ``num_pages`` fixed-size pages (page 0 reserved as the trash
    page) instead of per-slot (B, Hkv, max_len, Dh) buffers. Which pages
    belong to which slot lives in the engine's page tables, passed through
    decode inputs — so the pytree has NO slot axis, and per-slot
    take/write/select helpers do not apply to it.

    Only attention-family architectures are supported: recurrent/SSM
    carries have no positional validity mask, so they cannot share a
    lockstep-written global pool (the engine keeps those models on the
    contiguous slot-pooled layout).
    """
    bad = [k for k in cfg.pattern if k not in ("attn", "moe", "cross")]
    if bad:
        raise ValueError(
            f"paged decode state supports attention-family models only; "
            f"{cfg.name} has block kinds {sorted(set(bad))}")

    def paged():
        return init_paged_kv_cache(num_pages, cfg.num_kv_heads, page_size,
                                   cfg.head_dim)

    lpg, num_groups, tail = _group_counts(cfg)
    states: dict[str, Any] = {}
    for i in range(cfg.first_dense_layers):
        states[f"head{i}"] = paged()
    if num_groups:
        proto = {f"b{i}": paged() for i in range(lpg)
                 if cfg.pattern[i] != "cross"}
        states["stack"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (num_groups,) + a.shape), proto)
    for i in range(tail):
        if cfg.pattern[i % lpg] != "cross":
            states[f"tail{i}"] = paged()
    return states


def _state_batch_axis(path) -> int:
    """Slot/batch axis of a decode-state leaf under this module's stacking
    convention: leaves under ``states['stack']`` carry a leading scanned
    group axis (batch is axis 1); head/tail leaves put batch first."""
    first = path[0]
    key = getattr(first, "key", None)
    if key is None:
        key = getattr(first, "name", str(first))
    return 1 if str(key) == "stack" else 0


def take_decode_slots(states, idx):
    """Gather per-slot decode state along the slot/batch axis.

    idx: int array of slot indices. Returns a state pytree whose batch dim
    is ``len(idx)`` — used by the serving engine to run chunked prefill on
    one slot's state view and to compact a fragmented slot pool (a
    permutation gather, one device op per leaf, no host round-trip).
    """
    idx = jnp.asarray(idx, jnp.int32)

    def take(path, leaf):
        return jnp.take(leaf, idx, axis=_state_batch_axis(path))

    return jax.tree_util.tree_map_with_path(take, states)


def write_decode_slot(states, slot, sub):
    """Scatter a single-slot substate (batch dim 1) into the pool at
    ``slot``. Inverse of ``take_decode_slots(states, [slot])``."""

    def wr(path, pool_leaf, sub_leaf):
        return jax.lax.dynamic_update_slice_in_dim(
            pool_leaf, sub_leaf.astype(pool_leaf.dtype), slot,
            axis=_state_batch_axis(path))

    return jax.tree_util.tree_map_with_path(wr, states, sub)


def reset_decode_slot(states, slot):
    """Zero one slot's decode state (KV rows, recurrent/SSM carries) so a
    newly allocated request never sees the previous occupant's state."""

    def rz(path, leaf):
        ax = _state_batch_axis(path)
        shape = list(leaf.shape)
        shape[ax] = 1
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, jnp.zeros(shape, leaf.dtype), slot, axis=ax)

    return jax.tree_util.tree_map_with_path(rz, states)


def copy_decode_pages(states, src, dst):
    """Copy page-pool rows ``src`` onto rows ``dst`` (both int arrays of
    equal length) in a paged decode-state pytree — the device half of a
    copy-on-write: a slot about to write into a page shared with other
    sequences first duplicates it onto a private page. One gather + one
    scatter per leaf, entirely on device (the Gaussian KV triple never
    visits the host)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def cp(path, leaf):
        ax = _state_batch_axis(path)
        rows = jnp.take(leaf, src, axis=ax)
        if ax == 0:
            return leaf.at[dst].set(rows)
        return leaf.at[:, dst].set(rows)

    return jax.tree_util.tree_map_with_path(cp, states)


def select_decode_slots(new_states, old_states, keep_new):
    """Per-slot merge of two state pytrees: ``keep_new`` (B,) bool takes the
    freshly updated slot state where True and the old one where False.

    A batched decode step advances EVERY slot's state (recurrent/SSM
    carries unconditionally; KV caches write a row per slot) — parked and
    mid-prefill slots must keep their old state or the lockstep step
    corrupts them.
    """

    def sel(path, new, old):
        ax = _state_batch_axis(path)
        shape = [1] * new.ndim
        shape[ax] = new.shape[ax]
        return jnp.where(keep_new.reshape(shape), new, old)

    return jax.tree_util.tree_map_with_path(sel, new_states, old_states)


def decode_step(params, cfg: ModelConfig, inputs, states, ctx: Context):
    """One-token decode. inputs: {'tokens': (B,1)} or {'frame_embeddings':
    (B,1,D)}, plus 'positions': (B,1) absolute position, optional
    'cache_len': (B,) valid cache entries INCLUDING the tokens fed this
    step (feeding position p means cache_len >= p+1 — entries at or past
    cache_len are masked out of attention, and the paged insert redirects
    their writes to the trash page), optional 'page_table': (B, P)
    page-pool indirection (when ``states`` came from
    ``init_paged_decode_state``), optional 'write_start': (B,) first
    position each row may write (paged prefix sharing — rows below it are
    re-fed tokens whose k/v already live in copy-on-write-shared prefix
    pages), optional 'image_embeddings'.
    Returns (logits, new_states).
    """
    logits, _, new_states = decode_step_with_aux(params, cfg, inputs, states,
                                                 ctx)
    return logits, new_states


def decode_step_with_aux(params, cfg: ModelConfig, inputs, states,
                         ctx: Context):
    """:func:`decode_step` that also returns the MoE aux dict
    (``nn.moe.zero_aux``) — the serving engine reads the routing counters
    per pass. Runs the aux-loss-free inference path: the
    'loss' entry stays zero and the decode graph never builds the router's
    load-balance term. Returns (logits, aux, new_states)."""
    logits, aux, new_states = forward(
        params, cfg, inputs, ctx, states=dict(states), collect_states=True,
        moe_aux_loss=False)
    return logits, aux, new_states


def draft_decode_step(params, cfg: ModelConfig, inputs, states,
                      ctx: Context = None):
    """Mean-only (zero-variance) decode pass for speculative drafting.

    Runs :func:`decode_step` in ``Mode.DETERMINISTIC`` — every weight is
    its posterior mean, no variance is propagated — so the pass costs a
    plain point-estimate forward instead of a full PFP moment pass. On a
    Gaussian KV pool the deterministic path writes ``v_var = 0`` rows;
    draft writes are throwaway (the verify pass re-feeds the drafted
    tokens through the real PFP pass and overwrites the same rows, or the
    caller discards ``new_states`` outright), so the zero-variance rows
    never reach a served computation. Returns ``(mean_logits, new_states)``
    with ``mean_logits`` a plain (B, T, V) array.

    The same ``inputs`` dict as :func:`decode_step` also serves the
    block-verify pass: feed the K drafted tokens as a (B, K) chunk with
    ``cache_len``/``write_start`` bounding the writable window and a
    full-PFP ``Context`` — chunked paged attention masks by absolute
    position, so the multi-token window is causally exact and, on this
    backend, bit-identical to K sequential single-token passes.
    """
    dctx = (dataclasses.replace(ctx, mode=Mode.DETERMINISTIC)
            if ctx is not None else Context(mode=Mode.DETERMINISTIC))
    logits, new_states = decode_step(params, cfg, inputs, states, dctx)
    if is_gaussian(logits):
        logits = logits.mean
    return logits, new_states


def prefill(params, cfg: ModelConfig, inputs, ctx: Context, max_len: int):
    """Full-sequence pass that fills decode state (returns last logits)."""
    batch = (inputs["tokens"].shape[0] if cfg.embed_inputs
             else inputs["frame_embeddings"].shape[0])
    states = init_decode_state(cfg, batch, max_len)
    logits, _, new_states = forward(params, cfg, inputs, ctx,
                                    states=states, collect_states=True,
                                    moe_aux_loss=False)
    if is_gaussian(logits):
        last = GaussianTensor(logits.mean[:, -1:], logits.second[:, -1:],
                              logits.rep)
    else:
        last = logits[:, -1:]
    return last, new_states
