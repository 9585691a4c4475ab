"""Mixture-of-Experts: dropless grouped experts for serving, capacity-based
scatter dispatch (EP-shardable) for training.

Design (DeepSeekMoE / Llama-4 style): optional shared experts always run;
routed experts receive tokens via top-k routing.

Serving (``aux_loss=False``, the inference path of ``models/lm.py``) routes
every real token over all ``routed_experts`` experts, keeps the
assignments whose expert is held here (``expert_offset`` ..
``expert_offset + num_experts - 1``), and computes every one of them: the
assignments are laid out in a row buffer sorted by expert, each expert's
rows starting on a row tile of their own, and one grouped PFP GEMM per
projection (``dense_grouped``, kernels/pfp_moe.py) runs each tile against
its expert's weights. Rows the engine marks as padding (``valid``) get no
assignment. Nothing is dropped, so ``moe_dropped`` reads 0.

Training (``aux_loss=True``) keeps the capacity path below.

Dispatch is scatter/gather based — no (tokens, experts, capacity) one-hot
tensor is ever materialized, so the layer scales to pod-size token counts:

    buf  = zeros(E, C, d).at[expert_id, slot].add(x)      # scatter
    out  = expert_mlp(buf)                                # batched (E,C,d)
    y    = out[expert_id, slot] * gate                    # gather + combine

Under PFP the router works on *mean* logits (deterministic routing — the
moment-propagation analogue of the paper's "first-layer simplification":
control flow never sees distributions), so the scatter/gather indices are
shared by the mean and variance paths, and the gate combine is affine:
mean * g, var * g^2. Expert MLPs are batched PFP dense layers (Eq. 12 with
an E-leading einsum).

Sharding: experts -> 'model' (EP), capacity/tokens -> 'data'. By default
GSPMD turns the cross-shard scatter/gather into the MoE all-to-all; with
``dispatch_mode='a2a'`` the dispatch/combine movement is instead an
EXPLICIT shard_map program over the 'data' axis (tiled ``all_to_all`` for
dispatch, ``all_gather`` + local gather for combine), applied jointly to
the mean and SRM buffers — see :func:`_dispatch_a2a`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch
from repro.core.gaussian import GaussianTensor, SRM, VAR, is_gaussian
from repro.core.modes import Mode
from repro.nn.layers import activation_apply, dense_apply, dense_init
from repro.nn.mlp import mlp_apply, mlp_init
from repro.nn.module import Context, init_bayes, resolve_weight
from repro.nn.pjit_hints import constrain, get_rules


def moe_init(key, d_model: int, d_ff: int, num_experts: int, *,
             num_shared: int = 0, shared_d_ff: Optional[int] = None,
             gated: bool = True, sigma_init=1e-4, dtype=jnp.float32,
             router_width: int = 0):
    """``num_experts`` experts held here; the router scores
    ``router_width`` (0 = ``num_experts``)."""
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d_model, router_width or num_experts,
                             sigma_init=sigma_init, dtype=dtype),
        "experts": {
            "w_up": init_bayes(ks[1], (num_experts, d_model, d_ff),
                               fan_in=d_model, sigma_init=sigma_init, dtype=dtype),
            "w_down": init_bayes(ks[2], (num_experts, d_ff, d_model),
                                 fan_in=d_ff, sigma_init=sigma_init, dtype=dtype),
        },
    }
    if gated:
        p["experts"]["w_gate"] = init_bayes(
            ks[3], (num_experts, d_model, d_ff), fan_in=d_model,
            sigma_init=sigma_init, dtype=dtype)
    if num_shared:
        p["shared"] = mlp_init(ks[4], d_model,
                               (shared_d_ff or d_ff) * num_shared,
                               gated=gated, sigma_init=sigma_init, dtype=dtype)
    return p


def _expert_dense(param, x, ctx: Context):
    """Batched per-expert contraction: (E,C,din) x (E,din,dout).

    Routes through the registered ``dense_batched`` op, so
    ``Context(impl='kernel')`` runs the whole expert batch as ONE grid-level
    Pallas call (kernels/pfp_moe.py) instead of a vmapped per-expert chain.
    """
    w = resolve_weight(param, ctx)
    if isinstance(w, GaussianTensor):
        return dispatch.pfp_dense_batched(x, w, formulation=ctx.formulation,
                                          impl=ctx.impl)
    xv = x.mean if is_gaussian(x) else x
    return jnp.einsum("ecd,edf->ecf", xv, w)


def _expert_mlp(params, x, ctx: Context, activation: str):
    up = _expert_dense(params["w_up"], x, ctx)
    if "w_gate" in params:
        gate = _expert_dense(params["w_gate"], x, ctx)
        if is_gaussian(gate):
            g = dispatch.pfp_activation(gate, activation, impl=ctx.impl)
            h = dispatch.pfp_glu_product(g, up, impl=ctx.impl)
        else:
            h = activation_apply(gate, activation, ctx) * up
    else:
        h = activation_apply(up, activation, ctx)
    return _expert_dense(params["w_down"], h, ctx)


_TOKEN_CHUNK = 32768  # dispatch working-set bound for pod-scale prefill


# Aux entries that reduce by max over layers and chunks; the rest sum.
_AUX_MAX = ("moe_max_load",)


def zero_aux():
    """The aux dict every MoE forward returns (and non-MoE blocks mirror):
    the Switch-style load-balance loss, then the routing counters: routed
    assignments of real tokens (``moe_assignments``), those to experts
    held here (``moe_held``), held ones not computed (``moe_dropped``),
    the largest held expert's load (``moe_max_load``), the held experts
    that got any row (``moe_touched``, whose weights the pass reads) and
    assignments given to padding rows (``moe_pad_routed``)."""
    z = jnp.zeros((), jnp.float32)
    return {"loss": z, "moe_dropped": z, "moe_assignments": z,
            "moe_held": z, "moe_max_load": z, "moe_touched": z,
            "moe_pad_routed": z}


def merge_aux(a, b):
    """Two aux dicts as one: max for the max load, sums for the rest."""
    return {k: jnp.maximum(a[k], b[k]) if k in _AUX_MAX else a[k] + b[k]
            for k in a}


def moe_apply(params, x, ctx: Context, *, num_experts: int, top_k: int,
              capacity_factor: float = 1.25, activation: str = "silu",
              aux_loss: bool = True, dispatch_mode: str = "scatter",
              norm_topk_prob: bool = True,
              routed_experts: int = 0, expert_offset: int = 0, valid=None):
    """x: (B, T, d) array or GaussianTensor. Returns (same type, aux dict
    of f32 scalars, see :func:`zero_aux`).

    ``norm_topk_prob`` renormalises the top-k gates to sum to 1; False
    takes the softmax probabilities as they are (DeepSeekMoE).

    ``aux_loss=False`` is the inference path (module doc): dropless, with
    no load-balance loss term in the graph. Every assignment of a
    ``valid`` token ((B, T) bool, None = all) to a held expert is
    computed. ``routed_experts`` (0 = ``num_experts``) is the router's
    width and ``expert_offset`` the global index of the first held
    expert. ``aux_loss=True`` is the training path: capacity dispatch
    over every expert, and the loss. The routing counters are always
    returned — serving reads them.

    ``dispatch_mode='a2a'`` routes the training path's dispatch/combine
    through the explicit shard_map all-to-all program when a mesh is bound
    (see _dispatch_a2a); 'scatter' is the GSPMD scatter/gather lowering.

    Token counts beyond _TOKEN_CHUNK are processed in chunks via lax.scan
    (capacity is then per-chunk): the dispatch one-hot/cumsum and the
    (E, C, d) expert buffers stay bounded at pod-scale prefill (1M tokens),
    at the cost of a sequential chunk loop that XLA pipelines.
    """
    if not aux_loss:
        return _moe_dropless(params, x, ctx, num_experts=num_experts,
                             top_k=top_k, activation=activation,
                             norm_topk_prob=norm_topk_prob,
                             routed_experts=routed_experts or num_experts,
                             expert_offset=expert_offset, valid=valid)
    if (routed_experts or num_experts) != num_experts or expert_offset:
        raise ValueError("an expert share (routed_experts, expert_offset) "
                         "runs on the inference path only")
    pfp = is_gaussian(x)
    mean_all = x.mean if pfp else x
    b, t, d = mean_all.shape
    s_total = b * t
    if s_total > _TOKEN_CHUNK and s_total % _TOKEN_CHUNK == 0:
        nc = s_total // _TOKEN_CHUNK

        def flat(a):
            return a.reshape(nc, 1, _TOKEN_CHUNK, a.shape[-1])

        if pfp:
            xs = (flat(x.mean), flat(x.srm))
        else:
            xs = (flat(mean_all),)

        def body(carry, chunk):
            if pfp:
                cx = GaussianTensor(chunk[0], chunk[1], SRM)
            else:
                cx = chunk[0]
            out, aux = _moe_tokens(params, cx, ctx,
                                   num_experts=num_experts, top_k=top_k,
                                   capacity_factor=capacity_factor,
                                   activation=activation,
                                   dispatch_mode=dispatch_mode,
                                   norm_topk_prob=norm_topk_prob)
            acc = merge_aux(carry, aux)
            if pfp:
                return acc, (out.mean, out.var)
            return acc, (out,)

        aux_total, outs = jax.lax.scan(body, zero_aux(), xs)
        # Loss averages over chunks (it is a mean-statistic); the drop
        # counters are extensive and sum.
        aux_total = dict(aux_total, loss=aux_total["loss"] / nc)
        if pfp:
            routed = GaussianTensor(outs[0].reshape(b, t, d),
                                    outs[1].reshape(b, t, d), VAR)
        else:
            routed = outs[0].reshape(b, t, d)
        return routed, aux_total

    return _moe_tokens(params, x, ctx, num_experts=num_experts, top_k=top_k,
                       capacity_factor=capacity_factor, activation=activation,
                       dispatch_mode=dispatch_mode,
                       norm_topk_prob=norm_topk_prob)


def _a2a_mesh(dispatch_mode: str, num_experts: int, tokens: int):
    """The mesh the explicit a2a dispatch runs over, or None -> scatter.

    The a2a program shards experts and tokens over the 'data' axis, so it
    needs both counts divisible by the axis size; anything else falls back
    to the scatter lowering (identical semantics, GSPMD-routed)."""
    if dispatch_mode != "a2a":
        return None
    rules = get_rules()
    mesh = rules.get("mesh") if rules else None
    if mesh is None or "data" not in mesh.axis_names:
        return None
    dsize = mesh.shape["data"]
    if num_experts % dsize or tokens % dsize:
        return None
    return mesh


def _dispatch_a2a(mesh, vals_list, flat_e, slot, *, num_experts, capacity):
    """Explicit-collective dispatch replacing the GSPMD scatter.

    Each 'data' shard scatters its LOCAL assignment rows into a full-size
    partial (E, C, d) buffer using the GLOBAL slot values (slots come from
    one token-ordered cumsum, so shards write disjoint entries), then one
    tiled ``all_to_all`` exchanges expert chunks: shard r keeps experts
    [r*E/D, (r+1)*E/D) and sums the partials every shard contributed.
    Applied jointly to the mean and SRM buffers (``vals_list``). On a
    1-device data axis this is the scatter program bit-for-bit.
    """
    dsize = mesh.shape["data"]

    def fn(fe, sl, *vals):
        outs = []
        for v in vals:
            part = jnp.zeros((num_experts, capacity, v.shape[-1]), v.dtype)
            part = part.at[fe, sl].add(v, mode="drop")
            if dsize > 1:
                ex = jax.lax.all_to_all(part, "data", split_axis=0,
                                        concat_axis=1, tiled=True)
                part = ex.reshape(num_experts // dsize, dsize, capacity,
                                  v.shape[-1]).sum(axis=1)
            outs.append(part)
        return tuple(outs)

    n = len(vals_list)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("data"), P("data")) + (P("data", None),) * n,
        out_specs=(P("data", None, None),) * n,
        check_vma=False)(flat_e, slot, *vals_list)


def _combine_a2a(mesh, buf_weight_list, flat_e, slot, token_of, *, tokens):
    """Explicit-collective combine replacing the GSPMD gather.

    The expert outputs are expert-sharded; a token's experts can live on
    any shard, so combine is an expert->token ``all_gather`` over 'data'
    followed by a purely local gather + gated per-token reduction. (A
    slot-local a2a combine would need per-shard capacities, which changes
    the drop semantics — the global-capacity cumsum is kept instead.)
    ``buf_weight_list``: [(buf (E,C,d), weight (S*K,)), ...] pairs — mean
    with gate^1 and variance with gate^2 move through one shard_map.
    """
    dsize = mesh.shape["data"]
    s_local = tokens // dsize

    def fn(fe, sl, tok, *flat):
        bufs, weights = flat[::2], flat[1::2]
        tok_local = tok - jax.lax.axis_index("data") * s_local
        outs = []
        for part, wt in zip(bufs, weights):
            full = part
            if dsize > 1:
                full = jax.lax.all_gather(part, "data", axis=0, tiled=True)
            gathered = full[fe, sl] * wt[:, None]
            y = jnp.zeros((s_local, part.shape[-1]), part.dtype)
            outs.append(y.at[tok_local].add(gathered))
        return tuple(outs)

    n = len(buf_weight_list)
    flat_args = [a for pair in buf_weight_list for a in pair]
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"))
        + (P("data", None, None), P("data")) * n,
        out_specs=(P("data", None),) * n,
        check_vma=False)(flat_e, slot, token_of, *flat_args)


def _moe_tokens(params, x, ctx: Context, *, num_experts: int, top_k: int,
                capacity_factor: float, activation: str,
                dispatch_mode: str = "scatter",
                norm_topk_prob: bool = True):
    pfp = is_gaussian(x)
    mean_in = x.mean if pfp else x
    b, t, d = mean_in.shape
    s = b * t

    # --- routing on the mean path (deterministic control flow) -------------
    router_w = resolve_weight(params["router"]["w"], ctx)
    router_mu = router_w.mean if isinstance(router_w, GaussianTensor) else router_w
    logits = mean_in.reshape(s, d) @ router_mu                    # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)           # (S, K)
    if norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    capacity = int(max(top_k, round(s * top_k * capacity_factor / num_experts)))

    flat_e = expert_idx.reshape(-1)                               # (S*K,)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32) # (S*K, E)
    pos_in_e = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    keep = (pos_in_e < capacity) & (pos_in_e >= 0)
    slot = jnp.where(keep, pos_in_e, capacity - 1)
    token_of = jnp.repeat(jnp.arange(s), top_k)                   # (S*K,)
    keep_f = keep.astype(mean_in.dtype)

    # --- dispatch -----------------------------------------------------------
    # GSPMD cannot derive a2a semantics from scatter-adds (anchoring the
    # (E, C, d) buffers to EP x DP was tried and REVERTED: it turned the
    # dispatch into full-buffer all-reduces — deepseek train collective
    # 152 s -> 429 s). dispatch_mode='a2a' is that documented future work,
    # shipped: _dispatch_a2a/_combine_a2a run the movement as an explicit
    # shard_map all_to_all / all_gather over the 'data' axis.
    a2a_mesh = _a2a_mesh(dispatch_mode, num_experts, s)

    def dispatch(arr_flat):                                       # (S, d) -> (E, C, d)
        vals = arr_flat[token_of] * keep_f[:, None]
        buf = jnp.zeros((num_experts, capacity, d), arr_flat.dtype)
        return buf.at[flat_e, slot].add(vals, mode="drop")

    if a2a_mesh is not None:
        flats = [mean_in.reshape(s, d)] + ([x.srm.reshape(s, d)] if pfp
                                           else [])
        vals_list = [a[token_of] * keep_f[:, None] for a in flats]
        bufs = _dispatch_a2a(a2a_mesh, vals_list, flat_e, slot,
                             num_experts=num_experts, capacity=capacity)
        expert_in = GaussianTensor(bufs[0], bufs[1], SRM) if pfp else bufs[0]
    elif pfp:
        x_srm = x.srm.reshape(s, d)
        expert_in = GaussianTensor(
            dispatch(mean_in.reshape(s, d)), dispatch(x_srm), SRM
        )
    else:
        expert_in = dispatch(mean_in.reshape(s, d))

    expert_out = _expert_mlp(params["experts"], expert_in, ctx, activation)

    # --- combine ------------------------------------------------------------
    gate_flat = (gate_vals.reshape(-1) * keep_f)                  # (S*K,)

    def combine(buf, weight_pow):                                  # (E,C,d) -> (S,d)
        gathered = buf[flat_e, slot]                               # (S*K, d)
        w = gate_flat[:, None] ** weight_pow
        y = jnp.zeros((s, d), buf.dtype).at[token_of].add(gathered * w)
        return y

    if a2a_mesh is not None:
        pairs = ([(expert_out.mean, gate_flat),
                  (expert_out.var, jnp.square(gate_flat))] if pfp
                 else [(expert_out, gate_flat)])
        ys = _combine_a2a(a2a_mesh, pairs, flat_e, slot, token_of, tokens=s)
        if pfp:
            routed = GaussianTensor(ys[0].reshape(b, t, d),
                                    ys[1].reshape(b, t, d), VAR)
        else:
            routed = ys[0].reshape(b, t, d)
    elif pfp:
        out_mu = combine(expert_out.mean, 1)
        out_var = combine(expert_out.var, 2)
        routed = GaussianTensor(out_mu.reshape(b, t, d),
                                out_var.reshape(b, t, d), VAR)
    else:
        routed = combine(expert_out, 1).reshape(b, t, d)

    if "shared" in params:
        shared = mlp_apply(params["shared"], x, ctx, activation=activation)
        if pfp:
            routed = GaussianTensor(routed.mean + shared.mean,
                                    routed.var + shared.var, VAR)
        else:
            routed = routed + shared

    # Load-balance auxiliary loss (Switch-style), returned for training.
    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], num_experts), axis=0)
    router_prob = jnp.mean(probs, axis=0)
    loss = num_experts * jnp.sum(density * router_prob)
    assignments = jnp.asarray(s * top_k, jnp.float32)
    kept = jnp.sum(keep_f.astype(jnp.float32))
    load = jnp.sum(onehot * keep.astype(jnp.int32)[:, None], axis=0)
    aux = {"loss": loss,
           "moe_dropped": assignments - kept,
           "moe_assignments": assignments,
           "moe_held": kept,
           "moe_max_load": jnp.max(load).astype(jnp.float32),
           "moe_touched": jnp.sum(load > 0).astype(jnp.float32),
           "moe_pad_routed": jnp.zeros((), jnp.float32)}
    return routed, aux


# ---------------------------------------------------------------------------
# Dropless serving path: grouped experts over an expert-sorted row buffer
# ---------------------------------------------------------------------------
def row_tile(rows: int) -> int:
    """Rows of one tile of the grouped buffer, for a buffer that holds at
    most ``rows`` assignments: one bf16 sublane tile (16) at decode sizes,
    128 at prefill sizes, where a held expert sees tens of rows or more."""
    return 16 if rows <= 1024 else 128


def _stored_weight(param, ctx: Context):
    """A PFP weight as it is stored (no cast to the compute dtype: the
    grouped kernel casts its tiles, the router runs in fp32); in the
    sampling modes, what ``resolve_weight`` gives."""
    if ctx.mode == Mode.PFP:
        return resolve_weight(param, dataclasses.replace(ctx,
                                                         compute_dtype=None))
    return resolve_weight(param, ctx)


def _grouped_dense(param, x, layout, ctx: Context):
    """One projection of every held expert over the grouped row buffer:
    (M, din) rows x (E, din, dout) -> (M, dout). The weights reach the
    kernel as they are stored (fp32 ``mu``/``srm``); it casts each tile to
    the rows' dtype, so no bf16 copy of the experts is made per call.
    Sampled or deterministic weights run the op's xla chain at zero
    weight variance and keep its mean."""
    tile_expert, num_tiles, bm = layout
    w = _stored_weight(param, ctx)
    if isinstance(w, GaussianTensor):
        return dispatch.pfp_dense_grouped(x, w, tile_expert, num_tiles,
                                          block_m=bm,
                                          formulation=ctx.formulation,
                                          impl=ctx.impl)
    xv = x.mean if is_gaussian(x) else x
    out = dispatch.pfp_dense_grouped(
        GaussianTensor(xv, jnp.square(xv), SRM),
        GaussianTensor(w, jnp.zeros_like(w), VAR), tile_expert, num_tiles,
        block_m=bm, impl="xla")
    return out.mean


def _moe_dropless(params, x, ctx: Context, *, num_experts: int, top_k: int,
                  activation: str, norm_topk_prob: bool, routed_experts: int,
                  expert_offset: int, valid):
    pfp = is_gaussian(x)
    mean_in = x.mean if pfp else x
    b, t, d = mean_in.shape
    s = b * t
    e_held = num_experts

    with jax.named_scope("pfp.moe.route"):
        # Router logits in float32 on the mean path (deterministic
        # routing, as DeepSeekMoE's gate computes them in fp32).
        router_w = _stored_weight(params["router"]["w"], ctx)
        router_mu = (router_w.mean if isinstance(router_w, GaussianTensor)
                     else router_w)
        logits = jnp.dot(mean_in.reshape(s, d).astype(jnp.float32),
                         router_mu.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)      # (S, R)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, top_k)       # (S, K)
        if norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    with jax.named_scope("pfp.moe.dispatch"):
        real = (jnp.ones((s,), bool) if valid is None
                else valid.reshape(s))
        local = expert_idx - expert_offset                        # (S, K)
        held = (local >= 0) & (local < e_held) & real[:, None]
        # Each token holds at most min(K, E) of its distinct experts, and
        # each expert's rows start on a tile of their own.
        bm = row_tile(s * min(top_k, e_held))
        m_pad = -(-(s * min(top_k, e_held) + e_held * (bm - 1)) // bm) * bm
        n_tiles = m_pad // bm
        flat_e = jnp.where(held, local, e_held).reshape(-1)       # (S*K,)
        onehot = jax.nn.one_hot(flat_e, e_held, dtype=jnp.int32)  # (S*K, E)
        counts = jnp.sum(onehot, axis=0)                          # (E,)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
        tiles_e = (counts + bm - 1) // bm
        tile_end = jnp.cumsum(tiles_e)
        tile_start = tile_end - tiles_e
        is_held = flat_e < e_held
        dest = jnp.where(
            is_held,
            tile_start[jnp.minimum(flat_e, e_held - 1)] * bm + rank, m_pad)
        num_tiles = tile_end[-1].astype(jnp.int32)
        tile_expert = jnp.minimum(
            jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
            e_held - 1).astype(jnp.int32)
        token_of = jnp.repeat(jnp.arange(s, dtype=jnp.int32), top_k)
        row_token = jnp.zeros((m_pad,), jnp.int32).at[dest].set(
            token_of, mode="drop")
        row_gate = jnp.zeros((m_pad,), jnp.float32).at[dest].set(
            gate_vals.reshape(-1).astype(jnp.float32), mode="drop")
        row_live = jnp.zeros((m_pad,), bool).at[dest].set(True, mode="drop")
        if pfp:
            xs = x.to_srm()
            expert_in = GaussianTensor(
                xs.mean.reshape(s, d)[row_token],
                xs.srm.reshape(s, d)[row_token], SRM)
        else:
            expert_in = mean_in.reshape(s, d)[row_token]

    with jax.named_scope("pfp.moe.experts"):
        layout = (tile_expert, num_tiles, bm)
        ep = params["experts"]
        up = _grouped_dense(ep["w_up"], expert_in, layout, ctx)
        if "w_gate" in ep:
            gate = _grouped_dense(ep["w_gate"], expert_in, layout, ctx)
            if is_gaussian(gate):
                g = dispatch.pfp_activation(gate, activation, impl=ctx.impl)
                h = dispatch.pfp_glu_product(g, up, impl=ctx.impl)
            else:
                h = activation_apply(gate, activation, ctx) * up
        else:
            h = activation_apply(up, activation, ctx)
        expert_out = _grouped_dense(ep["w_down"], h, layout, ctx)

    with jax.named_scope("pfp.moe.combine"):
        g1 = jnp.where(row_live, row_gate, 0.0)[:, None]

        def combine(rows, weight):                               # (M,d)->(S,d)
            part = jnp.where(row_live[:, None],
                             rows.astype(jnp.float32) * weight, 0.0)
            y = jnp.zeros((s, d), jnp.float32).at[row_token].add(part)
            return y.reshape(b, t, d).astype(mean_in.dtype)

        if pfp:
            routed = GaussianTensor(combine(expert_out.mean, g1),
                                    combine(expert_out.var, jnp.square(g1)),
                                    VAR)
        else:
            routed = combine(expert_out, g1)

    if "shared" in params:
        with jax.named_scope("pfp.moe.shared"):
            shared = mlp_apply(params["shared"], x, ctx,
                               activation=activation)
            if pfp:
                routed = GaussianTensor(routed.mean + shared.mean,
                                        routed.var + shared.var, VAR)
            else:
                routed = routed + shared

    f32 = jnp.float32
    placed = jnp.sum(row_live.astype(f32))
    aux = {"loss": jnp.zeros((), f32),
           "moe_dropped": jnp.sum(held.astype(f32)) - placed,
           "moe_assignments": jnp.sum(real.astype(f32)) * top_k,
           "moe_held": placed,
           "moe_max_load": jnp.max(counts).astype(f32),
           "moe_touched": jnp.sum(counts > 0).astype(f32),
           "moe_pad_routed": jnp.sum((row_live & ~real[row_token]).astype(
               f32))}
    return routed, aux
