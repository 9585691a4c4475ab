"""Mixture-of-Experts with capacity-based scatter dispatch (EP-shardable).

Design (DeepSeekMoE / Llama-4 style): optional shared experts always run;
routed experts receive tokens via top-k routing with a capacity limit.

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

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch
from repro.core.gaussian import GaussianTensor, SRM, VAR, is_gaussian
from repro.nn.layers import activation_apply, dense_apply, dense_init
from repro.nn.mlp import mlp_apply, mlp_init
from repro.nn.module import Context, init_bayes, resolve_weight
from repro.nn.pjit_hints import constrain, get_rules


def moe_init(key, d_model: int, d_ff: int, num_experts: int, *,
             num_shared: int = 0, shared_d_ff: Optional[int] = None,
             gated: bool = True, sigma_init=1e-4, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d_model, num_experts,
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


def zero_aux():
    """The aux dict every MoE forward returns (and non-MoE blocks mirror):
    the Switch-style load-balance loss plus the drop-rate accounting."""
    z = jnp.zeros((), jnp.float32)
    return {"loss": z, "moe_dropped": z, "moe_assignments": z}


def moe_apply(params, x, ctx: Context, *, num_experts: int, top_k: int,
              capacity_factor: float = 1.25, activation: str = "silu",
              aux_loss: bool = True, dispatch_mode: str = "scatter"):
    """x: (B, T, d) array or GaussianTensor. Returns (same type, aux dict
    with 'loss' / 'moe_dropped' / 'moe_assignments' f32 scalars).

    ``aux_loss=False`` is the aux-loss-free inference path: the router's
    load-balance loss term is never built (decode graphs carry no training
    bookkeeping). Drop accounting is always returned — serving reads it.

    ``dispatch_mode='a2a'`` routes dispatch/combine through the explicit
    shard_map all-to-all program when a mesh is bound (see _dispatch_a2a);
    'scatter' is the GSPMD scatter/gather lowering.

    Token counts beyond _TOKEN_CHUNK are processed in chunks via lax.scan
    (capacity is then per-chunk): the dispatch one-hot/cumsum and the
    (E, C, d) expert buffers stay bounded at pod-scale prefill (1M tokens),
    at the cost of a sequential chunk loop that XLA pipelines.
    """
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
                                   activation=activation, aux_loss=aux_loss,
                                   dispatch_mode=dispatch_mode)
            acc = {k: carry[k] + aux[k] for k in carry}
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
                       aux_loss=aux_loss, dispatch_mode=dispatch_mode)


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
                aux_loss: bool = True, dispatch_mode: str = "scatter"):
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
    # aux_loss=False (the inference path) never builds the loss term — the
    # decode graph carries no training bookkeeping, only drop accounting.
    if aux_loss:
        density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], num_experts),
                           axis=0)
        router_prob = jnp.mean(probs, axis=0)
        loss = num_experts * jnp.sum(density * router_prob)
    else:
        loss = jnp.zeros((), jnp.float32)
    assignments = jnp.asarray(s * top_k, jnp.float32)
    aux = {"loss": loss,
           "moe_dropped": assignments - jnp.sum(keep_f.astype(jnp.float32)),
           "moe_assignments": assignments}
    return routed, aux
