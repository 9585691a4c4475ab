"""Public jit'd wrappers for the PFP Pallas kernels.

Responsibilities:
  * shape plumbing — flatten leading batch dims, pad to block multiples,
    slice results back (padding along K contributes exact zeros to all
    accumulators, so results are unaffected);
  * dispatch — ``impl='kernel'`` runs the Pallas kernel (interpret=True
    on the CPU, compiled everywhere else), ``impl='xla'`` runs the pure-jnp oracle from
    ``ref.py`` (what the pjit'd production graphs use — XLA already fuses
    the joint-operator structure there; the Pallas kernels are the
    TPU-core-level statement of the same schedule);
  * schedule resolution — every wrapper takes an optional
    :class:`~repro.tuning.schedules.Schedule`.  ``None`` (the cache-miss
    path) reproduces the legacy fixed defaults bit-for-bit; a tuned
    schedule overrides the block shapes, clamped to the padded problem so
    ANY schedule the search space emits is safe (wrong-but-fast is
    impossible — only padding volume changes, never the math);
  * a process-wide default so models can flip implementations globally.
"""
from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.pfp_activations import pfp_activation_pallas, pfp_glu_pallas
from repro.kernels.pfp_attention import (pfp_attention_cache_pallas,
                                         pfp_attention_paged_pallas,
                                         pfp_attention_pallas)
from repro.kernels.pfp_dense import pfp_dense_pallas, pfp_dense_var_pallas
from repro.kernels.pfp_fused import pfp_norm_dense_act_pallas
from repro.kernels.pfp_moe import (pfp_dense_batched_pallas,
                                   pfp_dense_batched_var_pallas,
                                   pfp_dense_grouped_pallas)
from repro.kernels.pfp_maxpool import pfp_maxpool2d_pallas
from repro.kernels.pfp_norms import pfp_layernorm_pallas, pfp_rmsnorm_pallas
from repro.tuning.schedules import AXIS_DEFAULTS, Schedule
from repro.tuning.search import (activation_tiling, activation_transposed,
                                 cost_summary)

Impl = Literal["kernel", "xla"]


def _round_up(x: int, base: int) -> int:
    return -(-x // base) * base


def _block(schedule: Optional[Schedule], name: str, legacy: int,
           dim: int, align: int) -> int:
    """Resolve one block size: a tuned override is clamped to the padded
    problem dim (so oversized candidates degrade to more padding, never to
    wrong results); without an override the legacy default clamp applies."""
    if schedule is not None and schedule.has(name):
        return min(schedule.block(name), _round_up(max(dim, 1), align))
    return legacy


def _axis(schedule: Optional[Schedule], name: str):
    """Resolve one categorical schedule axis (dims / k_order / epilogue /
    prefetch); an absent axis — or no schedule at all — falls back to the
    legacy default, so untuned calls lower exactly as before."""
    if schedule is not None:
        return schedule.axis(name)
    return AXIS_DEFAULTS[name]


def set_default_impl(impl: Impl) -> None:
    """Back-compat shim: the process-wide default now lives in the
    impl-dispatch registry (`repro.core.dispatch`), which models resolve
    their `Context.impl` against."""
    from repro.core.dispatch import set_default_impl as _set

    _set(impl)


def get_default_impl() -> Impl:
    from repro.core.dispatch import get_default_impl as _get

    return _get()


def _interpret() -> bool:
    # Interpret mode exists for tests on the CPU. Anywhere else the kernels
    # compile for the device or fail loudly; they never fall back quietly.
    return jax.default_backend() == "cpu"


def _pad_to(a, mult, axis):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def pfp_dense(
    mu_x, srm_x, mu_w, srm_w,
    *, impl: Impl | None = None,
    block_m: int = 128, block_n: int = 128, block_k: int = 512,
    first_layer: bool = False, schedule: Optional[Schedule] = None,
):
    """Joint PFP dense for (..., K) x (K, N). Returns (mean, var)."""
    impl = impl or get_default_impl()
    lead = mu_x.shape[:-1]
    kdim = mu_x.shape[-1]
    n = mu_w.shape[-1]
    mu2 = mu_x.reshape(-1, kdim)
    srm2 = srm_x.reshape(-1, kdim)

    if impl == "xla":
        if first_layer:
            mu, var = ref.pfp_dense_first_layer_ref(mu2, mu_w, srm_w)
        else:
            mu, var = ref.pfp_dense_ref(mu2, srm2, mu_w, srm_w)
    else:
        m = mu2.shape[0]
        bm = _block(schedule, "block_m", min(block_m, _ceil_mult(m)), m, 8)
        bn = _block(schedule, "block_n", min(block_n, _ceil_mult(n)), n, 128)
        bk = _block(schedule, "block_k", min(block_k, _ceil_mult(kdim)),
                    kdim, 128)
        mu2p = _pad_to(_pad_to(mu2, bm, 0), bk, 1)
        srm2p = _pad_to(_pad_to(srm2, bm, 0), bk, 1)
        mwp = _pad_to(_pad_to(mu_w, bk, 0), bn, 1)
        swp = _pad_to(_pad_to(srm_w, bk, 0), bn, 1)
        mu, var = pfp_dense_pallas(
            mu2p, srm2p, mwp, swp,
            block_m=bm, block_n=bn, block_k=bk,
            dims=_axis(schedule, "dims"),
            k_order=_axis(schedule, "k_order"),
            interpret=_interpret(), first_layer=first_layer,
        )
        mu, var = mu[:m, :n], var[:m, :n]
    return mu.reshape(*lead, n), var.reshape(*lead, n)


def pfp_dense_var(
    mu_x, var_x, mu_w, var_w,
    *, impl: Impl | None = None,
    block_m: int = 128, block_n: int = 128, block_k: int = 512,
    schedule: Optional[Schedule] = None,
):
    """Joint PFP dense, Eq. 7 'var' formulation, for (..., K) x (K, N).

    Consumes (mu, var) operands directly — the ablation's native
    representation (Fig. 5 fairness: no SRM conversion charged). Returns
    (mean, var)."""
    impl = impl or get_default_impl()
    lead = mu_x.shape[:-1]
    kdim = mu_x.shape[-1]
    n = mu_w.shape[-1]
    mu2 = mu_x.reshape(-1, kdim)
    var2 = var_x.reshape(-1, kdim)

    if impl == "xla":
        mu, var = ref.pfp_dense_var_ref(mu2, var2, mu_w, var_w)
    else:
        m = mu2.shape[0]
        bm = _block(schedule, "block_m", min(block_m, _ceil_mult(m)), m, 8)
        bn = _block(schedule, "block_n", min(block_n, _ceil_mult(n)), n, 128)
        bk = _block(schedule, "block_k", min(block_k, _ceil_mult(kdim)),
                    kdim, 128)
        mu2p = _pad_to(_pad_to(mu2, bm, 0), bk, 1)
        var2p = _pad_to(_pad_to(var2, bm, 0), bk, 1)
        mwp = _pad_to(_pad_to(mu_w, bk, 0), bn, 1)
        vwp = _pad_to(_pad_to(var_w, bk, 0), bn, 1)
        mu, var = pfp_dense_var_pallas(
            mu2p, var2p, mwp, vwp,
            block_m=bm, block_n=bn, block_k=bk,
            dims=_axis(schedule, "dims"),
            k_order=_axis(schedule, "k_order"),
            interpret=_interpret(),
        )
        mu, var = mu[:m, :n], var[:m, :n]
    return mu.reshape(*lead, n), var.reshape(*lead, n)


def pfp_dense_batched(
    mu_x, srm_x, mu_w, srm_w,
    *, impl: Impl | None = None,
    block_e: int = 1, block_c: int = 128, block_n: int = 128,
    block_k: int = 512, first_layer: bool = False,
    schedule: Optional[Schedule] = None,
):
    """Batched-expert joint PFP dense for (E, C, K) x (E, K, N).

    The MoE expert-MLP operator: E independent SRM dense problems in ONE
    Pallas call with the expert axis leading the grid (``block_e``
    experts per grid step — the tuner's expert-grid blocking axis). The
    xla impl is the vmapped per-expert oracle chain. Returns (mean, var),
    each (E, C, N)."""
    impl = impl or get_default_impl()
    e, c, kdim = mu_x.shape
    n = mu_w.shape[-1]

    if impl == "xla":
        if first_layer:
            return ref.pfp_dense_batched_first_layer_ref(mu_x, mu_w, srm_w)
        return ref.pfp_dense_batched_ref(mu_x, srm_x, mu_w, srm_w)

    be = _block(schedule, "block_e", min(block_e, e), e, 1)
    bc = _block(schedule, "block_c", min(block_c, _ceil_mult(c)), c, 8)
    bn = _block(schedule, "block_n", min(block_n, _ceil_mult(n)), n, 128)
    bk = _block(schedule, "block_k", min(block_k, _ceil_mult(kdim)), kdim, 128)
    mxp = _pad_to(_pad_to(_pad_to(mu_x, be, 0), bc, 1), bk, 2)
    sxp = _pad_to(_pad_to(_pad_to(srm_x, be, 0), bc, 1), bk, 2)
    mwp = _pad_to(_pad_to(_pad_to(mu_w, be, 0), bk, 1), bn, 2)
    swp = _pad_to(_pad_to(_pad_to(srm_w, be, 0), bk, 1), bn, 2)
    mu, var = pfp_dense_batched_pallas(
        mxp, sxp, mwp, swp,
        block_e=be, block_c=bc, block_n=bn, block_k=bk,
        dims=_axis(schedule, "dims"), k_order=_axis(schedule, "k_order"),
        interpret=_interpret(), first_layer=first_layer,
    )
    return mu[:e, :c, :n], var[:e, :c, :n]


def pfp_dense_batched_var(
    mu_x, var_x, mu_w, var_w,
    *, impl: Impl | None = None,
    block_e: int = 1, block_c: int = 128, block_n: int = 128,
    block_k: int = 512, schedule: Optional[Schedule] = None,
):
    """Batched-expert joint PFP dense, Eq. 7 'var' formulation, for
    (E, C, K) x (E, K, N). Consumes (mu, var) operands directly; shares
    the `dense_batched` schedule table (block legality is identical).
    Returns (mean, var), each (E, C, N)."""
    impl = impl or get_default_impl()
    e, c, kdim = mu_x.shape
    n = mu_w.shape[-1]

    if impl == "xla":
        return ref.pfp_dense_batched_var_ref(mu_x, var_x, mu_w, var_w)

    be = _block(schedule, "block_e", min(block_e, e), e, 1)
    bc = _block(schedule, "block_c", min(block_c, _ceil_mult(c)), c, 8)
    bn = _block(schedule, "block_n", min(block_n, _ceil_mult(n)), n, 128)
    bk = _block(schedule, "block_k", min(block_k, _ceil_mult(kdim)), kdim, 128)
    mxp = _pad_to(_pad_to(_pad_to(mu_x, be, 0), bc, 1), bk, 2)
    vxp = _pad_to(_pad_to(_pad_to(var_x, be, 0), bc, 1), bk, 2)
    mwp = _pad_to(_pad_to(_pad_to(mu_w, be, 0), bk, 1), bn, 2)
    vwp = _pad_to(_pad_to(_pad_to(var_w, be, 0), bk, 1), bn, 2)
    mu, var = pfp_dense_batched_var_pallas(
        mxp, vxp, mwp, vwp,
        block_e=be, block_c=bc, block_n=bn, block_k=bk,
        dims=_axis(schedule, "dims"), k_order=_axis(schedule, "k_order"),
        interpret=_interpret(),
    )
    return mu[:e, :c, :n], var[:e, :c, :n]


# Largest weight tile, per array, one step of the grouped kernel reads.
GROUPED_W_TILE_BYTES = 1 << 20


def _divisor_block(dim: int, most: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``most`` (at least 128); ``dim`` itself where it is at most ``most``
    or not a multiple of 128 (a block may span its whole axis)."""
    if dim <= most or dim % 128:
        return dim
    best = 128
    for b in range(128, most + 1, 128):
        if dim % b == 0:
            best = b
    return best


def grouped_blocks(k: int, n: int, w_bytes: int):
    """(block_k, block_n) of the grouped kernel for (K, N) weights: blocks
    that divide the weights (nothing is padded or copied per call), N up
    to 1536 wide, K as deep as ``GROUPED_W_TILE_BYTES`` allows."""
    bn = _divisor_block(n, 1536)
    bk = _divisor_block(k, max(128, GROUPED_W_TILE_BYTES // (bn * w_bytes)))
    return bk, bn


def pfp_dense_grouped(mu_x, srm_x, mu_w, srm_w, tile_expert, num_tiles, *,
                      block_m: int, impl: Impl | None = None):
    """Grouped-expert joint PFP dense (SRM formulation): (M, K) rows,
    sorted by expert in row tiles of ``block_m``, x (E, K, N) weights ->
    (mean, var), each (M, N) fp32. ``tile_expert`` (M/block_m,) names each
    tile's expert and ``num_tiles`` how many tiles are live; the rest come
    out zero. The weights may be stored in a wider dtype than the rows:
    each tile is cast to the rows' dtype in the kernel."""
    impl = impl or get_default_impl()
    if impl == "xla":
        return ref.pfp_dense_grouped_ref(mu_x, srm_x, mu_w, srm_w,
                                         tile_expert, num_tiles, block_m)
    bk, bn = grouped_blocks(mu_x.shape[-1], mu_w.shape[-1],
                            jnp.dtype(mu_w.dtype).itemsize)
    return pfp_dense_grouped_pallas(
        mu_x, srm_x, mu_w, srm_w, tile_expert, num_tiles, block_m=block_m,
        block_n=bn, block_k=bk, interpret=_interpret())


def pfp_activation(mu, var, *, kind: str = "relu", impl: Impl | None = None,
                   block_rows: int = 256, block_cols: int = 512,
                   schedule: Optional[Schedule] = None):
    """Fused moment-matched activation for any shape. Returns (mean, srm).

    The kernel runs on ``search.activation_tiling``'s view: the input as
    ``(rows, cols)``, or transposed where its trailing axis is narrower
    than a lane row. Each element meets the same moment code either way,
    so the view changes the grid, never the numbers."""
    impl = impl or get_default_impl()
    if impl == "xla":
        fn = {"relu": ref.pfp_relu_ref, "gelu": ref.pfp_gelu_ref,
              "silu": ref.pfp_silu_ref, "tanh": ref.pfp_tanh_ref,
              "sigmoid": ref.pfp_sigmoid_ref}[kind]
        return fn(mu, var)
    shape = mu.shape
    cols = shape[-1]
    rows = mu.size // cols
    transposed = activation_transposed(rows, cols)
    if schedule is not None:
        block_rows = schedule.block("block_rows", block_rows)
        block_cols = schedule.block("block_cols", block_cols)
    r, c, bm, bn = activation_tiling(rows, cols, block_rows, block_cols)

    # Zero padding is finite through every moment function (a zero
    # variance takes the deterministic branch); padded outputs are sliced
    # away.
    def view(a):
        a = a.reshape(rows, cols)
        return _pad_to(_pad_to(a.T if transposed else a, bm, 0), bn, 1)

    def unview(o):
        o = o[:r, :c]
        return (o.T if transposed else o).reshape(shape)

    mo, so = pfp_activation_pallas(
        view(mu), view(var), kind=kind, block_rows=bm, block_cols=bn,
        interpret=_interpret(),
    )
    return unview(mo), unview(so)


def pfp_maxpool2d(mu, var, *, impl: Impl | None = None,
                  block_rows: int = 256, block_cols: int = 128,
                  schedule: Optional[Schedule] = None):
    """2x2/2 PFP max pool on NHWC. Returns (mean, var)."""
    impl = impl or get_default_impl()
    if impl == "xla":
        return ref.pfp_maxpool2d_ref(mu, var)
    n, h, w, c = mu.shape
    rows = n * (h // 2) * (w // 2)
    bm = _block(schedule, "block_rows", block_rows, rows, 8)
    bn = _block(schedule, "block_cols", block_cols, c, 128)
    return pfp_maxpool2d_pallas(mu, var, block_rows=bm, block_cols=bn,
                                interpret=_interpret())


def pfp_attention(q_mu, k_mu, v_mu, v_var, *, scale: float, causal: bool = True,
                  impl: Impl | None = None, block_q: int = 128,
                  block_k: int = 128, schedule: Optional[Schedule] = None):
    """Mean-field PFP attention, q (B, H, Tq, D) x kv (B, Hkv, Tk, D).

    Grouped-query: H % Hkv == 0. The Pallas kernel maps query heads to
    shared KV tiles in its BlockSpec (no repeated KV buffers); the oracle
    materializes the repeat. Returns (mean, var)."""
    impl = impl or get_default_impl()
    if impl == "xla":
        group = q_mu.shape[1] // k_mu.shape[1]
        k_mu, v_mu, v_var = _repeat_kv(group, k_mu, v_mu, v_var)
        return ref.pfp_attention_ref(q_mu, k_mu, v_mu, v_var, scale, causal)
    bq = _block(schedule, "block_q", block_q, q_mu.shape[2], 8)
    bk = _block(schedule, "block_k", block_k, k_mu.shape[2], 8)
    return pfp_attention_pallas(
        q_mu, k_mu, v_mu, v_var, scale=scale, causal=causal,
        block_q=bq, block_k=bk, dims=_axis(schedule, "dims"),
        interpret=_interpret(),
    )


def _repeat_kv(group, *arrs):
    if group == 1:
        return arrs
    return tuple(jnp.repeat(a, group, axis=1) for a in arrs)


def pfp_attention_cache(q_mu, k_mu, v_mu, v_var, q_start, kv_len, *,
                        scale: float, causal: bool = True, window=None,
                        impl: Impl | None = None, block_q: int = 128,
                        block_k: int = 128,
                        schedule: Optional[Schedule] = None):
    """KV-cache PFP attention with per-batch dynamic valid lengths.

    q (B, H, Tq, D) x cache (B, Hkv, S, D); q_start/kv_len (B,) int32 give
    each batch row its own absolute query start and valid cache length
    (continuous-batching decode: slots sit at independent positions).
    Optional sliding ``window``. Returns (mean, var)."""
    impl = impl or get_default_impl()
    if impl == "xla":
        group = q_mu.shape[1] // k_mu.shape[1]
        k_mu, v_mu, v_var = _repeat_kv(group, k_mu, v_mu, v_var)
        return ref.pfp_attention_cache_ref(q_mu, k_mu, v_mu, v_var, q_start,
                                           kv_len, scale, causal=causal,
                                           window=window)
    bq = _block(schedule, "block_q", block_q, q_mu.shape[2], 8)
    bk = _block(schedule, "block_k", block_k, k_mu.shape[2], 8)
    return pfp_attention_cache_pallas(
        q_mu, k_mu, v_mu, v_var, q_start, kv_len, scale=scale, causal=causal,
        window=window, block_q=bq, block_k=bk,
        dims=_axis(schedule, "dims"), interpret=_interpret(),
    )


def pfp_attention_paged(q_mu, k_pages, v_pages, vv_pages, page_table,
                        q_start, kv_len, *, scale: float, causal: bool = True,
                        window=None, impl: Impl | None = None,
                        block_q: int = 128,
                        schedule: Optional[Schedule] = None):
    """Paged-KV PFP attention: q (B, H, Tq, D) x page pool
    (NP, Hkv, page_size, D) indirected by ``page_table`` (B, P).

    The kernel impl DMAs pages straight from the pool via a scalar-
    prefetched table (block_k == page_size, so only block_q is tunable);
    the xla impl gathers the pages into a contiguous cache first. Returns
    (mean, var)."""
    impl = impl or get_default_impl()
    if impl == "xla":
        return ref.pfp_attention_paged_ref(
            q_mu, k_pages, v_pages, vv_pages, page_table, q_start, kv_len,
            scale, causal=causal, window=window)
    bq = _block(schedule, "block_q", block_q, q_mu.shape[2], 8)
    return pfp_attention_paged_pallas(
        q_mu, k_pages, v_pages, vv_pages, page_table, q_start, kv_len,
        scale=scale, causal=causal, window=window, block_q=bq,
        prefetch=int(_axis(schedule, "prefetch")),
        dims=_axis(schedule, "dims"), interpret=_interpret(),
    )


def _norm_rows_that_fit(op: str, rows: int, d: int, block_rows: int) -> int:
    """The largest row block <= ``block_rows`` whose working set (the whole
    padded feature axis stays resident) fits the tuner's VMEM model — a
    fixed row block runs out of VMEM at wide ``d`` (256 x 4096 does)."""
    bm = block_rows
    while bm > 8 and not cost_summary(
            op, (rows, d), Schedule.make(op, block_rows=bm)).fits_vmem:
        bm //= 2
    return max(bm, 8)


def _norm_2d(op, mu, second, *, block_rows: int, schedule=None):
    """Flatten to (rows, d), pad rows to a block multiple and cols to lanes."""
    d = mu.shape[-1]
    mu2 = mu.reshape(-1, d)
    sec2 = second.reshape(-1, d)
    rows = mu2.shape[0]
    fit = _norm_rows_that_fit(op, rows, d, block_rows)
    bm = _block(schedule, "block_rows",
                min(fit, _ceil_mult(rows, 8)), rows, 8)
    mu2 = _pad_to(mu2, bm, 0)
    sec2 = _pad_to(sec2, bm, 0)
    mu2 = _pad_to(mu2, 128, 1)
    sec2 = _pad_to(sec2, 128, 1)
    return mu2, sec2, rows, d, bm


def _vec_pad(v, cols):
    return _pad_to(v.reshape(1, -1), cols, 1)


def pfp_rmsnorm(mu, second, gain, *, rep: str = "var", eps: float = 1e-6,
                act: str | None = None, impl: Impl | None = None,
                block_rows: int = 256, schedule: Optional[Schedule] = None):
    """Fused PFP RMSNorm over the last axis, any leading shape.

    Returns (mean, second): second is VAR without `act`, SRM with the fused
    activation epilogue (activation contract).
    """
    impl = impl or get_default_impl()
    if impl == "xla":
        shape = mu.shape
        m, v = ref.pfp_rmsnorm_ref(mu.reshape(-1, shape[-1]),
                                   second.reshape(-1, shape[-1]),
                                   gain, rep=rep, eps=eps)
        if act is not None:
            m, v = pfp_activation(m, v, kind=act, impl="xla")
        return m.reshape(shape), v.reshape(shape)
    shape = mu.shape
    mu2, sec2, rows, d, bm = _norm_2d("rmsnorm", mu, second,
                                      block_rows=block_rows,
                                      schedule=schedule)
    # epilogue='split' runs the same MOMENT_FNS epilogue as a standalone
    # activation kernel pass over the normalized fp32 moments instead of
    # in-register — elementwise on identical values, so bit-identical; it
    # trades an HBM round-trip for a smaller norm-kernel footprint.
    split = act is not None and _axis(schedule, "epilogue") == "split"
    mo, so = pfp_rmsnorm_pallas(
        mu2, sec2, _vec_pad(gain, mu2.shape[1]), rep=rep, d=d, eps=eps,
        act=None if split else act, block_rows=bm, interpret=_interpret())
    mo = mo[:rows, :d].reshape(shape)
    so = so[:rows, :d].reshape(shape)
    if split:
        return pfp_activation(mo, so, kind=act, impl="kernel")
    return mo, so


def pfp_layernorm(mu, second, gain, bias=None, *, rep: str = "var",
                  eps: float = 1e-6, act: str | None = None,
                  impl: Impl | None = None, block_rows: int = 256,
                  schedule: Optional[Schedule] = None):
    """Fused PFP LayerNorm over the last axis, any leading shape."""
    impl = impl or get_default_impl()
    if bias is None:
        bias = jnp.zeros_like(gain)
    if impl == "xla":
        shape = mu.shape
        m, v = ref.pfp_layernorm_ref(mu.reshape(-1, shape[-1]),
                                     second.reshape(-1, shape[-1]),
                                     gain, bias, rep=rep, eps=eps)
        if act is not None:
            m, v = pfp_activation(m, v, kind=act, impl="xla")
        return m.reshape(shape), v.reshape(shape)
    shape = mu.shape
    mu2, sec2, rows, d, bm = _norm_2d("layernorm", mu, second,
                                      block_rows=block_rows,
                                      schedule=schedule)
    cols = mu2.shape[1]
    split = act is not None and _axis(schedule, "epilogue") == "split"
    mo, so = pfp_layernorm_pallas(
        mu2, sec2, _vec_pad(gain, cols), _vec_pad(bias, cols), rep=rep, d=d,
        eps=eps, act=None if split else act, block_rows=bm,
        interpret=_interpret())
    mo = mo[:rows, :d].reshape(shape)
    so = so[:rows, :d].reshape(shape)
    if split:
        return pfp_activation(mo, so, kind=act, impl="kernel")
    return mo, so


def pfp_glu_product(mu_a, srm_a, mu_b, srm_b, *, impl: Impl | None = None,
                    block_rows: int = 256, block_cols: int = 512,
                    schedule: Optional[Schedule] = None):
    """Fused SRM gated product, any shape. Returns (mean, srm)."""
    impl = impl or get_default_impl()
    if impl == "xla":
        return ref.pfp_glu_ref(mu_a, srm_a, mu_b, srm_b)
    shape = mu_a.shape
    cols = shape[-1]
    args = [a.reshape(-1, cols) for a in (mu_a, srm_a, mu_b, srm_b)]
    m = args[0].shape[0]
    bm = _block(schedule, "block_rows", min(block_rows, _ceil_mult(m, 8)),
                m, 8)
    bn = _block(schedule, "block_cols", min(block_cols, _ceil_mult(cols)),
                cols, 128)
    args = [_pad_to(_pad_to(a, bm, 0), bn, 1) for a in args]
    mo, so = pfp_glu_pallas(*args, block_rows=bm, block_cols=bn,
                            interpret=_interpret())
    return mo[:m, :cols].reshape(shape), so[:m, :cols].reshape(shape)


def pfp_norm_dense_act(
    mu, second, gain, bias, mu_w, srm_w, b=None, *,
    norm: str = "rmsnorm", rep: str = "var", eps: float = 1e-6,
    act: str = "silu", impl: Impl | None = None,
    block_m: int = 128, block_n: int = 128, block_k: int = 512,
    schedule: Optional[Schedule] = None,
    dense_schedule: Optional[Schedule] = None,
):
    """Cross-op fused norm -> dense -> activation for (..., K) x (K, N).

    Consumes the raw norm-input moments (``rep`` tells whether ``second``
    holds variances or SRMs), the norm affine params, and the dense
    weight moments (mean + SRM). Returns (mean, srm) — the activation
    contract. ``bias`` is the LayerNorm shift (ignored for rmsnorm);
    ``b`` is the dense bias, supported on the xla path only — the fusion
    pass in ``core/dispatch.py`` fires exclusively on bias-free dense.

    ``schedule`` carries the fused unit's own (block_m, block_n, dims)
    axes; ``dense_schedule`` donates block_k from the standalone dense op
    at the same (K, N) so the fused K-tiling — and therefore the fp32
    accumulation tree — is structurally identical to the unfused chain
    (the bit-for-bit fallback guarantee).
    """
    impl = impl or get_default_impl()
    lead = mu.shape[:-1]
    k = mu.shape[-1]
    n = mu_w.shape[-1]
    mu2 = mu.reshape(-1, k)
    sec2 = second.reshape(-1, k)

    if impl == "xla":
        if norm == "rmsnorm":
            hm, hv = ref.pfp_rmsnorm_ref(mu2, sec2, gain, rep=rep, eps=eps)
        else:
            nb = jnp.zeros_like(gain) if bias is None else bias
            hm, hv = ref.pfp_layernorm_ref(mu2, sec2, gain, nb, rep=rep,
                                           eps=eps)
        ym, yv = ref.pfp_dense_ref(hm, hv + jnp.square(hm), mu_w, srm_w)
        if b is not None:
            ym = ym + b
        fn = {"relu": ref.pfp_relu_ref, "gelu": ref.pfp_gelu_ref,
              "silu": ref.pfp_silu_ref, "tanh": ref.pfp_tanh_ref,
              "sigmoid": ref.pfp_sigmoid_ref}[act]
        am, asrm = fn(ym, yv)
        return am.reshape(*lead, n), asrm.reshape(*lead, n)

    assert b is None, "fused kernel path requires a bias-free dense"
    m = mu2.shape[0]
    bm = _block(schedule, "block_m", min(block_m, _ceil_mult(m)), m, 8)
    bn = _block(schedule, "block_n", min(block_n, _ceil_mult(n)), n, 128)
    # block_k resolves against the DENSE schedule (fused schedules never
    # carry it) exactly as ops.pfp_dense would at this shape.
    bk = _block(dense_schedule, "block_k", min(block_k, _ceil_mult(k)),
                k, 128)
    k128 = _round_up(max(k, 1), 128)  # the standalone norm kernel's width
    kp = _round_up(k128, bk)
    mu2p = _pad_to(_pad_to(mu2, bm, 0), kp, 1)
    sec2p = _pad_to(_pad_to(sec2, bm, 0), kp, 1)
    gp = _vec_pad(gain, kp)
    bp = gp * 0.0 if (norm == "rmsnorm" or bias is None) \
        else _vec_pad(bias, kp)
    mwp = _pad_to(_pad_to(mu_w, kp, 0), bn, 1)
    swp = _pad_to(_pad_to(srm_w, kp, 0), bn, 1)
    am, asrm = pfp_norm_dense_act_pallas(
        mu2p, sec2p, gp, bp, mwp, swp,
        norm=norm, rep=rep, d=k, k128=k128, eps=eps, act=act,
        block_m=bm, block_n=bn, block_k=bk,
        dims=_axis(schedule, "dims"), interpret=_interpret(),
    )
    am, asrm = am[:m, :n], asrm[:m, :n]
    return am.reshape(*lead, n), asrm.reshape(*lead, n)


def _ceil_mult(x: int, base: int = 128) -> int:
    """Largest 'nice' block <= x: next multiple of base if x >= base else x."""
    if x >= base:
        return base
    return x


__all__ = [
    "pfp_dense", "pfp_dense_var", "pfp_dense_batched",
    "pfp_dense_batched_var", "pfp_activation", "pfp_maxpool2d",
    "pfp_attention",
    "pfp_attention_cache", "pfp_attention_paged",
    "pfp_rmsnorm", "pfp_layernorm", "pfp_glu_product",
    "pfp_norm_dense_act",
    "set_default_impl", "get_default_impl",
]
