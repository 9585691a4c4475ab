"""Single impl-dispatch registry for every PFP operator.

The paper's speedups come from a dedicated library of Gaussian-propagating
operators compiled and tuned per target (TVM there, Pallas here). This repo
previously carried TWO parallel operator stacks — ``core.pfp_layers``
(pure-jnp, GaussianTensor-level) and ``kernels.ops`` (padded/blocked
wrappers over the Pallas kernels) — and the model zoo hard-routed through
the former, leaving the tuned kernels dead code on every end-to-end
forward. This module collapses the stacks:

  * each PFP op is registered ONCE with an ``'xla'`` and a ``'kernel'``
    implementation, both operating on :class:`GaussianTensor`;
  * the representation contract (compute layers consume SRM and emit VAR,
    activations consume VAR and emit SRM — paper §5) is enforced HERE, in
    exactly one place, by the public wrappers;
  * ``Context.impl`` (or the process-wide :func:`set_default_impl`) flips
    an entire model forward — MLP, LeNet-5, the transformer LM zoo —
    between the XLA graph and the Pallas kernel path with one flag.

Layering: ``core`` must stay importable without ``kernels`` (oracle-only
tools, docs builds), so kernel implementations import ``repro.kernels.ops``
lazily at call time. Ops whose optimal form IS the XLA-native one (gather
for embeddings, the two adds of a residual) register the same function for
both impls — the registry still owns the routing decision, and the parity
suite (tests/test_impl_dispatch.py) covers them like any other op.

This registry is also the per-op autotuning seam (paper §6: pick block
shapes per (op, shape, target)): every kernel-impl call consults the
process-global schedule cache (``repro.tuning``) keyed on
``(op, logical shape, dtype, backend)`` and threads the tuned
:class:`~repro.tuning.schedules.Schedule` into ``kernels/ops.py``; a miss
falls back to the fixed defaults, so an untuned process behaves exactly as
before. ``repro.tuning.autotune(forward, params, batch)`` warms the cache
for a model's actual shape set. Multi-backend dispatch stays a "register
another implementation / decorate the lookup" change.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import pfp_layers
from repro.core.gaussian import SRM, VAR, GaussianTensor, as_gaussian, is_gaussian
from repro.core.pfp_layers import ACTIVATION_MOMENTS, DETERMINISTIC_ACTIVATIONS

IMPLS = ("xla", "kernel")
_DEFAULT_IMPL = "xla"

# op name -> {'xla': fn, 'kernel': fn}
_REGISTRY: Dict[str, Dict[str, Callable]] = {}

# Open count_fallbacks() scopes: dicts label -> count.
_FALLBACK_SCOPES: list = []


@contextlib.contextmanager
def count_fallbacks():
    """Scope yielding a dict (label -> count) of kernel-impl calls that ran
    an XLA formulation instead because no kernel covers the case. Calls are
    counted where they are traced, so a jitted program counts once per
    compilation, not per run."""
    counts: Dict[str, int] = {}
    _FALLBACK_SCOPES.append(counts)
    try:
        yield counts
    finally:
        _FALLBACK_SCOPES.remove(counts)


def note_fallback(label: str) -> None:
    """Record that a kernel-impl call fell back to XLA (``label`` names the
    op and the case), in every open scope."""
    for counts in _FALLBACK_SCOPES:
        counts[label] = counts.get(label, 0) + 1


# ---------------------------------------------------------------------------
# Registry plumbing
# ---------------------------------------------------------------------------
def set_default_impl(impl: str) -> None:
    """Process-wide default used when ``Context.impl`` is None."""
    global _DEFAULT_IMPL
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")
    _DEFAULT_IMPL = impl


def get_default_impl() -> str:
    return _DEFAULT_IMPL


def resolve_impl(impl: Optional[str]) -> str:
    """None -> process default; otherwise validate and pass through."""
    if impl is None:
        return _DEFAULT_IMPL
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")
    return impl


def register(name: str, impl: str):
    """Decorator: register ``fn`` as the ``impl`` implementation of ``name``."""
    assert impl in IMPLS, impl

    def deco(fn):
        _REGISTRY.setdefault(name, {})[impl] = fn
        return fn

    return deco


def get_op(name: str, impl: Optional[str] = None) -> Callable:
    impl = resolve_impl(impl)
    return _REGISTRY[name][impl]


def registered_ops() -> Dict[str, Dict[str, Callable]]:
    """Snapshot of the registry (op -> impl -> fn)."""
    return {k: dict(v) for k, v in _REGISTRY.items()}


def _kernel_ops():
    # Lazy: keeps core importable without the kernels package and avoids a
    # core <-> kernels import cycle at module-load time.
    from repro.kernels import ops

    return ops


def _out_dtype(*xs) -> Any:
    for x in xs:
        if hasattr(x, "dtype"):
            return x.dtype
    return jnp.float32


def _schedule_for(op: str, shape_key, dtype) -> Optional[Any]:
    """Consult the tuned-schedule cache for this kernel-impl call.

    Shapes are concrete at trace time, so this is a Python-side dict hit
    per op call per trace — zero cost in the compiled graph. Returns None
    (-> the wrapper's fixed defaults) on miss. The import is lazy only to
    keep module load order acyclic — the kernel path already hard-requires
    ``repro.tuning`` (kernels/ops.py imports its Schedule type).
    """
    from repro.tuning import cache as _schedule_cache

    return _schedule_cache.lookup(op, tuple(int(d) for d in shape_key),
                                  jnp.dtype(dtype).name)


def _rows(shape) -> int:
    n = 1
    for d in shape[:-1]:
        n *= int(d)
    return n


# ---------------------------------------------------------------------------
# dense — the paper's flagship operator (Eqs. 4/12/13)
# ---------------------------------------------------------------------------
@register("dense", "xla")
def _dense_xla(x, w, formulation):
    return pfp_layers.pfp_dense(x, w, formulation=formulation)


@register("dense", "kernel")
def _dense_kernel(x, w, formulation):
    if formulation not in ("srm", "var"):
        note_fallback(f"dense:{formulation}")
        return _dense_xla(x, w, formulation)
    ops = _kernel_ops()
    dtype = _out_dtype(x, w)
    shape_key = (_rows(x.shape), x.shape[-1], w.shape[-1])
    if not is_gaussian(x):
        # First-layer simplification (Eq. 13): deterministic inputs run a
        # two-matmul kernel — tuned under its own 'dense_first' op so its
        # schedules never collide with three-matmul entries. Shared by
        # both formulations (Eq. 13 is formulation-free).
        sched = _schedule_for("dense_first", shape_key, dtype)
        mu, var = ops.pfp_dense(x, x, w.mean, w.var, impl="kernel",
                                first_layer=True, schedule=sched)
    elif formulation == "var":
        # Eq. 7 'var' formulation: a four-matmul joint kernel consuming
        # (mu, var) operands natively — tuned under its own 'dense_var'
        # op (different matmul count and VMEM footprint than Eq. 12).
        sched = _schedule_for("dense_var", shape_key, dtype)
        mu, var = ops.pfp_dense_var(x.mean, x.var, w.mean, w.var,
                                    impl="kernel", schedule=sched)
    else:
        sched = _schedule_for("dense", shape_key, dtype)
        mu, var = ops.pfp_dense(x.mean, x.srm, w.mean, w.srm, impl="kernel",
                                schedule=sched)
    return GaussianTensor(mu.astype(dtype), var.astype(dtype), VAR)


def pfp_dense(x, w, b=None, *, formulation: str = "srm",
              impl: Optional[str] = None) -> GaussianTensor:
    """PFP dense y = x @ W (+ b). Consumes SRM, emits VAR (contract here).

    ``b`` may be None, a deterministic array, or a GaussianTensor (the
    paper's three bias configurations, §5) — bias handling is shared by
    both implementations.
    """
    if (isinstance(x, _PendingNorm) and formulation == "srm" and b is None
            and is_gaussian(w) and _fusion_active(impl)):
        # Fusion pass, step 2: a bias-free SRM dense over a pending norm
        # stays pending — a following activation may complete the fused
        # norm_dense_act unit.
        return _PendingNormDense(x, w, impl)
    x = _to_compute_rep(x, formulation)
    out = get_op("dense", impl)(x, w, formulation)
    return _add_bias(out, b)


def _to_compute_rep(x, formulation):
    # Production (Eq. 12) contract: compute layers consume SRM. The Eq. 7
    # ablation natively consumes variances — converting it to SRM here would
    # charge the ablation a conversion it doesn't need (Fig. 5 fairness).
    if not is_gaussian(x):
        return x
    return x.to_srm() if formulation == "srm" else x.to_var()


def _add_bias(out: GaussianTensor, b) -> GaussianTensor:
    if b is None:
        return out
    if is_gaussian(b):
        return GaussianTensor(out.mean + b.mean, out.var + b.var, VAR)
    return GaussianTensor(out.mean + b, out.var, VAR)


# ---------------------------------------------------------------------------
# einsum — generalized PFP contraction
# ---------------------------------------------------------------------------
@register("einsum", "xla")
def _einsum_xla(subscripts, x, w, formulation):
    return pfp_layers.pfp_einsum(subscripts, x, w, formulation=formulation)


def _parse_batched_mm(subscripts: str):
    """Match 'bmk,bkn->bmn'-shaped specs (e.g. the MoE 'ecd,edf->ecf').

    Returns True when both operands are rank-3 with a shared leading batch
    letter and a single shared contraction letter, so the op is a batch of
    independent PFP dense contractions.
    """
    spec = subscripts.replace(" ", "")
    if "->" not in spec or "." in spec:
        return False
    ins, out = spec.split("->")
    if ins.count(",") != 1:
        return False
    lhs, rhs = ins.split(",")
    if not (len(lhs) == len(rhs) == len(out) == 3):
        return False
    return (lhs[0] == rhs[0] == out[0] and lhs[2] == rhs[1]
            and out[1] == lhs[1] and out[2] == rhs[2])


@register("einsum", "kernel")
def _einsum_kernel(subscripts, x, w, formulation):
    spec = subscripts.replace(" ", "")
    if spec in ("...k,kn->...n", "bk,kn->bn", "btk,kn->btn") and \
            formulation in ("srm", "var"):
        # Dense-shaped contraction: both formulations have a blocked
        # kernel ('dense' / 'dense_var' schedules).
        return _dense_kernel(x, w, formulation)
    if _parse_batched_mm(spec) and formulation in ("srm", "var") \
            and is_gaussian(w):
        # Batched per-expert contraction (the MoE 'ecd,edf->ecf'): one
        # grid-level Pallas call with the expert axis on the grid.
        return _dense_batched_kernel(x, w, formulation)
    if spec == "wbtr,wr->btr" and formulation in ("srm", "var") \
            and is_gaussian(w):
        # Depthwise tap contraction (causal depthwise conv in
        # nn/recurrent.py): lifts onto the batched-expert kernel as an
        # R-batched matvec instead of falling back to XLA.
        return _depthwise_kernel(x, w, formulation)
    # General contractions have no blocked kernel; the XLA formulation is
    # the registered fallback — counted so silent fallbacks surface.
    note_fallback(f"einsum:{spec}:{formulation}")
    return _einsum_xla(subscripts, x, w, formulation)


def _depthwise_kernel(x, w, formulation):
    """'wbtr,wr->btr' via dense_batched: out[b,t,r] = sum_w x[w,b,t,r]*w[w,r]
    is, per channel r, a (B*T, W) x (W, 1) matvec — an R-batched dense."""
    mean_like = x.mean if is_gaussian(x) else x
    wd, b, t, r = mean_like.shape

    def to_tokens(a):  # (W, B, T, R) -> (R, B*T, W)
        return jnp.transpose(a, (3, 1, 2, 0)).reshape(r, b * t, wd)

    def to_weights(a):  # (W, R) -> (R, W, 1)
        return jnp.transpose(a)[:, :, None]

    if is_gaussian(x):
        xb = GaussianTensor(to_tokens(x.mean), to_tokens(x.second), x.rep)
    else:
        xb = to_tokens(x)
    wb = GaussianTensor(to_weights(w.mean), to_weights(w.second), w.rep)
    out = _dense_batched_kernel(xb, wb, formulation)  # (R, B*T, 1), VAR
    back = lambda a: jnp.transpose(a.reshape(r, b, t), (1, 2, 0))
    return GaussianTensor(back(out.mean), back(out.var), VAR)


def pfp_einsum(subscripts: str, x, w, *, formulation: str = "srm",
               impl: Optional[str] = None) -> GaussianTensor:
    """PFP generalized contraction. Consumes SRM, emits VAR."""
    return get_op("einsum", impl)(subscripts, _to_compute_rep(x, formulation),
                                  w, formulation)


# ---------------------------------------------------------------------------
# dense_batched — grid-level batched-expert dense (MoE expert MLPs)
# ---------------------------------------------------------------------------
@register("dense_batched", "xla")
def _dense_batched_xla(x, w, formulation):
    # The vmapped per-expert PFP dense chain — the oracle the grid-level
    # kernel is accepted against (kernels/ref.py vmaps the same chain).
    def per_expert(xe, we):
        return pfp_layers.pfp_einsum("ck,kn->cn", xe, we,
                                     formulation=formulation)

    return jax.vmap(per_expert)(x, w)


@register("dense_batched", "kernel")
def _dense_batched_kernel(x, w, formulation):
    if formulation not in ("srm", "var"):
        note_fallback(f"dense_batched:{formulation}")
        return _dense_batched_xla(x, w, formulation)
    ops = _kernel_ops()
    dtype = _out_dtype(x, w)
    mean_like = x.mean if is_gaussian(x) else x
    shape_key = (mean_like.shape[0], mean_like.shape[1], mean_like.shape[2],
                 w.mean.shape[-1])
    sched = _schedule_for("dense_batched", shape_key, dtype)
    if not is_gaussian(x):
        # First-layer simplification (Eq. 13) with a leading expert axis.
        mu, var = ops.pfp_dense_batched(x, x, w.mean, w.var, impl="kernel",
                                        first_layer=True, schedule=sched)
    elif formulation == "var":
        mu, var = ops.pfp_dense_batched_var(x.mean, x.var, w.mean, w.var,
                                            impl="kernel", schedule=sched)
    else:
        mu, var = ops.pfp_dense_batched(x.mean, x.srm, w.mean, w.srm,
                                        impl="kernel", schedule=sched)
    return GaussianTensor(mu.astype(dtype), var.astype(dtype), VAR)


def pfp_dense_batched(x, w, *, formulation: str = "srm",
                      impl: Optional[str] = None) -> GaussianTensor:
    """Batched-expert PFP dense: (E, C, K) x (E, K, N) -> (E, C, N), one
    independent PFP dense per leading index. Consumes SRM, emits VAR.

    This is the MoE expert-MLP contraction ('ecd,edf->ecf'). The kernel
    impl runs ONE Pallas call with the expert axis on the grid and
    ``block_e`` experts resident per grid step (kernels/pfp_moe.py); the
    xla impl is the vmapped per-expert chain the kernel is tested against.
    """
    return get_op("dense_batched", impl)(_to_compute_rep(x, formulation), w,
                                         formulation)


# ---------------------------------------------------------------------------
# dense_grouped — the dropless MoE path's grouped-expert dense
# ---------------------------------------------------------------------------
def map_row_tiles(fn, rows, tile_expert, num_tiles, block_m: int):
    """Map ``fn(*row_tiles, e) -> outputs`` over the row tiles of
    ``block_m`` rows of every (M, K) array in ``rows``, ``e`` being the
    tile's expert ``tile_expert[i]``; tiles from ``num_tiles`` on are
    zero. Returns the outputs as (M, N) arrays: the grouped-expert tile
    loop of the ``dense_grouped`` xla impl and of its kernel's oracle."""
    m = rows[0].shape[0]
    t = m // block_m

    def one(args):
        *tiles, e, i = args
        live = i < num_tiles
        return tuple(jnp.where(live, o, jnp.zeros_like(o))
                     for o in fn(*tiles, e))

    outs = jax.lax.map(one, (*[r.reshape(t, block_m, r.shape[-1])
                               for r in rows], tile_expert,
                             jnp.arange(t, dtype=jnp.int32)))
    return tuple(o.reshape(m, -1) for o in outs)


@register("dense_grouped", "xla")
def _dense_grouped_xla(x, w, tile_expert, num_tiles, block_m, formulation):
    # The PFP dense chain per row tile against its expert's weights, cast
    # to the rows' dtype as the kernel casts them.
    def one(xm, xs, e):
        we = GaussianTensor(w.mean[e].astype(xm.dtype),
                            w.second[e].astype(xm.dtype), w.rep)
        out = pfp_layers.pfp_dense(GaussianTensor(xm, xs, x.rep), we,
                                   formulation=formulation)
        return out.mean, out.var

    mu, var = map_row_tiles(one, (x.mean, x.second), tile_expert, num_tiles,
                            block_m)
    return GaussianTensor(mu, var, VAR)


@register("dense_grouped", "kernel")
def _dense_grouped_kernel(x, w, tile_expert, num_tiles, block_m, formulation):
    if formulation != "srm":
        note_fallback(f"dense_grouped:{formulation}")
        return _dense_grouped_xla(x, w, tile_expert, num_tiles, block_m,
                                  formulation)
    ops = _kernel_ops()
    mu, var = ops.pfp_dense_grouped(x.mean, x.srm, w.mean, w.srm,
                                    tile_expert, num_tiles, block_m=block_m,
                                    impl="kernel")
    return GaussianTensor(mu.astype(x.dtype), var.astype(x.dtype), VAR)


def pfp_dense_grouped(x, w, tile_expert, num_tiles, *, block_m: int,
                      formulation: str = "srm",
                      impl: Optional[str] = None) -> GaussianTensor:
    """Grouped-expert PFP dense: (M, K) rows sorted by expert in row tiles
    of ``block_m`` x (E, K, N) -> (M, N); tile i runs against expert
    ``tile_expert[i]``, and tiles from ``num_tiles`` on come out zero.
    Consumes SRM, emits VAR. The kernel impl is one Pallas call with the
    tiles' experts as scalar prefetch (kernels/pfp_moe.py); the xla impl
    is the per-tile PFP dense chain it is tested against."""
    return get_op("dense_grouped", impl)(_to_compute_rep(x, formulation), w,
                                         tile_expert, num_tiles, block_m,
                                         formulation)


# ---------------------------------------------------------------------------
# conv2d (im2col) — shares the dense kernel's blocked schedule
# ---------------------------------------------------------------------------
@register("conv2d_im2col", "xla")
def _conv_xla(x, w, stride, padding, formulation):
    return pfp_layers.pfp_conv2d_im2col(x, w, stride=stride, padding=padding,
                                        formulation=formulation)


@register("conv2d_im2col", "kernel")
def _conv_kernel(x, w, stride, padding, formulation):
    xp, w2 = pfp_layers.im2col(x, w, stride=stride, padding=padding)
    return _dense_kernel(xp, w2, formulation)


def pfp_conv2d_im2col(x, w, b=None, *, stride: int = 1, padding: str = "VALID",
                      formulation: str = "srm",
                      impl: Optional[str] = None) -> GaussianTensor:
    """PFP conv2d (NHWC, HWIO). Consumes SRM, emits VAR."""
    x = _to_compute_rep(x, formulation)
    out = get_op("conv2d_im2col", impl)(x, w, stride, padding, formulation)
    return _add_bias(out, b)


# ---------------------------------------------------------------------------
# activation — moment-matched elementwise nonlinearities
# ---------------------------------------------------------------------------
@register("activation", "xla")
def _activation_xla(x, kind):
    return pfp_layers.pfp_activation(x, kind)


@register("activation", "kernel")
def _activation_kernel(x, kind):
    if kind == "identity":  # pure representation conversion, no transcendentals
        return _activation_xla(x, kind)
    ops = _kernel_ops()
    sched = _schedule_for("activation", (_rows(x.shape), x.shape[-1]),
                          x.dtype)
    mu, srm = ops.pfp_activation(x.mean, x.var, kind=kind, impl="kernel",
                                 schedule=sched)
    return GaussianTensor(mu.astype(x.dtype), srm.astype(x.dtype), SRM)


def pfp_activation(x: GaussianTensor, kind: str,
                   impl: Optional[str] = None) -> GaussianTensor:
    """Moment-matched activation. Consumes VAR, emits SRM (contract here)."""
    if isinstance(x, _PendingNormDense):
        # Fusion pass, step 3: the chain completed — run it as one kernel
        # when the fused schedule is cached, else fall back unfused.
        fused = x.fuse(kind, impl)
        if fused is not None:
            return fused
    return get_op("activation", impl)(x.to_var(), kind)


# ---------------------------------------------------------------------------
# maxpool2d — Clark tournament (k=2), paper §6.2
# ---------------------------------------------------------------------------
@register("maxpool2d", "xla")
def _maxpool_xla(x, window):
    return pfp_layers.pfp_maxpool2d(x, window=window)


@register("maxpool2d", "kernel")
def _maxpool_kernel(x, window):
    assert window == 2, "production path specializes k=2 like the paper"
    ops = _kernel_ops()
    sched = _schedule_for("maxpool2d", x.shape, x.dtype)
    mu, var = ops.pfp_maxpool2d(x.mean, x.var, impl="kernel", schedule=sched)
    return GaussianTensor(mu.astype(x.dtype), var.astype(x.dtype), VAR)


def pfp_maxpool2d(x: GaussianTensor, window: int = 2,
                  impl: Optional[str] = None) -> GaussianTensor:
    """PFP max pool (NHWC). Consumes VAR, emits VAR."""
    return get_op("maxpool2d", impl)(x.to_var(), window)


# ---------------------------------------------------------------------------
# attention — mean-field joint mean/variance softmax attention
# ---------------------------------------------------------------------------
@register("attention", "xla")
def _attention_xla(q_mu, k_mu, v_mu, v_var, scale, causal):
    return _kernel_ops().pfp_attention(q_mu, k_mu, v_mu, v_var, scale=scale,
                                       causal=causal, impl="xla")


@register("attention", "kernel")
def _attention_kernel(q_mu, k_mu, v_mu, v_var, scale, causal):
    b, h, tq, d = q_mu.shape
    sched = _schedule_for(
        "attention", (b, h, k_mu.shape[1], tq, k_mu.shape[2], d), q_mu.dtype)
    return _kernel_ops().pfp_attention(q_mu, k_mu, v_mu, v_var, scale=scale,
                                       causal=causal, impl="kernel",
                                       schedule=sched)


def pfp_attention(q_mu, k_mu, v_mu, v_var, *, scale: float,
                  causal: bool = True, impl: Optional[str] = None):
    """Mean-field PFP attention: q (B, H, Tq, D), kv (B, Hkv, Tk, D),
    H % Hkv == 0 -> (mean, var) at H heads.

    Array-level (not GaussianTensor): attention mixes deterministic score
    means with value variances, so the layer assembles the tensors. Causal
    masking is right-aligned by index — callers with non-trivial position
    remappings, windows or per-batch validity masks keep the chunked XLA
    core in nn/attention.py.
    """
    dtype = q_mu.dtype
    mu, var = get_op("attention", impl)(q_mu, k_mu, v_mu, v_var, scale, causal)
    return mu.astype(dtype), var.astype(dtype)


# ---------------------------------------------------------------------------
# attention_cache / attention_paged — KV-cache decode attention
# ---------------------------------------------------------------------------
@register("attention_cache", "xla")
def _attention_cache_xla(q_mu, k_mu, v_mu, v_var, q_start, kv_len, scale,
                         causal, window):
    return _kernel_ops().pfp_attention_cache(
        q_mu, k_mu, v_mu, v_var, q_start, kv_len, scale=scale, causal=causal,
        window=window, impl="xla")


@register("attention_cache", "kernel")
def _attention_cache_kernel(q_mu, k_mu, v_mu, v_var, q_start, kv_len, scale,
                            causal, window):
    b, h, tq, d = q_mu.shape
    sched = _schedule_for(
        "attention_cache", (b, h, k_mu.shape[1], tq, k_mu.shape[2], d),
        q_mu.dtype)
    return _kernel_ops().pfp_attention_cache(
        q_mu, k_mu, v_mu, v_var, q_start, kv_len, scale=scale, causal=causal,
        window=window, impl="kernel", schedule=sched)


def pfp_attention_cache(q_mu, k_mu, v_mu, v_var, q_start, kv_len, *,
                        scale: float, causal: bool = True, window=None,
                        impl: Optional[str] = None):
    """KV-cache PFP attention with per-batch dynamic valid lengths.

    q (B, H, Tq, D) x cache (B, Hkv, S, D); q_start/kv_len (B,) int32.
    Query row i of batch b sits at absolute position ``q_start[b] + i``
    (the cache-insert contract: cached positions are contiguous from each
    slot's start); key j is real iff ``j < kv_len[b]``. This is the decode
    path whose per-batch ``cache_len`` previously forced the chunked-XLA
    fallback inside ``nn/attention.py``."""
    dtype = q_mu.dtype
    mu, var = get_op("attention_cache", impl)(q_mu, k_mu, v_mu, v_var,
                                              q_start, kv_len, scale, causal,
                                              window)
    return mu.astype(dtype), var.astype(dtype)


@register("attention_paged", "xla")
def _attention_paged_xla(q_mu, k_pages, v_pages, vv_pages, page_table,
                         q_start, kv_len, scale, causal, window):
    return _kernel_ops().pfp_attention_paged(
        q_mu, k_pages, v_pages, vv_pages, page_table, q_start, kv_len,
        scale=scale, causal=causal, window=window, impl="xla")


@register("attention_paged", "kernel")
def _attention_paged_kernel(q_mu, k_pages, v_pages, vv_pages, page_table,
                            q_start, kv_len, scale, causal, window):
    b, h, tq, d = q_mu.shape
    tk = page_table.shape[1] * k_pages.shape[2]
    sched = _schedule_for(
        "attention_paged", (b, h, k_pages.shape[1], tq, tk, d), q_mu.dtype)
    return _kernel_ops().pfp_attention_paged(
        q_mu, k_pages, v_pages, vv_pages, page_table, q_start, kv_len,
        scale=scale, causal=causal, window=window, impl="kernel",
        schedule=sched)


def pfp_attention_paged(q_mu, k_pages, v_pages, vv_pages, page_table,
                        q_start, kv_len, *, scale: float, causal: bool = True,
                        window=None, impl: Optional[str] = None):
    """Paged-KV PFP attention: q (B, H, Tq, D) against a global page pool
    (NP, Hkv, page_size, D) indirected by ``page_table`` (B, P) int32.

    The kernel impl DMAs each page straight from the pool via a scalar-
    prefetched table index map (block_k == page_size, so only block_q is
    tunable); the xla impl gathers pages into a contiguous cache first.
    Masking semantics match :func:`pfp_attention_cache` — kv_len doubles
    as the per-page valid-length mask."""
    dtype = q_mu.dtype
    mu, var = get_op("attention_paged", impl)(q_mu, k_pages, v_pages,
                                              vv_pages, page_table, q_start,
                                              kv_len, scale, causal, window)
    return mu.astype(dtype), var.astype(dtype)


# ---------------------------------------------------------------------------
# norms — delta-method RMSNorm/LayerNorm, optional fused activation epilogue
# ---------------------------------------------------------------------------
@register("rmsnorm", "xla")
def _rmsnorm_xla(x, gain, eps, act):
    out = pfp_layers.pfp_rmsnorm(x, gain, eps=eps)
    return pfp_layers.pfp_activation(out, act) if act is not None else out


@register("rmsnorm", "kernel")
def _rmsnorm_kernel(x, gain, eps, act):
    ops = _kernel_ops()
    sched = _schedule_for("rmsnorm", (_rows(x.shape), x.shape[-1]), x.dtype)
    mu, sec = ops.pfp_rmsnorm(x.mean, x.second, gain, rep=x.rep, eps=eps,
                              act=act, impl="kernel", schedule=sched)
    rep = SRM if act is not None else VAR
    return GaussianTensor(mu.astype(x.dtype), sec.astype(x.dtype), rep)


def pfp_rmsnorm(x: GaussianTensor, gain, *, eps: float = 1e-6,
                act: Optional[str] = None,
                impl: Optional[str] = None) -> GaussianTensor:
    """RMSNorm under PFP. Emits VAR; with ``act`` the following
    moment-matched activation is fused at the registry level and the op
    emits SRM (activation contract)."""
    if act is None and is_gaussian(x) and _fusion_active(impl):
        # Fusion pass, step 1: defer — a dense may consume this norm.
        return _PendingNorm(x, gain, None, "rmsnorm", eps, impl)
    return get_op("rmsnorm", impl)(x, gain, eps, act)


@register("layernorm", "xla")
def _layernorm_xla(x, gain, bias, eps, act):
    out = pfp_layers.pfp_layernorm(x, gain, bias=bias, eps=eps)
    return pfp_layers.pfp_activation(out, act) if act is not None else out


@register("layernorm", "kernel")
def _layernorm_kernel(x, gain, bias, eps, act):
    ops = _kernel_ops()
    sched = _schedule_for("layernorm", (_rows(x.shape), x.shape[-1]), x.dtype)
    mu, sec = ops.pfp_layernorm(x.mean, x.second, gain, bias, rep=x.rep,
                                eps=eps, act=act, impl="kernel",
                                schedule=sched)
    rep = SRM if act is not None else VAR
    return GaussianTensor(mu.astype(x.dtype), sec.astype(x.dtype), rep)


def pfp_layernorm(x: GaussianTensor, gain, bias=None, *, eps: float = 1e-6,
                  act: Optional[str] = None,
                  impl: Optional[str] = None) -> GaussianTensor:
    """LayerNorm under PFP. Emits VAR (SRM with fused ``act``)."""
    if act is None and is_gaussian(x) and _fusion_active(impl):
        return _PendingNorm(x, gain, bias, "layernorm", eps, impl)
    return get_op("layernorm", impl)(x, gain, bias, eps, act)


# ---------------------------------------------------------------------------
# glu_product — exact gated product (SwiGLU / GeGLU / RG-LRU gates)
# ---------------------------------------------------------------------------
@register("glu_product", "xla")
def _glu_xla(a, b):
    return pfp_layers.pfp_glu_product(a, b)


@register("glu_product", "kernel")
def _glu_kernel(a, b):
    ops = _kernel_ops()
    sched = _schedule_for("glu_product", (_rows(a.shape), a.shape[-1]),
                          a.dtype)
    mu, srm = ops.pfp_glu_product(a.mean, a.srm, b.mean, b.srm, impl="kernel",
                                  schedule=sched)
    return GaussianTensor(mu.astype(a.dtype), srm.astype(a.dtype), SRM)


def pfp_glu_product(a: GaussianTensor, b: GaussianTensor,
                    impl: Optional[str] = None) -> GaussianTensor:
    """Product of independent Gaussians. Consumes SRM, emits SRM (exact)."""
    return get_op("glu_product", impl)(a.to_srm(), b.to_srm())


# ---------------------------------------------------------------------------
# norm_dense_act — the cross-op fused schedule unit (norm -> dense -> act)
# ---------------------------------------------------------------------------
# The transformer block's FFN entry is a fixed three-op chain: pre-norm,
# a bias-free dense (the gate projection in gated MLPs, the up projection
# otherwise), then a moment-matched activation. When the fusion pass is
# enabled (OFF by default) the public wrappers stop executing eagerly and
# instead hand out lazy "pending" GaussianTensors; if the chain completes
# at an activation AND the tuned-schedule cache holds a schedule for the
# fused unit, ONE Pallas kernel runs the whole chain (kernels/pfp_fused.py,
# bit-for-bit with the unfused ops). Any other consumption of a pending —
# attention projections, residuals, lm_head, a cache miss — materializes
# the exact unfused chain, so enabling fusion can never change results.
_FUSION = False
_FUSABLE_ACTS = ("relu", "gelu", "silu", "tanh", "sigmoid")


def set_fusion(enabled: bool) -> bool:
    """Enable/disable the norm->dense->activation fusion pass process-wide.
    Returns the previous setting so scopes nest."""
    global _FUSION
    prev = _FUSION
    _FUSION = bool(enabled)
    return prev


def get_fusion() -> bool:
    return _FUSION


@contextlib.contextmanager
def fusion(enabled: bool = True):
    """Scoped :func:`set_fusion`."""
    prev = set_fusion(enabled)
    try:
        yield
    finally:
        set_fusion(prev)


def _fusion_active(impl: Optional[str]) -> bool:
    return _FUSION and resolve_impl(impl) == "kernel"


class _PendingFusion(GaussianTensor):
    """Lazy GaussianTensor: materializes its unfused value on first
    moment/rep access. Subclassing keeps ``is_gaussian`` and every layer
    helper working unchanged. Pendings normally live only between two
    consecutive dispatch calls inside one block trace; if one does reach
    a pytree boundary (jit return, scan carry, eval_shape output) its
    flatten forces the unfused value and it round-trips as a plain
    GaussianTensor."""

    def __init__(self):
        object.__setattr__(self, "_value", None)

    def _run(self) -> GaussianTensor:  # pragma: no cover - abstract
        raise NotImplementedError

    def _force(self) -> GaussianTensor:
        if self._value is None:
            object.__setattr__(self, "_value", self._run())
        return self._value

    @property
    def mean(self):
        return self._force().mean

    @property
    def second(self):
        return self._force().second

    @property
    def rep(self):
        return self._force().rep

    # Pendings that reach a pytree boundary (a jit return, a scan carry,
    # an eval_shape output — e.g. the lm_head chain, which ends without an
    # activation) force themselves and flatten as the plain unfused value.
    def tree_flatten(self):
        value = self._force()
        return (value.mean, value.second), (value.rep,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        mean, second = children
        return GaussianTensor(mean=mean, second=second, rep=aux[0])


class _PendingNorm(_PendingFusion):
    """A norm whose execution is deferred in case a dense+activation
    follows. Materializes via the registered unfused norm op (memoized —
    shared consumers like a gated MLP's two projections pay it once)."""

    def __init__(self, x, gain, bias, kind, eps, impl):
        super().__init__()
        object.__setattr__(self, "x", x)
        object.__setattr__(self, "gain", gain)
        object.__setattr__(self, "bias", bias)
        object.__setattr__(self, "kind", kind)
        object.__setattr__(self, "eps", eps)
        object.__setattr__(self, "impl", impl)

    def _run(self) -> GaussianTensor:
        if self.kind == "rmsnorm":
            return get_op("rmsnorm", self.impl)(self.x, self.gain, self.eps,
                                                None)
        return get_op("layernorm", self.impl)(self.x, self.gain, self.bias,
                                              self.eps, None)


class _PendingNormDense(_PendingFusion):
    """A bias-free SRM dense over a pending norm. If the next consumer is
    a fusable activation (and the fused schedule is cached), the whole
    chain runs as one kernel; otherwise materializes the exact unfused
    dense over the (memoized) norm output."""

    def __init__(self, pending_norm, w, impl):
        super().__init__()
        object.__setattr__(self, "pending_norm", pending_norm)
        object.__setattr__(self, "w", w)
        object.__setattr__(self, "impl", impl)

    def _run(self) -> GaussianTensor:
        h = self.pending_norm._force()
        return get_op("dense", self.impl)(_to_compute_rep(h, "srm"),
                                          self.w, "srm")

    def fuse(self, act: str, impl: Optional[str]):
        """Attempt the fused lowering; None -> caller falls back unfused.

        The fused-unit schedule is consulted on EVERY attempt (hit or
        miss) so shape recording discovers the unit and the tuning
        cache's consult counters see it — a warm fleet DB therefore proves
        itself with zero misses here too."""
        if (self._value is not None or act not in _FUSABLE_ACTS
                or not _fusion_active(impl)):
            return None
        norm = self.pending_norm
        x, w = norm.x, self.w
        dtype = _out_dtype(x, w)
        shape_key = (_rows(x.shape), x.shape[-1], w.mean.shape[-1])
        sched = _schedule_for("norm_dense_act", shape_key, dtype)
        if sched is None:
            return None  # cache miss: bit-for-bit unfused fallback
        return _nda_run(x, norm.gain, norm.bias, w, None, norm.kind,
                        norm.eps, act, sched, shape_key, dtype)


# Registration routes tree operations through _PendingFusion's forcing
# flatten instead of treating unregistered subclasses as opaque leaves.
jax.tree_util.register_pytree_node_class(_PendingNorm)
jax.tree_util.register_pytree_node_class(_PendingNormDense)


def _nda_run(x, gain, bias, w, b, norm, eps, act, sched, shape_key, dtype):
    """Run the fused kernel with an already-resolved fused schedule.
    block_k is donated by the standalone dense op's schedule at the same
    (K, N) so the fused accumulation tree matches the unfused chain."""
    ops = _kernel_ops()
    dense_sched = _schedule_for("dense", shape_key, dtype)
    mu, srm = ops.pfp_norm_dense_act(
        x.mean, x.second, gain, bias, w.mean, w.srm, b,
        norm=norm, rep=x.rep, eps=eps, act=act, impl="kernel",
        schedule=sched, dense_schedule=dense_sched)
    return GaussianTensor(mu.astype(dtype), srm.astype(dtype), SRM)


@register("norm_dense_act", "xla")
def _norm_dense_act_xla(x, gain, bias, w, b, norm, eps, act):
    # The fused unit's xla impl IS the unfused chain — the fallback
    # semantics by construction.
    if norm == "rmsnorm":
        h = _rmsnorm_xla(x, gain, eps, None)
    else:
        h = _layernorm_xla(x, gain, bias, eps, None)
    out = _add_bias(_dense_xla(_to_compute_rep(h, "srm"), w, "srm"), b)
    return _activation_xla(out.to_var(), act)


@register("norm_dense_act", "kernel")
def _norm_dense_act_kernel(x, gain, bias, w, b, norm, eps, act):
    dtype = _out_dtype(x, w)
    shape_key = (_rows(x.shape), x.shape[-1], w.mean.shape[-1])
    sched = _schedule_for("norm_dense_act", shape_key, dtype)
    return _nda_run(x, gain, bias, w, b, norm, eps, act, sched, shape_key,
                    dtype)


def pfp_norm_dense_act(x: GaussianTensor, gain, bias, w, b=None, *,
                       norm: str = "rmsnorm", eps: float = 1e-6,
                       act: str = "silu",
                       impl: Optional[str] = None) -> GaussianTensor:
    """Fused norm -> bias-free dense -> activation. Emits SRM.

    ``bias`` is the LayerNorm shift (None for rmsnorm); ``b`` the dense
    bias (xla impl only). Most callers never invoke this directly — the
    fusion pass rewrites eligible chains onto it when enabled."""
    return get_op("norm_dense_act", impl)(x, gain, bias, w, b, norm, eps,
                                          act)


# ---------------------------------------------------------------------------
# embedding / residual — memory-bound ops whose tuned form IS the XLA one
# ---------------------------------------------------------------------------
def _embedding_impl(table, ids):
    return pfp_layers.pfp_embedding(table, ids)


register("embedding", "xla")(_embedding_impl)
register("embedding", "kernel")(_embedding_impl)


def pfp_embedding(table: GaussianTensor, ids,
                  impl: Optional[str] = None) -> GaussianTensor:
    """Bayesian embedding gather. Emits VAR. (Gathers are XLA-native on
    every backend; both impls share the one implementation.)"""
    return get_op("embedding", impl)(table.to_var(), ids)


def _residual_impl(x, y):
    return pfp_layers.pfp_residual(x, y)


register("residual", "xla")(_residual_impl)
register("residual", "kernel")(_residual_impl)


def pfp_residual(x, y, impl: Optional[str] = None) -> GaussianTensor:
    """Residual add of independent Gaussians. Emits VAR."""
    return get_op("residual", impl)(as_gaussian(x), as_gaussian(y))


__all__ = [
    "IMPLS", "set_default_impl", "get_default_impl", "resolve_impl",
    "register", "get_op", "registered_ops",
    "count_fallbacks", "note_fallback", "map_row_tiles",
    "set_fusion", "get_fusion", "fusion",
    "pfp_dense", "pfp_dense_batched", "pfp_dense_grouped", "pfp_einsum",
    "pfp_conv2d_im2col",
    "pfp_activation",
    "pfp_maxpool2d", "pfp_attention", "pfp_attention_cache",
    "pfp_attention_paged", "pfp_rmsnorm", "pfp_layernorm",
    "pfp_glu_product", "pfp_norm_dense_act", "pfp_embedding", "pfp_residual",
    "ACTIVATION_MOMENTS", "DETERMINISTIC_ACTIVATIONS",
]
