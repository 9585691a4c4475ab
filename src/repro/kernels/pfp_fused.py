"""Cross-op fused PFP kernel: norm -> dense -> activation in one pass.

The transformer-LM block's FFN entry is always the same three-op chain —
``rmsnorm/layernorm`` (VAR out), a bias-free ``dense`` (SRM in, VAR out),
then a moment-matched activation (SRM out). Executed separately, the
normalized (rows, K) moments round-trip through HBM twice between the
norm and the matmuls. This kernel keeps them in VMEM: each (bm, K) strip
is normalized in-register, converted to SRM exactly like
``GaussianTensor.to_srm`` (srm = var + mu^2), pushed through the Eq. 12
three-matmul joint dense with an in-body K-tile loop, and finished with
the same ``MOMENT_FNS`` epilogue the standalone activation kernel uses.

Equivalence contract (tests/test_impl_dispatch.py pins it): the fused
kernel replays the EXACT fp32 operation sequence of the unfused chain —

  * the norm math is the ``pfp_norms.py`` kernel body verbatim, with the
    reductions sliced to the same round_up(K, 128) width the standalone
    norm kernel sees (wider zero-padding would change the reduction tree);
  * the K-tile loop accumulates ``0 + dot(t0) + dot(t1) + ...`` per
    accumulator in the same order as ``pfp_dense.py``'s grid kernel, with
    ``bk`` taken from the DENSE op's schedule at the same (K, N) so the
    tiling (and therefore the fp32 add tree) is structurally identical;
  * the epilogue applies the shared elementwise ``MOMENT_FNS`` to the
    same fp32 (mean, var) values the standalone activation kernel gets.

Schedule axes searched by the autotuner: ``block_m``, ``block_n`` and the
``dims`` dimension_semantics annotation. ``block_k`` is deliberately NOT
a fused axis — it is inherited from the dense op (see above).

One caveat: the op sequence is identical, but the compiler may contract
mul+add pairs into FMAs (XLA's CPU emitter does so per fusion region), and
fusing three kernel bodies into one changes where the unfused chain
rounds to fp32 at HBM boundaries. The moments therefore agree to ~1 ulp
per contraction (<= 1e-3 relative end-to-end) rather than bitwise; greedy
tokens and the cache-miss fallback (which runs the real unfused chain)
remain exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.gaussian import SRM, VAR
from repro.kernels.pfp_activations import MOMENT_FNS
from repro.kernels.pfp_dense import _compiler_params
from repro.kernels.pfp_norms import _split_reps


def _norm_dense_act_kernel(
    mu_ref, sec_ref, gain_ref, bias_ref, mu_w_ref, srm_w_ref,
    mu_out_ref, srm_out_ref,
    *, norm: str, rep: str, d: int, k128: int, eps: float, act: str,
    bk: int, nk: int,
):
    """One (i, j) grid step: full-K norm + SRM convert + tiled joint dense
    + activation epilogue, all in fp32 registers."""
    mu = mu_ref[...].astype(jnp.float32)          # (bm, kp)
    sec = sec_ref[...].astype(jnp.float32)
    var, srm = _split_reps(mu, sec, rep)
    gain = gain_ref[...].astype(jnp.float32)
    # Reductions run over the exact round_up(K, 128) window the standalone
    # norm kernel sees; any further (block_k-multiple) padding is zeros and
    # must stay OUT of the reduction tree to keep the fp32 sums bit-equal.
    if norm == "rmsnorm":
        nrm = jax.lax.rsqrt(
            jnp.sum(srm[:, :k128], axis=-1, keepdims=True) / d + eps)
        scale = nrm * gain
        h_mu = mu * scale
        h_var = var * jnp.square(scale)
    else:  # layernorm — pfp_norms._layernorm_kernel verbatim
        mu_tok = jnp.sum(mu[:, :k128], axis=-1, keepdims=True) / d
        spread = (jnp.sum(var[:, :k128] + jnp.square(mu[:, :k128]),
                          axis=-1, keepdims=True) / d
                  - jnp.square(mu_tok))
        scale = jax.lax.rsqrt(spread + eps) * gain
        h_mu = (mu - mu_tok) * scale + bias_ref[...].astype(jnp.float32)
        h_var = var * jnp.square(scale)
    # GaussianTensor.to_srm on a VAR tensor: second + mean^2. Padded
    # columns have gain == 0, so h_mu == h_var == h_srm == 0 there and the
    # dense accumulation below matches the zero-padded unfused operands.
    h_srm = h_var + jnp.square(h_mu)

    # Joint PFP dense (Eq. 12), same three-dot-per-tile order as
    # pfp_dense._dense_kernel so the fp32 accumulation is bit-identical.
    shape = mu_out_ref.shape
    mu_acc = jnp.zeros(shape, jnp.float32)
    var_acc = jnp.zeros(shape, jnp.float32)
    musq_acc = jnp.zeros(shape, jnp.float32)
    for t in range(nk):
        sl = slice(t * bk, (t + 1) * bk)
        xm = h_mu[:, sl]
        wm = mu_w_ref[sl, :]
        mu_acc = mu_acc + jnp.dot(xm, wm,
                                  preferred_element_type=jnp.float32)
        var_acc = var_acc + jnp.dot(h_srm[:, sl], srm_w_ref[sl, :],
                                    preferred_element_type=jnp.float32)
        musq_acc = musq_acc + jnp.dot(jnp.square(xm), jnp.square(wm),
                                      preferred_element_type=jnp.float32)
    y_var = var_acc - musq_acc

    # Shared moment-matched activation epilogue: VAR -> SRM, elementwise,
    # so tile geometry can't perturb it.
    a_mu, a_srm = MOMENT_FNS[act](mu_acc, y_var)
    mu_out_ref[...] = a_mu
    srm_out_ref[...] = a_srm


@functools.partial(
    jax.jit,
    static_argnames=("norm", "rep", "d", "k128", "eps", "act",
                     "block_m", "block_n", "block_k", "dims", "interpret"),
)
def pfp_norm_dense_act_pallas(
    mu, second, gain, bias, mu_w, srm_w,
    *,
    norm: str = "rmsnorm",
    rep: str = VAR,
    d: int,
    k128: int,
    eps: float = 1e-6,
    act: str = "silu",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    dims: str = "parallel",
    interpret: bool = False,
):
    """Fused norm+dense+activation on padded 2D operands.

    mu/second (M, Kp) x mu_w/srm_w (Kp, N) -> (mean, SRM) (M, N) fp32.
    ``d`` is the true feature count, ``k128`` the standalone norm kernel's
    round_up(d, 128) reduction width (Kp may exceed it to reach a block_k
    multiple — those columns are zero). ``bias`` is layernorm's shift
    (pass zeros for rmsnorm; the dense bias is not fused — the dispatch
    fusion pass only fires on bias-free dense).
    """
    assert norm in ("rmsnorm", "layernorm"), norm
    assert rep in (VAR, SRM), rep
    m, kp = mu.shape
    _, n = mu_w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, kp)
    assert m % bm == 0 and n % bn == 0 and kp % bk == 0, (m, n, kp, bm, bn, bk)
    assert k128 <= kp, (k128, kp)
    nk = kp // bk

    row_spec = pl.BlockSpec((bm, kp), lambda i, j: (i, 0))
    vec_spec = pl.BlockSpec((1, kp), lambda i, j: (0, 0))
    w_spec = pl.BlockSpec((kp, bn), lambda i, j: (0, j))
    out_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))

    common = dict(
        grid=(m // bm, n // bn),
        in_specs=[row_spec, row_spec, vec_spec, vec_spec, w_spec, w_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params((dims, dims)),
    )
    fn = pl.pallas_call(
        functools.partial(
            _norm_dense_act_kernel, norm=norm, rep=rep, d=d, k128=k128,
            eps=eps, act=act, bk=bk, nk=nk),
        **common,
    )
    return fn(mu, second, gain, bias, mu_w, srm_w)
