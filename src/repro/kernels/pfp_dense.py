"""Fused joint PFP dense Pallas kernel — the paper's flagship operator on TPU.

The paper's two key operator insights (TVM §5) map to one kernel design:

  * joint operator  — the mean and variance paths are computed in the SAME
    grid step, so each (bm, bk) tile of mu_x / srm_x and (bk, bn) tile of
    mu_w / srm_w is loaded into VMEM once and feeds all three MXU matmuls;
  * SRM formulation — Eq. 12 needs 3 matmuls (mu.mu, srm.srm, mu^2.mu^2)
    instead of Eq. 7's 4, and consumes the SRMs the previous activation
    already produced (no conversion pass over HBM).

Grid: (M/bm, N/bn, K/bk) with the K axis 'arbitrary' (sequential) so the
fp32 accumulators live in VMEM across K steps. Block shapes default to
MXU-aligned (128, 128) tiles with bk=512; the autotuner (repro.tuning)
overrides them per (shape, dtype, backend) through `ops.pfp_dense`'s
schedule argument — this kernel only requires block-multiple (padded)
operands, so any searched schedule is legal.

Beyond block shapes the autotuner searches two more axes here:

  * ``dims``     — Mosaic dimension_semantics for the spatial grid axes
    ("parallel" or "arbitrary"; the K axis always stays "arbitrary"
    because it carries the accumulator). A compiler annotation only —
    ignored in interpret mode, never changes results.
  * ``k_order``  — "mnk" (legacy grid), "nmk" (spatial axes swapped; K
    still innermost so each output block's accumulation order is
    untouched), or "unrolled" (grid (M/bm, N/bn) with full K strips
    resident and the K-tile loop unrolled in the kernel body — the same
    0 + dot(t0) + dot(t1) + ... sequence the grid version performs
    against its VMEM accumulator, so results are bit-identical).

A `first_layer` variant implements Eq. 13 (deterministic inputs): two
matmuls, no mu^2 correction accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dense_kernel(mu_x_ref, srm_x_ref, mu_w_ref, srm_w_ref,
                  mu_out_ref, var_out_ref, acc_musq_ref, *, nk: int):
    """One (i, j, k) grid step of the joint PFP dense operator."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        mu_out_ref[...] = jnp.zeros_like(mu_out_ref)
        var_out_ref[...] = jnp.zeros_like(var_out_ref)
        acc_musq_ref[...] = jnp.zeros_like(acc_musq_ref)

    mu_x = mu_x_ref[...]
    mu_w = mu_w_ref[...]
    # Three MXU matmuls sharing the tiles already resident in VMEM.
    mu_out_ref[...] += jnp.dot(mu_x, mu_w, preferred_element_type=jnp.float32)
    var_out_ref[...] += jnp.dot(
        srm_x_ref[...], srm_w_ref[...], preferred_element_type=jnp.float32
    )
    acc_musq_ref[...] += jnp.dot(
        jnp.square(mu_x), jnp.square(mu_w), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _finalize():
        # Eq. 12: sigma^2 = E[x^2].E[w^2] - (mu_x.mu_w)^2 (per-j, reduced).
        var_out_ref[...] = var_out_ref[...] - acc_musq_ref[...]


def _dense_kernel_unrolled(mu_x_ref, srm_x_ref, mu_w_ref, srm_w_ref,
                           mu_out_ref, var_out_ref, *, bk: int, nk: int):
    """One (i, j) grid step with the K-tile loop unrolled in-body.

    Replays the exact accumulation sequence of :func:`_dense_kernel`
    (zero-init, then one fp32 add per K tile per accumulator, then the
    mu^2 correction) so the two lowerings are bit-identical.
    """
    shape = mu_out_ref.shape
    mu_acc = jnp.zeros(shape, jnp.float32)
    var_acc = jnp.zeros(shape, jnp.float32)
    musq_acc = jnp.zeros(shape, jnp.float32)
    for t in range(nk):
        sl = slice(t * bk, (t + 1) * bk)
        mu_x = mu_x_ref[:, sl]
        mu_w = mu_w_ref[sl, :]
        mu_acc = mu_acc + jnp.dot(mu_x, mu_w,
                                  preferred_element_type=jnp.float32)
        var_acc = var_acc + jnp.dot(srm_x_ref[:, sl], srm_w_ref[sl, :],
                                    preferred_element_type=jnp.float32)
        musq_acc = musq_acc + jnp.dot(jnp.square(mu_x), jnp.square(mu_w),
                                      preferred_element_type=jnp.float32)
    mu_out_ref[...] = mu_acc
    var_out_ref[...] = var_acc - musq_acc


def _first_layer_kernel(x_ref, mu_w_ref, var_w_ref,
                        mu_out_ref, var_out_ref, *, nk: int):
    """Eq. 13: mu = x.mu_w ; var = x^2.var_w — two MXU matmuls."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        mu_out_ref[...] = jnp.zeros_like(mu_out_ref)
        var_out_ref[...] = jnp.zeros_like(var_out_ref)

    x = x_ref[...]
    mu_out_ref[...] += jnp.dot(x, mu_w_ref[...], preferred_element_type=jnp.float32)
    var_out_ref[...] += jnp.dot(
        jnp.square(x), var_w_ref[...], preferred_element_type=jnp.float32
    )


def _first_layer_kernel_unrolled(x_ref, mu_w_ref, var_w_ref,
                                 mu_out_ref, var_out_ref, *, bk: int,
                                 nk: int):
    shape = mu_out_ref.shape
    mu_acc = jnp.zeros(shape, jnp.float32)
    var_acc = jnp.zeros(shape, jnp.float32)
    for t in range(nk):
        sl = slice(t * bk, (t + 1) * bk)
        x = x_ref[:, sl]
        mu_acc = mu_acc + jnp.dot(x, mu_w_ref[sl, :],
                                  preferred_element_type=jnp.float32)
        var_acc = var_acc + jnp.dot(jnp.square(x), var_w_ref[sl, :],
                                    preferred_element_type=jnp.float32)
    mu_out_ref[...] = mu_acc
    var_out_ref[...] = var_acc


def _var_formulation_kernel(mu_x_ref, var_x_ref, mu_w_ref, var_w_ref,
                            mu_out_ref, var_out_ref, *, nk: int):
    """Eq. 7 ('var' formulation) grid step: mu = mu_x.mu_w and
    sigma^2 = var_x.mu_w^2 + mu_x^2.var_w + var_x.var_w — four MXU
    matmuls per tile (vs Eq. 12's three), every term non-negative so the
    variance accumulator needs no finalize correction. The joint-operator
    property is the same as the SRM kernel's: all four matmuls consume
    the (bm, bk) / (bk, bn) tiles already resident in VMEM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        mu_out_ref[...] = jnp.zeros_like(mu_out_ref)
        var_out_ref[...] = jnp.zeros_like(var_out_ref)

    mu_x = mu_x_ref[...]
    var_x = var_x_ref[...]
    mu_w = mu_w_ref[...]
    var_w = var_w_ref[...]
    mu_out_ref[...] += jnp.dot(mu_x, mu_w, preferred_element_type=jnp.float32)
    var_out_ref[...] += jnp.dot(
        var_x, jnp.square(mu_w), preferred_element_type=jnp.float32)
    var_out_ref[...] += jnp.dot(
        jnp.square(mu_x), var_w, preferred_element_type=jnp.float32)
    var_out_ref[...] += jnp.dot(
        var_x, var_w, preferred_element_type=jnp.float32)


def _var_formulation_kernel_unrolled(mu_x_ref, var_x_ref, mu_w_ref,
                                     var_w_ref, mu_out_ref, var_out_ref, *,
                                     bk: int, nk: int):
    shape = mu_out_ref.shape
    mu_acc = jnp.zeros(shape, jnp.float32)
    var_acc = jnp.zeros(shape, jnp.float32)
    for t in range(nk):
        sl = slice(t * bk, (t + 1) * bk)
        mu_x = mu_x_ref[:, sl]
        var_x = var_x_ref[:, sl]
        mu_w = mu_w_ref[sl, :]
        var_w = var_w_ref[sl, :]
        mu_acc = mu_acc + jnp.dot(mu_x, mu_w,
                                  preferred_element_type=jnp.float32)
        # Same three-add-per-tile order as the grid kernel.
        var_acc = var_acc + jnp.dot(var_x, jnp.square(mu_w),
                                    preferred_element_type=jnp.float32)
        var_acc = var_acc + jnp.dot(jnp.square(mu_x), var_w,
                                    preferred_element_type=jnp.float32)
        var_acc = var_acc + jnp.dot(var_x, var_w,
                                    preferred_element_type=jnp.float32)
    mu_out_ref[...] = mu_acc
    var_out_ref[...] = var_acc


def _compiler_params(dims=("parallel", "parallel", "arbitrary")):
    """Mosaic compiler params carrying ``dimension_semantics`` for the
    grid (rank must match); interpret mode ignores them."""
    return pltpu.CompilerParams(dimension_semantics=tuple(dims))


def _dense_geometry(k_order: str, dims: str, m: int, n: int,
                    bm: int, bn: int, bk: int, nk: int):
    """(grid, in_specs_x, in_specs_w, out_spec, semantics) for one dense
    K-loop order. 'nmk' swaps the spatial grid axes only — K stays the
    innermost sequential axis either way, so per-output accumulation
    order (and therefore bits) never changes."""
    if k_order == "unrolled":
        grid = (m // bm, n // bn)
        kdim = bk * nk
        return (grid,
                pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
                pl.BlockSpec((kdim, bn), lambda i, j: (0, j)),
                pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                (dims, dims))
    if k_order == "nmk":
        grid = (n // bn, m // bm, nk)
        return (grid,
                pl.BlockSpec((bm, bk), lambda j, i, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda j, i, k: (k, j)),
                pl.BlockSpec((bm, bn), lambda j, i, k: (i, j)),
                (dims, dims, "arbitrary"))
    if k_order != "mnk":
        raise ValueError(f"unknown k_order {k_order!r}")
    grid = (m // bm, n // bn, nk)
    return (grid,
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            (dims, dims, "arbitrary"))


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret",
                     "first_layer", "dims", "k_order"),
)
def pfp_dense_pallas(
    mu_x,
    srm_x,
    mu_w,
    srm_w,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
    first_layer: bool = False,
    dims: str = "parallel",
    k_order: str = "mnk",
):
    """Joint PFP dense: (M,K)x(K,N) -> mean (M,N), variance (M,N) in fp32.

    For ``first_layer=True`` the inputs are interpreted as
    (x, x_unused, mu_w, var_w) per Eq. 13; pass ``srm_x=x``.

    Shapes must be multiples of the block sizes — `ops.pfp_dense` pads.
    """
    m, kdim = mu_x.shape
    _, n = mu_w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (m, n, kdim, bm, bn, bk)
    nk = kdim // bk
    grid, in_specs_x, in_specs_w, out_spec, sem = _dense_geometry(
        k_order, dims, m, n, bm, bn, bk, nk)

    out_shape = [
        jax.ShapeDtypeStruct((m, n), jnp.float32),
        jax.ShapeDtypeStruct((m, n), jnp.float32),
    ]

    common = dict(
        grid=grid,
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_compiler_params(sem),
    )

    unrolled = k_order == "unrolled"
    if first_layer:
        kernel = (functools.partial(_first_layer_kernel_unrolled, bk=bk, nk=nk)
                  if unrolled else
                  functools.partial(_first_layer_kernel, nk=nk))
        fn = pl.pallas_call(
            kernel,
            in_specs=[in_specs_x, in_specs_w, in_specs_w],
            **common,
        )
        mu, var = fn(mu_x, mu_w, srm_w)
        return mu, var

    if unrolled:
        fn = pl.pallas_call(
            functools.partial(_dense_kernel_unrolled, bk=bk, nk=nk),
            in_specs=[in_specs_x, in_specs_x, in_specs_w, in_specs_w],
            **common,
        )
    else:
        fn = pl.pallas_call(
            functools.partial(_dense_kernel, nk=nk),
            in_specs=[in_specs_x, in_specs_x, in_specs_w, in_specs_w],
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            **common,
        )
    mu, var = fn(mu_x, srm_x, mu_w, srm_w)
    return mu, var


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret",
                              "dims", "k_order"))
def pfp_dense_var_pallas(
    mu_x,
    var_x,
    mu_w,
    var_w,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
    dims: str = "parallel",
    k_order: str = "mnk",
):
    """Joint PFP dense, Eq. 7 'var' formulation: (M,K)x(K,N) -> (mean,
    variance) in fp32 from (mu, var) operands. Four matmuls per tile (the
    Fig. 5 ablation's native representation — no SRM conversion charged).

    Shapes must be multiples of the block sizes — `ops.pfp_dense_var`
    pads.
    """
    m, kdim = mu_x.shape
    _, n = mu_w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        (m, n, kdim, bm, bn, bk)
    nk = kdim // bk
    grid, in_specs_x, in_specs_w, out_spec, sem = _dense_geometry(
        k_order, dims, m, n, bm, bn, bk, nk)
    common = dict(
        grid=grid,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(sem),
    )
    kernel = (functools.partial(_var_formulation_kernel_unrolled, bk=bk, nk=nk)
              if k_order == "unrolled" else
              functools.partial(_var_formulation_kernel, nk=nk))
    fn = pl.pallas_call(
        kernel,
        in_specs=[in_specs_x, in_specs_x, in_specs_w, in_specs_w],
        **common,
    )
    return fn(mu_x, var_x, mu_w, var_w)

