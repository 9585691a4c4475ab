"""Batched-expert joint PFP dense Pallas kernel — the MoE fast path.

The MoE expert MLP contracts (E, C, K) dispatch buffers against
(E, K, N) expert weight stacks: E independent PFP dense problems. The
`xla` impl vmaps the per-expert reference chain; this kernel instead puts
the expert axis ON THE GRID of one Pallas call, so

  * one kernel launch covers all experts (the vmapped lowering pays one
    program per expert, or relies on XLA batching heuristics);
  * ``block_e`` experts share a grid step — their (bc, bk) / (bk, bn)
    tiles are resident in VMEM together, amortizing grid-step overhead
    E/block_e-fold (the autotuner's "expert-grid blocking" axis);
  * per-expert math is byte-for-byte the `pfp_dense` kernels' Eq. 12 /
    Eq. 13 / Eq. 7 accumulation, so the oracle chain (kernel -> vmapped
    ref -> pfp_math -> Monte-Carlo) is unchanged.

Grid: (E/be, C/bc, N/bn, K/bk) with K innermost and 'arbitrary' (the
fp32 accumulators live in VMEM across K steps, exactly like
`pfp_dense.py`). The searched axes (`dims`, `k_order`, block shapes) have
the same semantics as the dense kernel; ``k_order='unrolled'`` drops the
K grid axis and replays the identical accumulation sequence in-body.

The grouped kernel (``pfp_dense_grouped_pallas``) is the dropless serving
path's: rows sorted by expert, each expert's rows starting on a row tile
of their own, and per tile its expert's index as scalar prefetch. Each
row tile runs Eq. 12 against its own expert's (``mu``, ``srm``) tiles, so
an expert's weights are read once per row tile it has, and an expert with
no rows is not read at all. The row buffer is sized for the worst case;
tiles from the real count on are skipped (zero output, no weight DMA).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pfp_dense import _compiler_params


def _bdense_kernel(mu_x_ref, srm_x_ref, mu_w_ref, srm_w_ref,
                   mu_out_ref, var_out_ref, acc_musq_ref, *, be: int,
                   nk: int):
    """One (e, i, j, k) grid step: Eq. 12 for ``be`` resident experts."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        mu_out_ref[...] = jnp.zeros_like(mu_out_ref)
        var_out_ref[...] = jnp.zeros_like(var_out_ref)
        acc_musq_ref[...] = jnp.zeros_like(acc_musq_ref)

    for b in range(be):
        mu_x = mu_x_ref[b]
        mu_w = mu_w_ref[b]
        mu_out_ref[b] += jnp.dot(mu_x, mu_w,
                                 preferred_element_type=jnp.float32)
        var_out_ref[b] += jnp.dot(srm_x_ref[b], srm_w_ref[b],
                                  preferred_element_type=jnp.float32)
        acc_musq_ref[b] += jnp.dot(jnp.square(mu_x), jnp.square(mu_w),
                                   preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finalize():
        var_out_ref[...] = var_out_ref[...] - acc_musq_ref[...]


def _bdense_kernel_unrolled(mu_x_ref, srm_x_ref, mu_w_ref, srm_w_ref,
                            mu_out_ref, var_out_ref, *, be: int, bk: int,
                            nk: int):
    """(e, i, j) grid step with the K-tile loop unrolled in-body —
    replays the grid kernel's exact per-expert accumulation sequence."""
    for b in range(be):
        shape = mu_out_ref.shape[1:]
        mu_acc = jnp.zeros(shape, jnp.float32)
        var_acc = jnp.zeros(shape, jnp.float32)
        musq_acc = jnp.zeros(shape, jnp.float32)
        for t in range(nk):
            sl = slice(t * bk, (t + 1) * bk)
            mu_x = mu_x_ref[b, :, sl]
            mu_w = mu_w_ref[b, sl, :]
            mu_acc = mu_acc + jnp.dot(mu_x, mu_w,
                                      preferred_element_type=jnp.float32)
            var_acc = var_acc + jnp.dot(srm_x_ref[b, :, sl],
                                        srm_w_ref[b, sl, :],
                                        preferred_element_type=jnp.float32)
            musq_acc = musq_acc + jnp.dot(jnp.square(mu_x), jnp.square(mu_w),
                                          preferred_element_type=jnp.float32)
        mu_out_ref[b] = mu_acc
        var_out_ref[b] = var_acc - musq_acc


def _bfirst_layer_kernel(x_ref, mu_w_ref, var_w_ref,
                         mu_out_ref, var_out_ref, *, be: int, nk: int):
    """Eq. 13 per expert: mu = x.mu_w ; var = x^2.var_w."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        mu_out_ref[...] = jnp.zeros_like(mu_out_ref)
        var_out_ref[...] = jnp.zeros_like(var_out_ref)

    for b in range(be):
        x = x_ref[b]
        mu_out_ref[b] += jnp.dot(x, mu_w_ref[b],
                                 preferred_element_type=jnp.float32)
        var_out_ref[b] += jnp.dot(jnp.square(x), var_w_ref[b],
                                  preferred_element_type=jnp.float32)


def _bfirst_layer_kernel_unrolled(x_ref, mu_w_ref, var_w_ref,
                                  mu_out_ref, var_out_ref, *, be: int,
                                  bk: int, nk: int):
    for b in range(be):
        shape = mu_out_ref.shape[1:]
        mu_acc = jnp.zeros(shape, jnp.float32)
        var_acc = jnp.zeros(shape, jnp.float32)
        for t in range(nk):
            sl = slice(t * bk, (t + 1) * bk)
            x = x_ref[b, :, sl]
            mu_acc = mu_acc + jnp.dot(x, mu_w_ref[b, sl, :],
                                      preferred_element_type=jnp.float32)
            var_acc = var_acc + jnp.dot(jnp.square(x), var_w_ref[b, sl, :],
                                        preferred_element_type=jnp.float32)
        mu_out_ref[b] = mu_acc
        var_out_ref[b] = var_acc


def _bvar_formulation_kernel(mu_x_ref, var_x_ref, mu_w_ref, var_w_ref,
                             mu_out_ref, var_out_ref, *, be: int, nk: int):
    """Eq. 7 ('var' formulation) per expert: four MXU matmuls, every
    variance term non-negative so no finalize correction."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        mu_out_ref[...] = jnp.zeros_like(mu_out_ref)
        var_out_ref[...] = jnp.zeros_like(var_out_ref)

    for b in range(be):
        mu_x = mu_x_ref[b]
        var_x = var_x_ref[b]
        mu_w = mu_w_ref[b]
        var_w = var_w_ref[b]
        mu_out_ref[b] += jnp.dot(mu_x, mu_w,
                                 preferred_element_type=jnp.float32)
        var_out_ref[b] += jnp.dot(var_x, jnp.square(mu_w),
                                  preferred_element_type=jnp.float32)
        var_out_ref[b] += jnp.dot(jnp.square(mu_x), var_w,
                                  preferred_element_type=jnp.float32)
        var_out_ref[b] += jnp.dot(var_x, var_w,
                                  preferred_element_type=jnp.float32)


def _bvar_formulation_kernel_unrolled(mu_x_ref, var_x_ref, mu_w_ref,
                                      var_w_ref, mu_out_ref, var_out_ref, *,
                                      be: int, bk: int, nk: int):
    for b in range(be):
        shape = mu_out_ref.shape[1:]
        mu_acc = jnp.zeros(shape, jnp.float32)
        var_acc = jnp.zeros(shape, jnp.float32)
        for t in range(nk):
            sl = slice(t * bk, (t + 1) * bk)
            mu_x = mu_x_ref[b, :, sl]
            var_x = var_x_ref[b, :, sl]
            mu_w = mu_w_ref[b, sl, :]
            var_w = var_w_ref[b, sl, :]
            mu_acc = mu_acc + jnp.dot(mu_x, mu_w,
                                      preferred_element_type=jnp.float32)
            var_acc = var_acc + jnp.dot(var_x, jnp.square(mu_w),
                                        preferred_element_type=jnp.float32)
            var_acc = var_acc + jnp.dot(jnp.square(mu_x), var_w,
                                        preferred_element_type=jnp.float32)
            var_acc = var_acc + jnp.dot(var_x, var_w,
                                        preferred_element_type=jnp.float32)
        mu_out_ref[b] = mu_acc
        var_out_ref[b] = var_acc


def _batched_geometry(k_order: str, dims: str, e: int, c: int, n: int,
                      be: int, bc: int, bn: int, bk: int, nk: int):
    """(grid, x_spec, w_spec, out_spec, semantics) with the expert axis
    leading the grid. Like the dense geometry, 'nmk' swaps only the
    spatial (c, n) axes — K stays innermost so per-output accumulation
    order never changes; the expert axis is independent work either way
    and shares the spatial ``dims`` annotation."""
    if k_order == "unrolled":
        grid = (e // be, c // bc, n // bn)
        kdim = bk * nk
        return (grid,
                pl.BlockSpec((be, bc, kdim), lambda ei, i, j: (ei, i, 0)),
                pl.BlockSpec((be, kdim, bn), lambda ei, i, j: (ei, 0, j)),
                pl.BlockSpec((be, bc, bn), lambda ei, i, j: (ei, i, j)),
                (dims, dims, dims))
    if k_order == "nmk":
        grid = (e // be, n // bn, c // bc, nk)
        return (grid,
                pl.BlockSpec((be, bc, bk), lambda ei, j, i, k: (ei, i, k)),
                pl.BlockSpec((be, bk, bn), lambda ei, j, i, k: (ei, k, j)),
                pl.BlockSpec((be, bc, bn), lambda ei, j, i, k: (ei, i, j)),
                (dims, dims, dims, "arbitrary"))
    if k_order != "mnk":
        raise ValueError(f"unknown k_order {k_order!r}")
    grid = (e // be, c // bc, n // bn, nk)
    return (grid,
            pl.BlockSpec((be, bc, bk), lambda ei, i, j, k: (ei, i, k)),
            pl.BlockSpec((be, bk, bn), lambda ei, i, j, k: (ei, k, j)),
            pl.BlockSpec((be, bc, bn), lambda ei, i, j, k: (ei, i, j)),
            (dims, dims, dims, "arbitrary"))


@functools.partial(
    jax.jit,
    static_argnames=("block_e", "block_c", "block_n", "block_k", "interpret",
                     "first_layer", "dims", "k_order"),
)
def pfp_dense_batched_pallas(
    mu_x,
    srm_x,
    mu_w,
    srm_w,
    *,
    block_e: int = 1,
    block_c: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
    first_layer: bool = False,
    dims: str = "parallel",
    k_order: str = "mnk",
):
    """Batched joint PFP dense: (E,C,K)x(E,K,N) -> mean, variance
    (E,C,N) fp32, one Pallas call with the expert axis on the grid.

    For ``first_layer=True`` the inputs are (x, x_unused, mu_w, var_w)
    per Eq. 13; pass ``srm_x=x``.

    Shapes must be multiples of the block sizes — `ops.pfp_dense_batched`
    pads.
    """
    e, c, kdim = mu_x.shape
    _, _, n = mu_w.shape
    be = min(block_e, e)
    bc, bn, bk = min(block_c, c), min(block_n, n), min(block_k, kdim)
    assert e % be == 0 and c % bc == 0 and n % bn == 0 and kdim % bk == 0, \
        (e, c, n, kdim, be, bc, bn, bk)
    nk = kdim // bk
    grid, x_spec, w_spec, out_spec, sem = _batched_geometry(
        k_order, dims, e, c, n, be, bc, bn, bk, nk)

    common = dict(
        grid=grid,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((e, c, n), jnp.float32),
            jax.ShapeDtypeStruct((e, c, n), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(sem),
    )

    unrolled = k_order == "unrolled"
    if first_layer:
        kernel = (functools.partial(_bfirst_layer_kernel_unrolled, be=be,
                                    bk=bk, nk=nk)
                  if unrolled else
                  functools.partial(_bfirst_layer_kernel, be=be, nk=nk))
        fn = pl.pallas_call(
            kernel,
            in_specs=[x_spec, w_spec, w_spec],
            **common,
        )
        return fn(mu_x, mu_w, srm_w)

    if unrolled:
        fn = pl.pallas_call(
            functools.partial(_bdense_kernel_unrolled, be=be, bk=bk, nk=nk),
            in_specs=[x_spec, x_spec, w_spec, w_spec],
            **common,
        )
    else:
        fn = pl.pallas_call(
            functools.partial(_bdense_kernel, be=be, nk=nk),
            in_specs=[x_spec, x_spec, w_spec, w_spec],
            scratch_shapes=[pltpu.VMEM((be, bc, bn), jnp.float32)],
            **common,
        )
    return fn(mu_x, srm_x, mu_w, srm_w)


@functools.partial(
    jax.jit,
    static_argnames=("block_e", "block_c", "block_n", "block_k", "interpret",
                     "dims", "k_order"),
)
def pfp_dense_batched_var_pallas(
    mu_x,
    var_x,
    mu_w,
    var_w,
    *,
    block_e: int = 1,
    block_c: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
    dims: str = "parallel",
    k_order: str = "mnk",
):
    """Batched joint PFP dense, Eq. 7 'var' formulation: (E,C,K)x(E,K,N)
    -> (mean, variance) (E,C,N) fp32 from (mu, var) operands."""
    e, c, kdim = mu_x.shape
    _, _, n = mu_w.shape
    be = min(block_e, e)
    bc, bn, bk = min(block_c, c), min(block_n, n), min(block_k, kdim)
    assert e % be == 0 and c % bc == 0 and n % bn == 0 and kdim % bk == 0, \
        (e, c, n, kdim, be, bc, bn, bk)
    nk = kdim // bk
    grid, x_spec, w_spec, out_spec, sem = _batched_geometry(
        k_order, dims, e, c, n, be, bc, bn, bk, nk)
    common = dict(
        grid=grid,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((e, c, n), jnp.float32),
            jax.ShapeDtypeStruct((e, c, n), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(sem),
    )
    kernel = (functools.partial(_bvar_formulation_kernel_unrolled, be=be,
                                bk=bk, nk=nk)
              if k_order == "unrolled" else
              functools.partial(_bvar_formulation_kernel, be=be, nk=nk))
    fn = pl.pallas_call(
        kernel,
        in_specs=[x_spec, x_spec, w_spec, w_spec],
        **common,
    )
    return fn(mu_x, var_x, mu_w, var_w)


# ---------------------------------------------------------------------------
# Grouped experts: one row tile per step group, its expert by scalar prefetch
# ---------------------------------------------------------------------------
def _grouped_kernel(tile_expert_ref, num_tiles_ref, mu_x_ref, srm_x_ref,
                    mu_w_ref, srm_w_ref, mu_out_ref, var_out_ref,
                    acc_musq_ref, *, nk: int):
    """One (i, j, k) step: Eq. 12 for row tile i against its expert's
    (k, j) weight tiles, read as stored and cast to the rows' dtype."""
    del tile_expert_ref  # used by the index maps only
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        mu_out_ref[...] = jnp.zeros_like(mu_out_ref)
        var_out_ref[...] = jnp.zeros_like(var_out_ref)
        acc_musq_ref[...] = jnp.zeros_like(acc_musq_ref)

    @pl.when(i < num_tiles_ref[0])
    def _accumulate():
        mu_x = mu_x_ref[...]
        mu_w = mu_w_ref[0].astype(mu_x.dtype)
        mu_out_ref[...] += jnp.dot(mu_x, mu_w,
                                   preferred_element_type=jnp.float32)
        var_out_ref[...] += jnp.dot(srm_x_ref[...],
                                    srm_w_ref[0].astype(mu_x.dtype),
                                    preferred_element_type=jnp.float32)
        acc_musq_ref[...] += jnp.dot(jnp.square(mu_x), jnp.square(mu_w),
                                     preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finalize():
        var_out_ref[...] = var_out_ref[...] - acc_musq_ref[...]


def _grouped_steps(i, j, k, num_tiles_ref, nn: int, nk: int):
    """(row tile, n, k) a step reads. A skipped tile (i >= the real count)
    reads what the last live step read, so no tile is fetched for it."""
    nt = num_tiles_ref[0]
    dead = (i >= nt).astype(jnp.int32)
    return (jnp.minimum(i, jnp.maximum(nt - 1, 0)),
            j + dead * (nn - 1 - j), k + dead * (nk - 1 - k))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k", "interpret"))
def pfp_dense_grouped_pallas(mu_x, srm_x, mu_w, srm_w, tile_expert,
                             num_tiles, *, block_m: int, block_n: int,
                             block_k: int, interpret: bool = False):
    """Grouped joint PFP dense: (M, K) rows x (E, K, N) expert weights ->
    mean, variance (M, N) fp32. Row tile i (``block_m`` rows) belongs to
    expert ``tile_expert[i]``; ``num_tiles`` (shape (1,)) tiles are live.
    M, K, N must be multiples of the blocks (`ops.pfp_dense_grouped`
    chooses blocks that divide them)."""
    m, kdim = mu_x.shape
    n = mu_w.shape[-1]
    bm, bn, bk = block_m, block_n, block_k
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        (m, n, kdim, bm, bn, bk)
    nn, nk = n // bn, kdim // bk

    def x_map(i, j, k, te, nt):
        ii, _, kk = _grouped_steps(i, j, k, nt, nn, nk)
        return ii, kk

    def w_map(i, j, k, te, nt):
        ii, jj, kk = _grouped_steps(i, j, k, nt, nn, nk)
        return te[ii], kk, jj

    x_spec = pl.BlockSpec((bm, bk), x_map)
    w_spec = pl.BlockSpec((1, bk, bn), w_map)
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, k, te, nt: (i, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // bm, nn, nk),
        in_specs=[x_spec, x_spec, w_spec, w_spec],
        out_specs=[out_spec, out_spec],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_grouped_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((m, n), jnp.float32),
                   jax.ShapeDtypeStruct((m, n), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
    )
    return fn(tile_expert.astype(jnp.int32),
              jnp.reshape(num_tiles, (1,)).astype(jnp.int32),
              mu_x, srm_x, mu_w, srm_w)
