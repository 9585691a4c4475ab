"""Expert-parallel MoE benchmark: the grid-level batched-expert Gaussian
dense kernel against its vmapped per-expert baseline, plus the MoE engine
decode step.

Row families, emitted through benchmarks/common.py:

  moe/expert_gemm/...   one row per (E, C, K, N) expert-GEMM fixture: the
                        ONE-Pallas-call batched-expert kernel under the
                        calibrated cost model's best block_e > 1 schedule,
                        timed against the vmapped baseline two ways — the
                        best block_e = 1 schedule (structurally the
                        vmapped grid: one expert per grid step) and the
                        vmapped XLA oracle chain. The derived column
                        carries the cost model's predicted seconds for
                        both kernel schedules and ``ranked_faster`` —
                        whether the model ranks the grid-level kernel
                        ahead of the vmapped baseline (the acceptance
                        bit) — plus the max |err| of the batched kernel
                        vs the vmapped oracle;
  moe/moe_forward/...   the routed MoE block end to end (router + scatter
                        dispatch + batched expert GEMMs + combine) through
                        ``nn.moe.moe_apply`` on the xla and kernel stacks,
                        with the capacity drop fraction in derived.

Off-TPU the kernel wall clocks are Pallas interpret-mode timings (the
relative numbers measure the interpreter, not the schedule); the
predicted_* columns are backend-independent and carry the ranking
acceptance. Deterministic seeds, so rows are comparable across PRs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.kernels import ops
from repro.tuning import search

QUICK_SHAPES = [(8, 64, 64, 128)]
FULL_SHAPES = [(8, 64, 64, 128), (8, 512, 64, 128), (16, 256, 128, 256)]


def _gaussian_operands(key, shape_key):
    e, c, k, n = shape_key
    kx, kw = jax.random.split(key)
    mu_x = jax.random.normal(kx, (e, c, k), jnp.float32)
    mu_w = jax.random.normal(kw, (e, k, n), jnp.float32) / jnp.sqrt(k)
    # SRM operands: E[a^2] = mu^2 + var with a small positive variance.
    srm_x = mu_x ** 2 + 0.05
    srm_w = mu_w ** 2 + 0.01
    return mu_x, srm_x, mu_w, srm_w


def _best(cands, pred, *, batched):
    pool = [s for s in cands if (s.block("block_e", 1) > 1) == batched]
    return min(pool, key=pred) if pool else None


def _expert_gemm_row(lines, shape_key, *, iters):
    mu_x, srm_x, mu_w, srm_w = _gaussian_operands(
        jax.random.PRNGKey(0), shape_key)
    cands = search.candidates("dense_batched", shape_key)
    pred = lambda s: search.predicted_seconds(  # noqa: E731
        "dense_batched", shape_key, s)
    batched = _best(cands, pred, batched=True)
    vmapped = _best(cands, pred, batched=False)
    if batched is None or vmapped is None:
        return  # degenerate shape: the menu collapsed onto one grid form

    def run_kernel(sched):
        fn = jax.jit(lambda a, b, c, d: ops.pfp_dense_batched(
            a, b, c, d, impl="kernel", schedule=sched))
        return fn, time_fn(fn, mu_x, srm_x, mu_w, srm_w,
                           warmup=1, iters=iters)

    fn_b, t_batched = run_kernel(batched)
    _, t_vmapped = run_kernel(vmapped)
    oracle = jax.jit(lambda a, b, c, d: ops.pfp_dense_batched(
        a, b, c, d, impl="xla"))
    t_xla = time_fn(oracle, mu_x, srm_x, mu_w, srm_w, warmup=1, iters=iters)

    mu_k, var_k = fn_b(mu_x, srm_x, mu_w, srm_w)
    mu_o, var_o = oracle(mu_x, srm_x, mu_w, srm_w)
    err = max(float(jnp.max(jnp.abs(mu_k - mu_o))),
              float(jnp.max(jnp.abs(var_k - var_o))))

    pb, pv = pred(batched), pred(vmapped)
    derived = ";".join([
        f"predicted_batched_s={pb:.2e}",
        f"predicted_vmapped_s={pv:.2e}",
        f"predicted_speedup={pv / pb:.3f}",
        f"ranked_faster={int(pb < pv)}",
        f"vmapped_kernel_s={t_vmapped:.6f}",
        f"vmapped_xla_s={t_xla:.6f}",
        f"candidates={len(cands)}",
        f"max_err_vs_oracle={err:.2e}",
    ])
    name = "x".join(str(d) for d in shape_key)
    lines.append(emit(f"moe/expert_gemm/{name}", t_batched, derived,
                      impl="kernel", schedule=batched.describe()))


def _moe_forward_row(lines, *, iters):
    """The routed MoE block end to end on both dispatch stacks."""
    from repro.core.gaussian import SRM, GaussianTensor
    from repro.core.modes import Mode
    from repro.nn.module import Context
    from repro.nn import moe

    key = jax.random.PRNGKey(1)
    s, d, ff, n_e, top_k = 64, 32, 64, 8, 2
    params = moe.moe_init(key, d_model=d, d_ff=ff, num_experts=n_e,
                          num_shared=1, gated=True)
    mu = jax.random.normal(jax.random.fold_in(key, 1), (1, s, d), jnp.float32)
    x = GaussianTensor(mu, mu ** 2 + 0.05, SRM)

    rows = {}
    for impl in ("xla", "kernel"):
        ctx = Context(mode=Mode.PFP, formulation="srm", impl=impl)
        fn = jax.jit(lambda p, a, _ctx=ctx: moe.moe_apply(
            p, a, _ctx, num_experts=n_e, top_k=top_k,
            capacity_factor=1.0))
        rows[impl] = (fn, time_fn(fn, params, x, warmup=1, iters=iters))
    _, aux_k = rows["kernel"][0](params, x)
    drop = float(aux_k["moe_dropped"]) / float(aux_k["moe_assignments"])
    for impl, (_, t) in rows.items():
        lines.append(emit(f"moe/moe_forward/{s}x{d}x{ff}e{n_e}k{top_k}", t,
                          f"drop_rate={drop:.4f};experts={n_e};top_k={top_k}",
                          impl=impl))


def run(quick: bool = True):
    lines = []
    iters = 3 if quick else 10
    for shape_key in (QUICK_SHAPES if quick else FULL_SHAPES):
        _expert_gemm_row(lines, shape_key, iters=iters)
    _moe_forward_row(lines, iters=iters)
    return lines


if __name__ == "__main__":
    from benchmarks.common import CSV_HEADER

    print(CSV_HEADER)
    run()
