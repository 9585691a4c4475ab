"""Impl-dispatch registry: xla/kernel parity for full-model forwards.

The acceptance bar for the unified operator registry (core/dispatch.py):
  * every registered PFP op carries BOTH implementations;
  * `Context(impl='kernel')` routes an end-to-end MLP, LeNet-5 and
    transformer-LM forward through the Pallas kernel wrappers (asserted
    structurally: the kernel-impl jaxpr contains pallas_call, the xla one
    does not) and produces the same (mean, var) as the XLA stack;
  * `set_default_impl` flips forwards that carry no explicit impl.

Kernels run in interpret mode off-TPU, so the parity here is numerical
(fp32 accumulate vs XLA's fused graph), not bitwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bayes.convert import svi_to_pfp
from repro.configs import reduced_config
from repro.core import dispatch
from repro.core.gaussian import GaussianTensor, SRM, VAR
from repro.core.modes import Mode
from repro.models import lm
from repro.models.simple import (lenet5_forward, lenet5_init, mlp_forward,
                                 mlp_init)
from repro.nn.module import Context

KEY = jax.random.PRNGKey(0)


def _assert_close(a, b, rtol, atol):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol)


def _parity(forward, params, x):
    out_x = forward(params, x, Context(mode=Mode.PFP, impl="xla"))
    out_k = forward(params, x, Context(mode=Mode.PFP, impl="kernel"))
    _assert_close(out_x.mean, out_k.mean, rtol=1e-3, atol=1e-4)
    _assert_close(out_x.var, out_k.var, rtol=1e-2, atol=1e-5)
    return out_x, out_k


# ---------------------------------------------------------------------------
# Registry invariants
# ---------------------------------------------------------------------------
def test_every_registered_op_has_both_impls():
    ops = dispatch.registered_ops()
    assert ops, "registry is empty"
    for name, impls in ops.items():
        assert set(impls) == set(dispatch.IMPLS), (name, sorted(impls))
    # The operator library the tentpole promised, at minimum:
    for required in ("dense", "einsum", "conv2d_im2col", "activation",
                     "maxpool2d", "attention", "rmsnorm", "layernorm",
                     "glu_product"):
        assert required in ops, required


def test_default_impl_flips_unannotated_contexts():
    p = svi_to_pfp(mlp_init(KEY, d_hidden=32))
    x = jax.random.normal(KEY, (2, 784))
    assert dispatch.get_default_impl() == "xla"
    baseline = mlp_forward(p, x, Context(mode=Mode.PFP))  # impl=None
    try:
        dispatch.set_default_impl("kernel")
        assert dispatch.resolve_impl(None) == "kernel"
        flipped = mlp_forward(p, x, Context(mode=Mode.PFP))
    finally:
        dispatch.set_default_impl("xla")
    _assert_close(baseline.mean, flipped.mean, rtol=1e-3, atol=1e-4)
    _assert_close(baseline.var, flipped.var, rtol=1e-2, atol=1e-5)
    with pytest.raises(ValueError):
        dispatch.set_default_impl("tvm")


def test_kernel_impl_lowers_to_pallas_calls():
    p = svi_to_pfp(mlp_init(KEY, d_hidden=32))
    x = jax.random.normal(KEY, (2, 784))

    def jaxpr_for(impl):
        return str(jax.make_jaxpr(
            lambda p_, x_: mlp_forward(p_, x_, Context(mode=Mode.PFP,
                                                       impl=impl)))(p, x))

    assert "pallas_call" not in jaxpr_for("xla")
    assert jaxpr_for("kernel").count("pallas_call") >= 4  # 3 dense + acts


# ---------------------------------------------------------------------------
# Full-model parity: the paper's evaluation models
# ---------------------------------------------------------------------------
def test_mlp_forward_parity():
    params = svi_to_pfp(mlp_init(KEY, d_hidden=64))
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 784))
    _parity(mlp_forward, params, x)


def test_mlp_forward_parity_var_formulation():
    # The 'var' (Eq. 7) ablation runs its own four-matmul Pallas kernel
    # under impl='kernel' ('dense_var' schedules) — full-model parity
    # against the XLA formulation, and the forward must actually lower to
    # pallas_call (the old xla-only fallback is gone).
    params = svi_to_pfp(mlp_init(KEY, d_hidden=32), rep="var")
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (4, 784))
    out_x = mlp_forward(params, x, Context(mode=Mode.PFP, impl="xla",
                                           formulation="var"))
    out_k = mlp_forward(params, x, Context(mode=Mode.PFP, impl="kernel",
                                           formulation="var"))
    _assert_close(out_x.mean, out_k.mean, rtol=1e-3, atol=1e-4)
    _assert_close(out_x.var, out_k.var, rtol=1e-2, atol=1e-5)
    jaxpr = str(jax.make_jaxpr(
        lambda p_, x_: mlp_forward(p_, x_, Context(
            mode=Mode.PFP, impl="kernel", formulation="var")))(params, x))
    assert jaxpr.count("pallas_call") >= 3  # hidden/out dense + activations


def test_dense_var_op_parity_across_schedules():
    # Registry-level parity of the Eq. 7 kernel against its oracle, under
    # the default AND several tuned candidate schedules (any emitted
    # candidate must be numerically safe).
    from repro.kernels import ops
    from repro.tuning.search import candidates

    rng = np.random.default_rng(7)
    m, k, n = 12, 200, 48
    mu_x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    var_x = jnp.asarray(abs(rng.standard_normal((m, k))), jnp.float32)
    mu_w = jnp.asarray(0.1 * rng.standard_normal((k, n)), jnp.float32)
    var_w = jnp.asarray(abs(0.1 * rng.standard_normal((k, n))), jnp.float32)
    want = ops.pfp_dense_var(mu_x, var_x, mu_w, var_w, impl="xla")
    for sched in [None] + candidates("dense_var", (m, k, n), limit=3):
        got = ops.pfp_dense_var(mu_x, var_x, mu_w, var_w, impl="kernel",
                                schedule=sched)
        for g, w in zip(got, want):
            _assert_close(g, w, rtol=1e-4, atol=1e-5)


def test_dense_var_is_tunable():
    from repro.tuning import DEFAULT_SCHEDULES, TUNABLE_OPS
    from repro.tuning.measure import make_runner
    from repro.tuning.search import candidates, cost_summary

    assert "dense_var" in TUNABLE_OPS and "dense_var" in DEFAULT_SCHEDULES
    shape_key = (8, 96, 64)
    cands = candidates("dense_var", shape_key)
    assert cands and all(cost_summary("dense_var", shape_key, c).fits_vmem
                         for c in cands)
    run = make_runner("dense_var", shape_key)
    want = run(None)
    for sched in cands[:2]:
        got = run(sched)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=sched.describe())


def test_lenet5_forward_parity():
    params = svi_to_pfp(lenet5_init(jax.random.fold_in(KEY, 3)))
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 28, 28, 1))
    _parity(lenet5_forward, params, x)


@pytest.mark.parametrize("arch", ["granite-8b", "deepseek-moe-16b"])
def test_lm_forward_parity(arch):
    cfg = reduced_config(arch)
    params = svi_to_pfp(lm.init_params(cfg, jax.random.fold_in(KEY, 5)))
    tokens = {"tokens": jax.random.randint(jax.random.fold_in(KEY, 6),
                                           (2, 16), 0, cfg.vocab_size)}
    # the training path (capacity dispatch for MoE) and the inference
    # path (dropless grouped experts)
    for aux_loss in (True, False):
        lx, _, _ = lm.forward(params, cfg, tokens,
                              Context(mode=Mode.PFP, impl="xla"),
                              moe_aux_loss=aux_loss)
        lk, _, _ = lm.forward(params, cfg, tokens,
                              Context(mode=Mode.PFP, impl="kernel"),
                              moe_aux_loss=aux_loss)
        _assert_close(lx.mean, lk.mean, rtol=1e-3, atol=1e-4)
        _assert_close(lx.var, lk.var, rtol=1e-2, atol=1e-5)


def test_lm_custom_positions_parity():
    # Packed/remapped position ids: the kernel attention masks causally by
    # INDEX, so the fast path must fall back to the position-aware XLA core
    # — the two impls still have to agree.
    cfg = reduced_config("granite-8b")
    params = svi_to_pfp(lm.init_params(cfg, jax.random.fold_in(KEY, 20)))
    b, t = 2, 16
    pos = jnp.broadcast_to(jnp.arange(t // 2, dtype=jnp.int32), (b, t // 2))
    inputs = {
        "tokens": jax.random.randint(jax.random.fold_in(KEY, 21), (b, t), 0,
                                     cfg.vocab_size),
        # two packed segments: positions restart halfway through
        "positions": jnp.concatenate([pos, pos], axis=1),
    }
    lx, _, _ = lm.forward(params, cfg, inputs,
                          Context(mode=Mode.PFP, impl="xla"))
    lk, _, _ = lm.forward(params, cfg, inputs,
                          Context(mode=Mode.PFP, impl="kernel"))
    _assert_close(lx.mean, lk.mean, rtol=1e-3, atol=1e-4)
    _assert_close(lx.var, lk.var, rtol=1e-2, atol=1e-5)


def test_lm_kernel_impl_reaches_pallas():
    cfg = reduced_config("granite-8b")
    params = svi_to_pfp(lm.init_params(cfg, jax.random.fold_in(KEY, 7)))
    tokens = {"tokens": jnp.zeros((2, 16), jnp.int32)}

    def jaxpr_for(impl):
        return str(jax.make_jaxpr(
            lambda p_, t_: lm.forward(p_, cfg, t_,
                                      Context(mode=Mode.PFP,
                                              impl=impl))[0])(params, tokens))

    assert "pallas_call" not in jaxpr_for("xla")
    # dense projections + attention + norms + activations inside the
    # scanned block, plus embedding-side ops and the lm head.
    assert jaxpr_for("kernel").count("pallas_call") >= 5


# ---------------------------------------------------------------------------
# Per-op parity for the ops full models exercise only partially
# ---------------------------------------------------------------------------
def _gauss(key, shape, scale=1.0, rep=VAR):
    k1, k2 = jax.random.split(key)
    mu = scale * jax.random.normal(k1, shape)
    var = scale * jax.nn.softplus(jax.random.normal(k2, shape))
    gt = GaussianTensor(mu, var, VAR)
    return gt.to_srm() if rep == SRM else gt


@pytest.mark.parametrize("act", [None, "relu", "gelu"])
def test_fused_norm_activation_parity(act):
    x = _gauss(jax.random.fold_in(KEY, 8), (6, 48))
    gain = jax.random.normal(jax.random.fold_in(KEY, 9), (48,))
    bias = jax.random.normal(jax.random.fold_in(KEY, 10), (48,))
    a = dispatch.pfp_rmsnorm(x, gain, act=act, impl="xla")
    b = dispatch.pfp_rmsnorm(x, gain, act=act, impl="kernel")
    assert a.rep == b.rep == (SRM if act else VAR)
    _assert_close(a.mean, b.mean, rtol=1e-4, atol=1e-5)
    _assert_close(a.second, b.second, rtol=1e-4, atol=1e-5)
    a = dispatch.pfp_layernorm(x, gain, bias, act=act, impl="xla")
    b = dispatch.pfp_layernorm(x, gain, bias, act=act, impl="kernel")
    _assert_close(a.mean, b.mean, rtol=1e-4, atol=1e-5)
    _assert_close(a.second, b.second, rtol=1e-4, atol=1e-5)


def test_batched_expert_einsum_parity():
    x = _gauss(jax.random.fold_in(KEY, 11), (4, 8, 32), rep=SRM)
    w = _gauss(jax.random.fold_in(KEY, 12), (4, 32, 16), 0.1, rep=SRM)
    a = dispatch.pfp_einsum("ecd,edf->ecf", x, w, impl="xla")
    b = dispatch.pfp_einsum("ecd,edf->ecf", x, w, impl="kernel")
    _assert_close(a.mean, b.mean, rtol=1e-4, atol=1e-4)
    _assert_close(a.var, b.var, rtol=1e-3, atol=1e-4)


def test_depthwise_einsum_parity():
    # The recurrent block's causal depthwise conv taps ("wbtr,wr->btr")
    # used to be a silent XLA fallback under impl='kernel'; it now runs as
    # an R-batched matvec on the batched-expert dense kernel.
    x = _gauss(jax.random.fold_in(KEY, 16), (4, 2, 6, 24), rep=SRM)
    w = _gauss(jax.random.fold_in(KEY, 17), (4, 24), 0.1, rep=SRM)
    a = dispatch.pfp_einsum("wbtr,wr->btr", x, w, impl="xla")
    b = dispatch.pfp_einsum("wbtr,wr->btr", x, w, impl="kernel")
    assert a.rep == b.rep
    _assert_close(a.mean, b.mean, rtol=1e-4, atol=1e-5)
    _assert_close(a.second, b.second, rtol=1e-3, atol=1e-5)


def test_profiler_counts_einsum_fallbacks():
    # A spec with no kernel mapping must be COUNTED when it falls back to
    # the XLA impl, so a 'kernel impl' run can't silently hide XLA work
    # (the benchmark's `fallbacks` check reads this count) — and the
    # lifted specs must not count.
    x = _gauss(jax.random.fold_in(KEY, 18), (3, 5, 7), rep=SRM)
    w = _gauss(jax.random.fold_in(KEY, 19), (5, 7), 0.1, rep=SRM)
    lifted_x = _gauss(jax.random.fold_in(KEY, 16), (4, 2, 6, 24), rep=SRM)
    lifted_w = _gauss(jax.random.fold_in(KEY, 17), (4, 24), 0.1, rep=SRM)
    with dispatch.count_fallbacks() as falls:
        dispatch.pfp_einsum("abc,bc->abc", x, w, impl="kernel")
        dispatch.pfp_einsum("wbtr,wr->btr", lifted_x, lifted_w,
                            impl="kernel")
    assert any(label.startswith("einsum:abc,bc->abc") for label in falls)
    assert not any("wbtr" in label for label in falls)
    assert sum(falls.values()) == 1
    # the scope is closed: a later fallback is not counted in it
    dispatch.pfp_einsum("abc,bc->abc", x, w, impl="kernel")
    assert sum(falls.values()) == 1


@pytest.mark.parametrize("kv_heads", [4, 2, 1])  # MHA, GQA, MQA
def test_attention_op_parity_gqa_shapes(kv_heads):
    kq, kk, kv, kw = jax.random.split(jax.random.fold_in(KEY, 13), 4)
    q = jax.random.normal(kq, (2, 4, 16, 8))
    k = jax.random.normal(kk, (2, kv_heads, 16, 8))
    v = jax.random.normal(kv, (2, kv_heads, 16, 8))
    vv = jax.nn.softplus(jax.random.normal(kw, (2, kv_heads, 16, 8)))
    for causal in (True, False):
        am, av = dispatch.pfp_attention(q, k, v, vv, scale=8 ** -0.5,
                                        causal=causal, impl="xla")
        bm, bv = dispatch.pfp_attention(q, k, v, vv, scale=8 ** -0.5,
                                        causal=causal, impl="kernel")
        _assert_close(am, bm, rtol=1e-4, atol=1e-5)
        _assert_close(av, bv, rtol=1e-4, atol=1e-5)


def test_glu_product_parity():
    a = _gauss(jax.random.fold_in(KEY, 14), (5, 33))
    b = _gauss(jax.random.fold_in(KEY, 15), (5, 33))
    x = dispatch.pfp_glu_product(a, b, impl="xla")
    y = dispatch.pfp_glu_product(a, b, impl="kernel")
    assert x.rep == y.rep == SRM
    _assert_close(x.mean, y.mean, rtol=1e-5, atol=1e-6)
    _assert_close(x.second, y.second, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Cross-op fused norm_dense_act: transformer-LM-level equivalence
# ---------------------------------------------------------------------------
# The fusion pass may never change what a model says. Equivalence bar (see
# kernels/pfp_fused.py): greedy tokens EXACT and the cache-miss fallback
# bitwise; moments and MI traces to float tolerance (XLA contracts mul+add
# into FMAs inside its fused regions, so the unfused chain itself is not
# bitwise reproducible against any two-kernel split of the same math).
_NDA_TOL = dict(rtol=1e-3, atol=5e-4)


@pytest.fixture
def clean_fusion_state():
    from repro.tuning import cache as tcache

    tcache.reset_global_cache()
    prev = dispatch.set_fusion(False)
    try:
        yield tcache
    finally:
        dispatch.set_fusion(prev)
        tcache.reset_global_cache()


def _lm_fixture():
    cfg = reduced_config("granite-8b")
    params = svi_to_pfp(lm.init_params(cfg, jax.random.fold_in(KEY, 30)))
    tokens = {"tokens": jax.random.randint(jax.random.fold_in(KEY, 31),
                                           (2, 16), 0, cfg.vocab_size)}
    return cfg, params, tokens


def _variant_schedule(op, shape_key, variant):
    """A non-default schedule for ``op`` at ``shape_key``: pick from the
    tuner's own candidate space (every emitted candidate is numerically
    safe) among those with an explicitly non-default grid annotation, so
    the fused unit provably runs a searched lowering, not the miss-path
    defaults. variant 0/1 take opposite ends of that slice."""
    from repro.tuning.search import candidates

    cands = [s for s in candidates(op, shape_key, limit=64)
             if s.axis("dims") == "arbitrary"]
    assert len(cands) >= 2, (op, shape_key)
    return cands[0] if variant == 0 else cands[-1]


@pytest.mark.parametrize("variant", [0, 1])
def test_lm_fused_norm_dense_act_parity(variant, clean_fusion_state):
    from repro.serving.decode import uncertainty_decode

    tcache = clean_fusion_state
    cfg, params, tokens = _lm_fixture()

    # Discover every (op, shape, dtype) the fused model consults — with an
    # empty cache the pendings all fall back, so the recorder sees both the
    # fused-unit queries and the unfused chain's dense queries.
    with dispatch.fusion(True), tcache.record_shapes() as queries:
        lm.forward(params, cfg, tokens, Context(mode=Mode.PFP,
                                                impl="kernel"))
    assert any(q[0] == "norm_dense_act" for q in queries), \
        "fusion pass never consulted the fused unit"

    # Warm the cache at a non-default schedule per consulted shape — the
    # dense entries double as the fused unit's block_k donor, keeping the
    # fused accumulation order identical to the unfused chain's.
    cache = tcache.global_cache()
    for op, shape_key, dtype, backend in dict.fromkeys(queries):
        if op in ("norm_dense_act", "dense"):
            cache.put(op, shape_key, dtype, backend,
                      _variant_schedule(op, shape_key, variant))

    mi_key = jax.random.fold_in(KEY, 32)
    for impl in ("xla", "kernel"):
        ctx = Context(mode=Mode.PFP, impl=impl)
        with dispatch.fusion(False):
            base, _, _ = lm.forward(params, cfg, tokens, ctx)
        with dispatch.fusion(True), tcache.record_shapes() as fused_q:
            fused, _, _ = lm.forward(params, cfg, tokens, ctx)
        if impl == "kernel":
            # The warmed run really dispatched the fused kernel: the fused
            # unit was consulted and every fused-unit/donor-dense consult
            # hit (other ops stay cold on purpose — their miss defaults
            # are not under test here).
            nda_q = [q for q in fused_q
                     if q[0] in ("norm_dense_act", "dense")]
            assert any(q[0] == "norm_dense_act" for q in nda_q)
            assert all(cache.get(*q) is not None for q in nda_q), nda_q
        else:
            # The fusion pass is kernel-only: under xla it must be a
            # bitwise no-op, not merely close.
            np.testing.assert_array_equal(np.asarray(base.mean),
                                          np.asarray(fused.mean))
        # Greedy tokens: exact at every position, both impls.
        np.testing.assert_array_equal(
            np.argmax(np.asarray(base.mean), -1),
            np.argmax(np.asarray(fused.mean), -1))
        _assert_close(fused.mean, base.mean, **_NDA_TOL)
        _assert_close(fused.var, base.var, **_NDA_TOL)
        # MI trace: the uncertainty head sees the same predictive moments.
        mi_base = uncertainty_decode(base.mean, base.var, mi_key)
        mi_fused = uncertainty_decode(fused.mean, fused.var, mi_key)
        np.testing.assert_array_equal(np.asarray(mi_base.token),
                                      np.asarray(mi_fused.token))
        _assert_close(mi_fused.mutual_info, mi_base.mutual_info, **_NDA_TOL)
        _assert_close(mi_fused.total_unc, mi_base.total_unc, **_NDA_TOL)


def test_lm_fusion_cache_miss_falls_back_bitwise(clean_fusion_state):
    # Fusion enabled but no norm_dense_act entry in the cache: every
    # pending must materialize the real unfused chain — bit-for-bit, not
    # allclose (the fallback runs the exact same jaxpr).
    cfg, params, tokens = _lm_fixture()
    ctx = Context(mode=Mode.PFP, impl="kernel")
    with dispatch.fusion(False):
        base, _, _ = lm.forward(params, cfg, tokens, ctx)
    with dispatch.fusion(True):
        fused, _, _ = lm.forward(params, cfg, tokens, ctx)
    np.testing.assert_array_equal(np.asarray(base.mean),
                                  np.asarray(fused.mean))
    np.testing.assert_array_equal(np.asarray(base.var),
                                  np.asarray(fused.var))
