"""Expert-parallel Gaussian MoE fast path.

Acceptance bars for the grid-level batched-expert kernel and the explicit
all-to-all dispatch (kernels/pfp_moe.py, core/dispatch.py, nn/moe.py):

  * the ONE-Pallas-call batched-expert kernel matches its vmapped XLA
    oracle (mean <= 1e-5, var <= 1e-4) under the default AND non-default
    tuned candidate schedules, for the SRM (Eq. 12), first-layer (Eq. 13)
    and 'var' (Eq. 7) formulations;
  * the routed MoE block agrees across the xla and kernel dispatch stacks,
    gated and ungated;
  * dispatch_mode='a2a' (explicit shard_map all_to_all dispatch/combine)
    is bit-for-bit the single-host scatter path on a 1-device mesh, and
    allclose on a real 4-device CPU mesh (subprocess — the main test
    process must keep seeing ONE device);
  * PFP moments through the routed block match Monte-Carlo weight
    sampling (SVI forwards) within CLT bands;
  * the aux accounting is exact: moe_dropped equals the independently
    recomputed capacity-overflow count, zero when capacity is ample;
  * the dropless serving path: the grouped-expert kernel matches its
    per-tile oracle, four expert shares add up to the uncut layer, and
    every real token's assignments are computed while padding rows get
    none (the capacity path drops them).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gaussian import GaussianTensor, SRM
from repro.core.modes import Mode
from repro.kernels import ops
from repro.nn import moe
from repro.nn.module import Context
from repro.tuning.schedules import DEFAULT_SCHEDULES
from repro.tuning.search import candidates

KEY = jax.random.PRNGKey(0)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _assert_close(a, b, rtol, atol, msg=""):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol, err_msg=msg)


def _operands(key, e, c, k, n):
    kx, kw = jax.random.split(key)
    mu_x = jax.random.normal(kx, (e, c, k), jnp.float32)
    mu_w = jax.random.normal(kw, (e, k, n), jnp.float32) * 0.1
    srm_x = mu_x ** 2 + 0.3
    srm_w = mu_w ** 2 + 0.01
    return mu_x, srm_x, mu_w, srm_w


def _nondefault(op, shape_key, count):
    default = DEFAULT_SCHEDULES[op].describe()
    picked = [s for s in candidates(op, shape_key)
              if s.describe() != default]
    assert len(picked) >= count, (op, shape_key, len(picked))
    # spread across the ranked space so block_e > 1 grids are covered
    step = max(1, len(picked) // count)
    return picked[::step][:count]


# ---------------------------------------------------------------------------
# Kernel vs vmapped oracle, across tuned candidate schedules
# ---------------------------------------------------------------------------
def test_batched_kernel_matches_vmapped_oracle_across_schedules():
    e, c, k, n = 4, 24, 40, 48
    mu_x, srm_x, mu_w, srm_w = _operands(jax.random.fold_in(KEY, 1),
                                         e, c, k, n)
    want = ops.pfp_dense_batched(mu_x, srm_x, mu_w, srm_w, impl="xla")
    for sched in [None] + _nondefault("dense_batched", (e, c, k, n), 3):
        got = ops.pfp_dense_batched(mu_x, srm_x, mu_w, srm_w, impl="kernel",
                                    schedule=sched)
        label = sched.describe() if sched else "default"
        _assert_close(got[0], want[0], rtol=0.0, atol=1e-5, msg=label)
        _assert_close(got[1], want[1], rtol=0.0, atol=1e-4, msg=label)


def test_batched_kernel_first_layer_matches_oracle():
    e, c, k, n = 4, 24, 40, 48
    mu_x, _, mu_w, srm_w = _operands(jax.random.fold_in(KEY, 2), e, c, k, n)
    want = ops.pfp_dense_batched(mu_x, mu_x, mu_w, srm_w, impl="xla",
                                 first_layer=True)
    for sched in [None] + _nondefault("dense_batched", (e, c, k, n), 3):
        got = ops.pfp_dense_batched(mu_x, mu_x, mu_w, srm_w, impl="kernel",
                                    first_layer=True, schedule=sched)
        label = sched.describe() if sched else "default"
        _assert_close(got[0], want[0], rtol=0.0, atol=1e-5, msg=label)
        _assert_close(got[1], want[1], rtol=0.0, atol=1e-4, msg=label)


def test_batched_kernel_var_formulation_matches_oracle():
    e, c, k, n = 4, 24, 40, 48
    mu_x, srm_x, mu_w, srm_w = _operands(jax.random.fold_in(KEY, 3),
                                         e, c, k, n)
    var_x, var_w = srm_x - mu_x ** 2, srm_w - mu_w ** 2
    want = ops.pfp_dense_batched_var(mu_x, var_x, mu_w, var_w, impl="xla")
    for sched in [None] + _nondefault("dense_batched", (e, c, k, n), 3):
        got = ops.pfp_dense_batched_var(mu_x, var_x, mu_w, var_w,
                                        impl="kernel", schedule=sched)
        label = sched.describe() if sched else "default"
        _assert_close(got[0], want[0], rtol=0.0, atol=1e-5, msg=label)
        _assert_close(got[1], want[1], rtol=0.0, atol=1e-4, msg=label)


def test_candidate_space_covers_batched_expert_grids():
    # The tuner's menu must actually expose the grid-level axis the kernel
    # exists for: block_e > 1 candidates that fit VMEM.
    cands = candidates("dense_batched", (8, 64, 64, 128))
    assert any(s.block("block_e", 1) > 1 for s in cands)


# ---------------------------------------------------------------------------
# Routed MoE block: xla vs kernel dispatch stacks
# ---------------------------------------------------------------------------
def _moe_fixture(key, *, gated, d=16, ff=32, n_e=4, s=12, sigma=1e-2):
    params = moe.moe_init(key, d_model=d, d_ff=ff, num_experts=n_e,
                          num_shared=1, gated=gated, sigma_init=sigma)
    mu = jax.random.normal(jax.random.fold_in(key, 1), (1, s, d), jnp.float32)
    x = GaussianTensor(mu, mu ** 2 + 0.1, SRM)
    return params, x


@pytest.mark.parametrize("gated", [True, False])
def test_moe_apply_impl_parity(gated):
    # both paths: training (capacity, batched experts) and inference
    # (dropless, grouped experts, no load-balance loss term)
    params, x = _moe_fixture(jax.random.fold_in(KEY, 4), gated=gated)
    for aux_loss in (True, False):
        outs = {}
        for impl in ("xla", "kernel"):
            ctx = Context(mode=Mode.PFP, impl=impl)
            outs[impl], aux = moe.moe_apply(params, x, ctx, num_experts=4,
                                            top_k=2, capacity_factor=1.25,
                                            aux_loss=aux_loss)
            assert (float(aux["loss"]) == 0.0) == (not aux_loss)
        _assert_close(outs["xla"].mean, outs["kernel"].mean,
                      rtol=1e-4, atol=1e-5)
        _assert_close(outs["xla"].var, outs["kernel"].var,
                      rtol=1e-3, atol=1e-5)


def test_moe_kernel_impl_reaches_batched_pallas():
    params, x = _moe_fixture(jax.random.fold_in(KEY, 5), gated=True)

    def jaxpr_for(impl):
        ctx = Context(mode=Mode.PFP, impl=impl)
        return str(jax.make_jaxpr(lambda p, a: moe.moe_apply(
            p, a, ctx, num_experts=4, top_k=2)[0])(params, x))

    assert "pallas_call" not in jaxpr_for("xla")
    assert "pallas_call" in jaxpr_for("kernel")


# ---------------------------------------------------------------------------
# Explicit shard_map all-to-all dispatch vs single-host scatter
# ---------------------------------------------------------------------------
def test_a2a_dispatch_bitwise_on_single_device_mesh():
    from repro.launch.mesh import make_mesh
    from repro.nn import pjit_hints

    params, x = _moe_fixture(jax.random.fold_in(KEY, 6), gated=True)
    ctx = Context(mode=Mode.PFP)
    kw = dict(num_experts=4, top_k=2, capacity_factor=1.0)
    base, base_aux = moe.moe_apply(params, x, ctx, dispatch_mode="scatter",
                                   **kw)
    mesh = make_mesh((1, 1), ("data", "model"))
    prev = pjit_hints.get_rules()
    try:
        pjit_hints.set_rules({"mesh": mesh})
        a2a, a2a_aux = moe.moe_apply(params, x, ctx, dispatch_mode="a2a",
                                     **kw)
    finally:
        pjit_hints.set_rules(prev)
    # D=1: the a2a program degenerates to the same scatter expressions —
    # the contract is bit-for-bit, not allclose.
    np.testing.assert_array_equal(np.asarray(base.mean), np.asarray(a2a.mean))
    np.testing.assert_array_equal(np.asarray(base.var), np.asarray(a2a.var))
    assert float(base_aux["moe_dropped"]) == float(a2a_aux["moe_dropped"])


def test_a2a_without_mesh_falls_back_to_scatter():
    params, x = _moe_fixture(jax.random.fold_in(KEY, 7), gated=True)
    ctx = Context(mode=Mode.PFP)
    kw = dict(num_experts=4, top_k=2)
    base, _ = moe.moe_apply(params, x, ctx, dispatch_mode="scatter", **kw)
    a2a, _ = moe.moe_apply(params, x, ctx, dispatch_mode="a2a", **kw)
    np.testing.assert_array_equal(np.asarray(base.mean), np.asarray(a2a.mean))


def test_a2a_dispatch_on_four_device_mesh():
    """Real cross-device all_to_all: 4-way data-parallel CPU mesh in a
    subprocess (the main process must keep seeing one device)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    code = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.gaussian import GaussianTensor, SRM
    from repro.core.modes import Mode
    from repro.launch.mesh import make_mesh
    from repro.nn import moe, pjit_hints
    from repro.nn.module import Context

    key = jax.random.PRNGKey(6)
    d, ff, n_e, s = 16, 32, 8, 16
    params = moe.moe_init(key, d_model=d, d_ff=ff, num_experts=n_e,
                          num_shared=1, gated=True, sigma_init=1e-2)
    mu = jax.random.normal(jax.random.fold_in(key, 1), (1, s, d),
                           jnp.float32)
    x = GaussianTensor(mu, mu ** 2 + 0.1, SRM)
    ctx = Context(mode=Mode.PFP)
    kw = dict(num_experts=n_e, top_k=2, capacity_factor=1.0)
    base, base_aux = moe.moe_apply(params, x, ctx,
                                   dispatch_mode="scatter", **kw)
    mesh = make_mesh((4, 1), ("data", "model"))
    pjit_hints.set_rules({"mesh": mesh})
    with mesh:
        a2a, a2a_aux = moe.moe_apply(params, x, ctx,
                                     dispatch_mode="a2a", **kw)
    np.testing.assert_allclose(np.asarray(a2a.mean), np.asarray(base.mean),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(a2a.var), np.asarray(base.var),
                               rtol=1e-5, atol=1e-6)
    assert float(base_aux["moe_dropped"]) == float(a2a_aux["moe_dropped"])
    print("a2a-4dev-ok")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=420, env=env)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "a2a-4dev-ok" in r.stdout


# ---------------------------------------------------------------------------
# Statistical ground truth: PFP routed block vs Monte-Carlo SVI sampling
# ---------------------------------------------------------------------------
def test_moe_pfp_moments_vs_monte_carlo():
    # Deterministic input + deterministic router (plain mu array, so SVI
    # samples route identically to PFP's mean path) — the expert and
    # shared MLP weights stay variational. MC = many SVI forwards.
    d, ff, n_e, s = 8, 16, 4, 6
    key = jax.random.fold_in(KEY, 8)
    params = moe.moe_init(key, d_model=d, d_ff=ff, num_experts=n_e,
                          num_shared=1, gated=True, sigma_init=0.1)
    params = dict(params, router={"w": params["router"]["w"]["mu"]})
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, s, d), jnp.float32)
    n_mc = 4000
    band = 10.0 / np.sqrt(n_mc)
    # both paths: training (capacity 2.0 keeps every assignment) and the
    # dropless inference path, whose sampled weights run the grouped op's
    # xla chain at zero weight variance
    for aux_loss in (True, False):
        kw = dict(num_experts=n_e, top_k=2, capacity_factor=2.0,
                  aux_loss=aux_loss)
        pfp_out, _ = moe.moe_apply(
            params, GaussianTensor(x, jnp.square(x), SRM),
            Context(mode=Mode.PFP), **kw)

        def one(k, kw=kw):
            out, _ = moe.moe_apply(params, x, Context(mode=Mode.SVI, key=k),
                                   **kw)
            return out

        samples = jax.lax.map(
            jax.jit(one), jax.random.split(jax.random.fold_in(key, 2), n_mc))
        mc_mean = np.asarray(jnp.mean(samples, axis=0))
        mc_var = np.asarray(jnp.var(samples, axis=0))
        np.testing.assert_allclose(np.asarray(pfp_out.mean), mc_mean,
                                   atol=band * np.sqrt(mc_var.max() + 1e-6))
        np.testing.assert_allclose(np.asarray(pfp_out.var), mc_var,
                                   rtol=0.3, atol=band * mc_var.max())


# ---------------------------------------------------------------------------
# Drop accounting under forced capacity overflow
# ---------------------------------------------------------------------------
def _expected_drops(params, x_mean, *, num_experts, top_k, capacity_factor):
    """Independent numpy replay of the routing + capacity cumsum."""
    s = x_mean.shape[0] * x_mean.shape[1]
    d = x_mean.shape[-1]
    router = params["router"]["w"]
    router_mu = np.asarray(router["mu"] if isinstance(router, dict)
                           else router)
    logits = np.asarray(x_mean).reshape(s, d) @ router_mu
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    idx = np.argsort(-probs, axis=-1, kind="stable")[:, :top_k]
    # jax.lax.top_k breaks ties by lowest index — stable argsort matches.
    capacity = int(max(top_k, round(s * top_k * capacity_factor
                                    / num_experts)))
    fill = {e: 0 for e in range(num_experts)}
    dropped = 0
    for tok in range(s):
        for e in idx[tok]:
            fill[e] += 1
            if fill[e] > capacity:
                dropped += 1
    return dropped


@pytest.mark.parametrize("capacity_factor,overflow", [(8.0, False),
                                                      (0.25, True)])
def test_drop_accounting_matches_independent_replay(capacity_factor,
                                                    overflow):
    n_e, top_k, s = 4, 2, 24
    params, x = _moe_fixture(jax.random.fold_in(KEY, 9), gated=True, s=s,
                             n_e=n_e)
    _, aux = moe.moe_apply(params, x, Context(mode=Mode.PFP),
                           num_experts=n_e, top_k=top_k,
                           capacity_factor=capacity_factor, aux_loss=True)
    assert float(aux["moe_assignments"]) == s * top_k
    want = _expected_drops(params, x.mean, num_experts=n_e, top_k=top_k,
                           capacity_factor=capacity_factor)
    assert float(aux["moe_dropped"]) == want
    assert (want > 0) == overflow


# ---------------------------------------------------------------------------
# The dropless serving path: grouped kernel, expert share, no drops
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("e,k,n,dtype", [
    (3, 40, 48, jnp.float32),
    (3, 40, 48, jnp.bfloat16),
    # deepseek's down projection: 11 K blocks of 128, two N blocks
    (2, 1408, 2048, jnp.bfloat16),
], ids=["f32", "bf16", "deep"])
def test_grouped_kernel_matches_oracle(e, k, n, dtype):
    bm, tiles = 16, 4
    kx, kw = jax.random.split(jax.random.fold_in(KEY, 20))
    mu_x = jax.random.normal(kx, (bm * tiles, k), jnp.float32)
    mu_w = jax.random.normal(kw, (e, k, n), jnp.float32) * k ** -0.5
    srm_x, srm_w = mu_x ** 2 + 0.3, mu_w ** 2 + 0.01
    # three live tiles (experts 2, 0, 0 — one expert over two tiles),
    # then a skipped one that must come out zero
    tile_expert = jnp.asarray([e - 1, 0, 0, 0], jnp.int32)
    num_tiles = jnp.asarray(3, jnp.int32)
    args = (mu_x.astype(dtype), srm_x.astype(dtype), mu_w, srm_w,
            tile_expert, num_tiles)
    want = ops.pfp_dense_grouped(*args, block_m=bm, impl="xla")
    got = ops.pfp_dense_grouped(*args, block_m=bm, impl="kernel")
    # The means differ in summation order only (bf16 products are exact
    # in fp32). So do fp32 variances; with bf16 rows the kernel rounds the
    # squares x_mu^2, w_mu^2 to bf16, as the dense kernel does, where the
    # oracle squares in fp32, and the SRM form's cancellation magnifies
    # that to about 1.5% of the largest variance.
    var_tol = 1e-5 if dtype == jnp.float32 else 3e-2
    _assert_close(got[0], want[0], rtol=0.0,
                  atol=1e-5 * float(jnp.max(jnp.abs(want[0]))))
    _assert_close(got[1], want[1], rtol=0.0,
                  atol=var_tol * float(jnp.max(jnp.abs(want[1]))))
    assert not np.asarray(got[0][3 * bm:]).any()
    assert not np.asarray(got[1][3 * bm:]).any()


def _routed_fixture(key, *, d=16, ff=8, n_e=16, s=24, sigma=1e-2):
    params = moe.moe_init(key, d_model=d, d_ff=ff, num_experts=n_e,
                          num_shared=2, gated=True, sigma_init=sigma)
    mu = jax.random.normal(jax.random.fold_in(key, 1), (1, s, d), jnp.float32)
    return params, GaussianTensor(mu, mu ** 2 + 0.1, SRM)


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_expert_shares_add_up_to_the_uncut_layer(impl):
    """Four chips' shares of 4 experts each (the router 16 wide, top-6,
    un-normalised gates), summed with the shared experts counted once,
    are the uncut layer: mean and variance."""
    from repro.nn.mlp import mlp_apply

    params, x = _routed_fixture(jax.random.fold_in(KEY, 21))
    ctx = Context(mode=Mode.PFP, impl=impl)
    kw = dict(top_k=6, norm_topk_prob=False, aux_loss=False,
              routed_experts=16)
    whole, whole_aux = moe.moe_apply(params, x, ctx, num_experts=16, **kw)
    mean = mlp_apply(params["shared"], x, ctx).mean
    var = mlp_apply(params["shared"], x, ctx).var
    held = 0.0
    for r in range(4):
        share = {"router": params["router"],
                 "experts": jax.tree_util.tree_map(
                     lambda a, r=r: a[4 * r:4 * r + 4], params["experts"])}
        out, aux = moe.moe_apply(share, x, ctx, num_experts=4,
                                 expert_offset=4 * r, **kw)
        mean, var = mean + out.mean, var + out.var
        held += float(aux["moe_held"])
        assert float(aux["moe_dropped"]) == 0
    _assert_close(mean, whole.mean, rtol=0.0, atol=1e-5)
    _assert_close(var, whole.var, rtol=0.0, atol=1e-6)
    assert held == float(whole_aux["moe_held"]) == 24 * 6
    assert float(whole_aux["moe_assignments"]) == 24 * 6


def test_dropless_routes_every_real_token_and_no_padding():
    """Every token's first choice is expert 3 (24 tokens: capacity 1.25
    would keep 11 of them), and the last 5 rows are padding: the dropless
    path computes every real token's 6 assignments, gives the padding
    rows none (their routed part is zero), and reads 0 dropped."""
    from repro.nn.mlp import mlp_apply

    params, x = _routed_fixture(jax.random.fold_in(KEY, 22))
    mu = x.mean.at[..., 0].set(3.0)
    x = GaussianTensor(mu, mu ** 2 + 0.1, SRM)
    w = params["router"]["w"]["mu"].at[0, 3].set(20.0)
    params = dict(params, router={"w": dict(params["router"]["w"], mu=w)})
    valid = jnp.arange(24)[None, :] < 19
    ctx = Context(mode=Mode.PFP, impl="kernel")
    kw = dict(num_experts=16, top_k=6, norm_topk_prob=False)
    out, aux = moe.moe_apply(params, x, ctx, aux_loss=False, valid=valid,
                             **kw)
    # the capacity (training) path drops most of expert 3's tokens ...
    capped, capped_aux = moe.moe_apply(params, x, ctx, capacity_factor=1.25,
                                       aux_loss=True, **kw)
    assert float(capped_aux["moe_dropped"]) > 0
    # ... ample capacity drops none: the per-token routed result
    want, want_aux = moe.moe_apply(params, x, ctx, capacity_factor=100.0,
                                   aux_loss=True, **kw)
    assert float(want_aux["moe_dropped"]) == 0
    _assert_close(out.mean[:, :19], want.mean[:, :19], rtol=0.0, atol=1e-5)
    _assert_close(out.var[:, :19], want.var[:, :19], rtol=0.0, atol=1e-6)
    shared = mlp_apply(params["shared"], x, ctx)
    _assert_close(out.mean[:, 19:], shared.mean[:, 19:], rtol=0.0, atol=1e-6)
    _assert_close(out.var[:, 19:], shared.var[:, 19:], rtol=0.0, atol=1e-6)
    assert float(aux["moe_dropped"]) == 0
    assert float(aux["moe_pad_routed"]) == 0
    assert float(aux["moe_held"]) == float(aux["moe_assignments"]) == 19 * 6
    assert float(aux["moe_max_load"]) == 19
