"""Per-architecture smoke tests: reduced configs, all three execution modes,
forward + train step + decode on CPU, asserting shapes and finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bayes.convert import svi_to_pfp
from repro.configs import ASSIGNED_ARCHS, get_config, reduced_config
from repro.core.gaussian import is_gaussian
from repro.core.modes import Mode
from repro.models import lm
from repro.nn.module import Context
from repro.training.optimizer import Adam
from repro.training.train_loop import init_train_state, make_svi_train_step

KEY = jax.random.PRNGKey(0)
B, T = 2, 16


def _inputs(cfg, t=T, batch=B):
    out = {}
    if cfg.embed_inputs:
        out["tokens"] = jax.random.randint(KEY, (batch, t), 0, cfg.vocab_size)
    else:
        out["frame_embeddings"] = jax.random.normal(KEY, (batch, t, cfg.d_model))
    if cfg.family == "vlm":
        out["image_embeddings"] = jax.random.normal(
            KEY, (batch, cfg.num_image_tokens, cfg.d_model))
    return out


@pytest.fixture(scope="module")
def models():
    cache = {}
    for arch in ASSIGNED_ARCHS:
        cfg = reduced_config(arch)
        cache[arch] = (cfg, lm.init_params(cfg, KEY))
    return cache


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("mode", [Mode.DETERMINISTIC, Mode.SVI, Mode.PFP])
def test_forward_all_modes(models, arch, mode):
    cfg, params = models[arch]
    ctx = Context(mode=mode, key=jax.random.PRNGKey(1))
    logits, aux, _ = lm.forward(params, cfg, _inputs(cfg), ctx)
    if is_gaussian(logits):
        assert logits.mean.shape == (B, T, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits.mean)))
        assert bool(jnp.all(jnp.isfinite(logits.var)))
        assert bool(jnp.all(logits.var >= -1e-5))
    else:
        assert logits.shape == (B, T, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_decode_step(models, arch):
    cfg, params = models[arch]
    ctx = Context(mode=Mode.PFP)
    s_len = 24
    states = lm.init_decode_state(cfg, B, s_len)
    inp = _inputs(cfg, t=1)
    inp["positions"] = jnp.full((B, 1), 5, jnp.int32)
    inp["cache_len"] = jnp.full((B,), 6, jnp.int32)
    logits, new_states = lm.decode_step(params, cfg, inp, states, ctx)
    m = logits.mean if is_gaussian(logits) else logits
    assert m.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(m)))
    assert jax.tree_util.tree_structure(new_states) is not None


@pytest.mark.parametrize("arch", ["granite-8b", "mamba2-370m",
                                  "recurrentgemma-2b", "deepseek-moe-16b"])
def test_svi_train_step_decreases_nothing_nan(models, arch):
    cfg, params = models[arch]

    def fwd(p, batch, ctx):
        logits, aux, _ = lm.forward(p, cfg, batch, ctx)
        return logits, aux

    opt = Adam(learning_rate=1e-3, clip_norm=1.0)
    step = make_svi_train_step(fwd, opt, num_data=1000)
    state = init_train_state(params, opt)
    batch = _inputs(cfg)
    batch["targets"] = jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)
    for i in range(2):
        state, metrics = step(state, batch, jax.random.PRNGKey(i))
        assert np.isfinite(float(metrics["loss"])), arch
    assert int(state.step) == 2


@pytest.mark.parametrize("arch", ["granite-8b", "gemma-7b",
                                  "deepseek-moe-16b"])
def test_prefill_then_decode_consistent(models, arch):
    """Prefill state + one decode step == full forward on the extended seq
    (PFP mean path, tolerance for bf16-free fp32 run). The MoE model
    serves dropless, so no token's routing depends on the others'."""
    cfg, params = models[arch]
    params_pfp = svi_to_pfp(params)
    ctx = Context(mode=Mode.PFP)
    toks = jax.random.randint(KEY, (B, T + 1), 0, cfg.vocab_size)

    # full forward over T+1 tokens (the inference path, as prefill and
    # decode run)
    full, _, _ = lm.forward(params_pfp, cfg, {"tokens": toks}, ctx,
                            moe_aux_loss=False)

    # prefill T, then decode token T
    last, states = lm.prefill(params_pfp, cfg, {"tokens": toks[:, :T]}, ctx,
                              max_len=T + 1)
    dec_in = {
        "tokens": toks[:, T:],
        "positions": jnp.full((B, 1), T, jnp.int32),
        # valid entries INCLUDING the token fed this step (the decode-input
        # contract enforced now that forward() threads cache_len into the
        # attention mask)
        "cache_len": jnp.full((B,), T + 1, jnp.int32),
    }
    dec, _ = lm.decode_step(params_pfp, cfg, dec_in, states, ctx)
    np.testing.assert_allclose(
        np.asarray(dec.mean[:, 0]), np.asarray(full.mean[:, -1]),
        rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(dec.var[:, 0]), np.asarray(full.var[:, -1]),
        rtol=5e-3, atol=5e-3)


def test_long_500k_skip_logic():
    from repro.launch.programs import cell_is_applicable

    ok, _ = cell_is_applicable("mamba2-370m", "long_500k")
    assert ok
    ok, why = cell_is_applicable("granite-8b", "long_500k")
    assert not ok and "sub-quadratic" in why


def test_param_counts_sane():
    granite = get_config("granite-8b").param_count()
    assert 7e9 < granite < 9.5e9, granite
    moe = get_config("deepseek-moe-16b")
    # 16.4B parameters, 2.8B active (arXiv:2401.06066): the dense layer 0
    # is 10944 wide, the router 64 wide, 6 routed + 2 shared active
    assert moe.dense_ffn_width == 10944 and moe.router_width == 64
    assert 1.6e10 < moe.param_count() < 1.7e10, moe.param_count()
    assert 2.7e9 < moe.active_param_count() < 2.9e9
    vision = get_config("llama-3.2-vision-90b").param_count()
    assert 7e10 < vision < 1.1e11, vision
