"""The plain references against the system at a small size on the CPU:
the engine's prefill and paged decode against the dense-LM reference, and
the kernel-impl LeNet-5 forward against its reference."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.reference import common, dense_lm, lenet5
from bench.tests import small

# bf16 compute: every op rounds to bf16 (2^-8 relative) and the 2-layer
# model and head compound that to a few 1e-2 of the largest logit mean.
SERVE_MEAN_TOL = 5e-2
# Variances: the dense kernel's SRM form (srm.srm - mu^2.mu^2) cancels in
# bf16, which the reference's Eq. 7 form does not: ~1e-2 a layer.
SERVE_VAR_TOL = 1e-1
# LeNet-5, fp32: the kernels' rational erf and the reference's lax.erf
# differ by ulps near +-1, and ReLU's left-tail cancellation amplifies
# that to ~1e-4 of the largest logit (3e-4 measured at batch 100).
PAPER_TOL = 2e-3


@pytest.fixture(scope="module")
def granite():
    conf = {**spec.config("granite-8b"), **small.GRANITE}
    cell = {**spec.workload("granite-8b.chat.steady")}
    from bench.systems import lm_serving
    return conf, lm_serving.System(conf, cell, seed=2**31 + 7)


def test_engine_prefill_and_paged_decode_match_reference(granite):
    from repro.serving.batcher import Request

    conf, system = granite
    engine = system.new_engine()
    prompt = np.random.default_rng(1).integers(0, 97, 21).astype(np.int32)
    req = Request(uid=5, prompt=prompt, max_new_tokens=3)
    engine.submit(req)
    rows = []
    for _ in range(2):       # prefill (two chunks) + first decode; decode
        system.step(engine)
        slot = 0
        rows.append(tuple(np.asarray(b[slot]) for b in engine.logit_buffers))
    while not engine.idle:
        system.step(engine)
    gen = list(req.generated)
    # after step 1 the buffers hold position P (fed gen[0]); after step 2
    # position P + 1 (fed gen[1])
    seq = np.concatenate([prompt, np.asarray(gen[:2], np.int32)])
    p = len(prompt)
    mu, var = dense_lm.logits(conf, system.seed, [seq], [[p, p + 1]])
    for i, (m, v) in enumerate(rows):
        assert common.rel_err(m, mu[i]) <= SERVE_MEAN_TOL
        assert common.rel_err(v, var[i]) <= SERVE_VAR_TOL
    out = dense_lm.compare(conf, system.seed, system.seed,
                           conf["engine"]["num_uncertainty_samples"],
                           [(5, prompt, gen, list(req.mi_trace))])
    assert out["tokens"] == 3
    # served tokens sit at or within bf16 noise of the reference argmax
    assert out["logit_gap"] <= SERVE_MEAN_TOL * float(jnp.max(jnp.abs(mu)))
    assert out["mi_gap"] <= 5e-2       # MI moves with the logit moments


def test_lenet5_kernel_forward_matches_reference():
    from bench.systems import paper_cnn
    from bench.traffic import batch
    from bench.weights import logit_sample_key

    conf = spec.config("paper-lenet5")
    seed = 11
    system = paper_cnn.System(conf, {}, seed)
    x = batch.images(4, 3)
    out = system.call(x, 7)
    errs = lenet5.compare(conf, seed, logit_sample_key(seed),
                          [(7, x, out)])
    assert errs["mean_err"] <= PAPER_TOL and errs["var_err"] <= PAPER_TOL
    # same normals on both sides: entropy and MI agree to float rounding
    assert errs["entropy_gap"] <= 1e-4 and errs["mi_gap"] <= 1e-4
    r_mu, r_var = lenet5.forward(lenet5.load(conf, seed), jnp.asarray(x))
    assert np.asarray(out[2]).shape == (4,)
    assert float(jnp.min(r_var)) > 0


def test_reference_numerics():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    exact = common.Numerics("highest").einsum("ik,kj->ij", a, b)
    high = common.Numerics("high").einsum("ik,kj->ij", a, b)
    bf16 = common.Numerics("bf16").einsum("ik,kj->ij", a, b)
    fp8 = common.Numerics("highest", fp8=True).einsum("ik,kj->ij", a, b)
    e_high, e_fp8 = common.rel_err(high, exact), common.rel_err(fp8, exact)
    e_bf16 = common.rel_err(bf16, exact)
    assert 0 < e_high < 1e-4 < e_bf16 < 1e-2 < e_fp8 < 0.2
