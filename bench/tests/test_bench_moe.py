"""The deepseek-moe-16b cell at a size the CPU holds: the engine's prefill
and paged decode against the plain MoE reference, the cell's code driven
end to end through ``run_cell`` (system, traffic, reference, and the
control failing a limit), the MoE metric readers on a trace worked out by
hand, and the expert kernel's costs by hand."""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, spec
from bench import trace as tr
from bench.costs import moe as moe_costs
from bench.costs import pfp
from bench.reference import common, moe_lm
from bench.systems import moe_serving
from bench.tests import small_moe

CELL = "deepseek-moe-16b.chat.decode"
SEED = 2**31 + 12345          # above 32 signed bits, as run seeds may be
# bf16 compute: every op rounds to 2^-8, compounded over three layers and
# the head; read 0.44-0.71% of the largest logit mean and 0.37-0.44% of
# the largest variance on two seeds, so a 3-4x margin. A router that
# picked another expert for a token near a tie would move a logit by
# several percent and fail it.
SERVE_MEAN_TOL = 2.5e-2
SERVE_VAR_TOL = 2e-2
# Limits at this test size on the CPU (the cell's own limits are set
# from chip readings at the timed size). Readings at seed SEED: the
# program logit_gap 0, mi_gap 4.3e-4 nats and less, routed_gap and
# routed_var_gap about 5e-3 and 7e-3; the fp8 control fails mi_gap and
# reads about 0.2 and 0.066 on the routed part (see
# test_control_fails_a_limit).
SERVE_LIMITS = {"logit_gap": 0.05, "mi_gap": 2.5e-3, "routed_gap": 0.03,
                "routed_var_gap": 0.025}
MS = 1_000_000


def _conf():
    return {**spec.config("deepseek-moe-16b"), **small_moe.DEEPSEEK}


def _run(trace=False, **kw):
    from repro.serving.engine import engine as engine_mod

    engine_mod.clear_shared_pass_cache()
    return run.run_cell(CELL, SEED, 3.0, trace, t_start=time.perf_counter(),
                        conf_override=small_moe.DEEPSEEK,
                        cell_override={"traffic": small_moe.SERVE_TRAFFIC},
                        peak_kind="TPU v5 lite", limits=SERVE_LIMITS, **kw)


def test_engine_prefill_and_paged_decode_match_reference():
    from repro.serving.batcher import Request

    conf = _conf()
    system = moe_serving.System(conf, spec.workload(CELL), seed=2**31 + 7)
    engine = system.new_engine()
    prompt = np.random.default_rng(1).integers(0, 97, 21).astype(np.int32)
    req = Request(uid=5, prompt=prompt, max_new_tokens=3)
    engine.submit(req)
    rows = []
    for _ in range(2):       # prefill (two chunks) + first decode; decode
        system.step(engine)
        rows.append(tuple(np.asarray(b[0]) for b in engine.logit_buffers))
    while not engine.idle:
        system.step(engine)
    gen = list(req.generated)
    seq = np.concatenate([prompt, np.asarray(gen[:2], np.int32)])
    p = len(prompt)
    mu, var = moe_lm.logits(conf, system.seed, [seq], [[p, p + 1]])
    for i, (m, v) in enumerate(rows):
        assert common.rel_err(m, mu[i]) <= SERVE_MEAN_TOL
        assert common.rel_err(v, var[i]) <= SERVE_VAR_TOL
    out = moe_lm.compare(conf, system.seed, system.seed,
                         conf["engine"]["num_uncertainty_samples"],
                         [(5, prompt, gen, list(req.mi_trace))])
    assert out["tokens"] == 3
    assert out["logit_gap"] <= SERVE_MEAN_TOL * float(jnp.max(jnp.abs(mu)))
    assert out["mi_gap"] <= 5e-2       # MI moves with the logit moments
    s = engine.metrics.summary()
    # routed dropless: real tokens only, 6 of 16 experts each, some held
    assert s["moe_dropped_assignments"] == 0
    assert s["moe_padding_routed"] == 0
    assert 0 < s["moe_held_assignments"] < s["moe_assignments"]
    # two decode passes (three tokens, the first from the prefill) of
    # one row, over two MoE layers of 8 held experts
    assert 0 < s["moe_decode_held_assignments"] <= 2 * 2 * 6
    assert 0 < s["moe_decode_experts_touched"] <= \
        s["moe_decode_held_assignments"]
    assert s["moe_experts_touched"] <= s["moe_held_assignments"]


def test_rehearsal():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    man = spec.manifest()
    want = {m["name"] for m in spec.cell_metrics(man, CELL, "end_to_end")}
    assert set(r["metrics"]) == want
    assert r["checks"]["fallbacks"]["value"] == 0


def test_traced_rehearsal():
    r = _run(trace=True)
    assert r["correct"], r["checks"]
    # no device planes on the CPU: the device readers find nothing
    assert "moe_expert_share" not in r["metrics"]
    assert "expert_gemm_roofline" not in r["metrics"]


def test_control_fails_a_limit():
    r = _run(control=True)
    assert r["correct"], r["checks"]
    assert r["control_correct"] is False, r["control"]
    assert any(r["control"][k] > v for k, v in SERVE_LIMITS.items())


@pytest.fixture(scope="module")
def moe_inputs():
    """The first MoE layer's inputs (normed mean and variance) at every
    position of three random sequences, from the reference."""
    conf = _conf()
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, conf["vocab_size"], 24).astype(np.int32)
            for _ in range(3)]
    _, _, (n_mu, n_var) = moe_lm.logits(conf, SEED, seqs,
                                        [np.arange(24)] * 3, moe_input=True)
    return conf, n_mu, n_var


def _faults():
    """Faults of the program's routed path, each a (module, attribute,
    wrapper of the attribute) to patch in."""
    from repro.core import dispatch
    from repro.core.gaussian import VAR, GaussianTensor
    from repro.models import lm
    from repro.nn import moe

    def zero_var(old):
        def f(*a, **k):
            out = old(*a, **k)
            return GaussianTensor(out.mean, jnp.zeros_like(out.var), VAR)
        return f

    def zero_out(old):
        def f(*a, **k):
            out = old(*a, **k)
            return GaussianTensor(out.mean * 0, out.var * 0, VAR)
        return f

    def options(**kw):
        return lambda old: lambda cfg: {**old(cfg), **{
            k: v(cfg) for k, v in kw.items()}}

    return {
        "routed_zeroed": (moe, "_grouped_dense", zero_out),
        "gates_renormalised": (lm, "moe_options",
                               options(norm_topk_prob=lambda c: True)),
        "kernel_var_zeroed": (dispatch, "pfp_dense_grouped", zero_var),
        "expert_offset_wrong": (lm, "moe_options", options(
            expert_offset=lambda c: c.expert_offset + c.num_experts)),
        "last_tile_dropped": (dispatch, "pfp_dense_grouped",
                              lambda old: lambda x, w, te, nt, **k: old(
                                  x, w, te, nt - 1, **k)),
    }


def test_routed_check_passes_the_program(moe_inputs):
    conf, n_mu, n_var = moe_inputs
    got = moe_lm.routed_gaps(conf, SEED, n_mu, n_var)
    assert got["routed_gap"] <= SERVE_LIMITS["routed_gap"]
    assert got["routed_var_gap"] <= SERVE_LIMITS["routed_var_gap"]


@pytest.mark.parametrize("fault", ["routed_zeroed", "gates_renormalised",
                                   "kernel_var_zeroed",
                                   "expert_offset_wrong",
                                   "last_tile_dropped"])
def test_routed_check_catches_faults(fault, moe_inputs, monkeypatch):
    """A fault of the routed path that the served tokens and MI hardly
    show fails the routed numbers' limits."""
    conf, n_mu, n_var = moe_inputs
    mod, attr, wrap = _faults()[fault]
    monkeypatch.setattr(mod, attr, wrap(getattr(mod, attr)))
    got = moe_lm.routed_gaps(conf, SEED, n_mu, n_var)
    assert any(got[k] > SERVE_LIMITS[k] for k in got), got


def _synthetic_trace():
    """One decode program (10 ms) with two expert kernel calls (1 ms and
    3 ms), a small op of the kernel's wrapper, an MoE-scoped gather (1 ms)
    and an attention kernel (4 ms); a prefill program (10 ms) with 2 ms of
    MoE-scoped ops; a kernel call in another program, which no reader
    counts; and the system's decode counters after each of two steps."""
    det = "jit(_decode_step_paged)/while/body/pfp.moe.{}/x"
    ops = [["%pfp_dense_grouped_pallas.3 = (f32[336,1408]) custom-call()",
            1 * MS, 1 * MS, det.format("experts")],
           ["%pfp_dense_grouped_pallas.4 = (f32[336,2048]) custom-call()",
            2 * MS, 3 * MS, det.format("experts")],
           ["%convert.2 = s32[21] convert()", 5 * MS, MS // 1000,
            det.format("experts") + "/jit(pfp_dense_grouped_pallas)/c"],
           ["%gather.1 = bf16[336,2048] gather()", 6 * MS, 1 * MS,
            det.format("dispatch")],
           ["%pfp_attention_paged_pallas.9 = () custom-call()", 7 * MS,
            4 * MS, "jit(_decode_step_paged)/while/body/attn"],
           ["%fusion.5 = bf16[4096,2048] fusion()", 12 * MS, 2 * MS,
            "jit(_batch_chunk_step)/while/body/pfp.moe.combine/y"],
           ["%pfp_dense_grouped_pallas.7 = () custom-call()", 30 * MS,
            1 * MS, "jit(other)/pfp.moe.experts"],
           # the TPU trace's form: no scope in the detail, the held
           # experts' weight slice in the name
           ["%dynamic-slice_bitcast_fusion.16 = f32[16,1408,2048] fusion("
            "f32[4,16,1408,2048] %w)", 15 * MS, MS // 2, ""],
           ["%while.64 = (f32[4,16,2048,1408]) while()", 1 * MS, 9 * MS,
            ""]]
    mods = [["jit__decode_step_paged(1)", 1 * MS, 10 * MS],
            ["jit__batch_chunk_step(2)", 11 * MS, 10 * MS],
            ["jit_other(3)", 30 * MS, 1 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.OPS_LINE, "events": ops},
            {"name": tr.MODULES_LINE, "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 0, 40 * MS],
            [moe_serving.decode_span(12, 5), 11 * MS, 0],
            [moe_serving.decode_span(24, 9), 21 * MS, 0]]}]}]}


def test_moe_readers_by_hand():
    conf = spec.config("deepseek-moe-16b")
    peak = pfp.peaks("TPU v5 lite")
    ctx = run.MetricContext(tr.Trace(_synthetic_trace()), peak, conf,
                            spec.workload(CELL))
    share = spec.load_module("metrics", "moe_expert_share").read(ctx)
    # the kernel's 1 + 3 ms and its wrapper's 0.001 ms in decode, and the
    # slice of one layer's held experts (0.5 ms), of 20 ms; the dispatch
    # and combine ops are the layer's but not the experts'
    assert share == pytest.approx(100 * 4.501 / 20)
    roof = spec.load_module("metrics", "expert_gemm_roofline").read(ctx)
    # the window's last counters: 24 rows reaching 9 held experts
    least = moe_costs.least_time_s(conf, peak, 24, 9)
    assert roof == pytest.approx(100 * least / 0.004)
    empty = run.MetricContext(tr.Trace({"planes": []}), peak, conf, {})
    assert spec.load_module("metrics",
                            "moe_expert_share").read(empty) is None
    assert spec.load_module("metrics",
                            "expert_gemm_roofline").read(empty) is None


def test_expert_costs_by_hand():
    conf = spec.config("deepseek-moe-16b")
    d, f = 2048, 1408
    assert moe_costs.projections(conf) == [(d, f), (d, f), (f, d)]
    # 13 reached experts' fp32 mu and srm: 13 x 2048 x 1408 x 2 x 4 B,
    # then 24 rows of bf16 mean and srm in, fp32 mean and var out
    b = moe_costs.grouped_bytes(24, d, f, 13, w_bytes=4, act_bytes=2,
                                out_bytes=4)
    assert b == 13 * d * f * 8 + 24 * d * 4 + 24 * f * 8
    assert moe_costs.grouped_flops(24, d, f) == 6 * 24 * d * f
    peak = pfp.peaks("TPU v5 lite")
    # bound by bytes: the three projections read the reached weights
    t = moe_costs.least_time_s(conf, peak, 24, 13)
    assert t == pytest.approx((2 * b + moe_costs.grouped_bytes(
        24, f, d, 13, w_bytes=4, act_bytes=2, out_bytes=4)) / 819e9)
    # a prefill round's 4,096 rows on one expert are bound by operations
    t = moe_costs.least_time_s(conf, peak, 4096, 1)
    assert t == pytest.approx(3 * 6 * 4096 * d * f / 197e12)
