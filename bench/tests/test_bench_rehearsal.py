"""Each traffic kind's code driven against the system at a small size on
the CPU, through ``run_cell`` (everything a run does but the look for a
chip); the refusal off a TPU; and the timed path broken underneath, once
per fault a cell can have, with ``correct`` coming out false."""
from __future__ import annotations

import json
import time

import numpy as np

from bench import run, spec
from bench.tests import small

SERVE = "granite-8b.chat.steady"
PAPER = "paper-lenet5.b100"
SEED = 2**31 + 12345          # above 32 signed bits, as run seeds may be
# Limits at this test size on the CPU (the cells' own limits are set from
# chip readings at the timed size). Readings here, seed SEED, on an idle
# host: the program logit_gap 0, mi_gap 9.6e-4 nats; the fp8 control 0 and
# 3.6e-3 (at vocabulary 97 fp8 moves no argmax); a decode that leaves the
# KV pool unchanged 0.083 and 5.9e-3. On a host loaded by other jobs the
# program has read up to 0.46 and 2e-2 at this size, also with every
# request due at once, so not through the batches it forms: the engine's
# numerics on the CPU vary with load (PERF.md, section 7). LeNet-5 at
# batch 4: mean_err 1.1e-4, var_err 7.2e-5 (the kernels' own arithmetic;
# the program's XLA path reads 3e-6), entropy and MI gaps under 1e-6; the
# one-pass bf16 control 7.1e-3, 2.8e-2, 6.0e-5 and 1.3e-5.
SERVE_LIMITS = {"logit_gap": 0.05, "mi_gap": 2.5e-3}
PAPER_LIMITS = {"mean_err": 1e-3, "var_err": 1e-3, "entropy_gap": 1e-5,
                "mi_gap": 1e-5}


def _serve(trace=False, **kw):
    from repro.serving.engine import engine as engine_mod

    engine_mod.clear_shared_pass_cache()
    return run.run_cell(SERVE, SEED, 3.0, trace, t_start=time.perf_counter(),
                        conf_override=small.GRANITE,
                        cell_override={"traffic": small.SERVE_TRAFFIC},
                        peak_kind="TPU v5 lite", limits=SERVE_LIMITS, **kw)


def _paper(trace=False, **kw):
    return run.run_cell(PAPER, SEED, 2.0, trace, t_start=time.perf_counter(),
                        cell_override={"traffic": small.BATCH_TRAFFIC},
                        peak_kind="TPU v5 lite", limits=PAPER_LIMITS, **kw)


def _result_line_shape(r: dict, trace: bool):
    keys = list(r)
    assert keys[:3] == ["correct", "attempted", "failed"]
    assert keys[-1] == "checks"
    assert {"metrics", "device"} <= set(keys)
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(r["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(r["device"])
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(r)
    for name, c in r["checks"].items():
        assert set(c) == {"value", "limit"}


def test_main_refuses_without_a_tpu(capsys):
    import jax
    assert jax.devices()[0].platform != "tpu"
    assert run.main(["--workload", SERVE, "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_serve_rehearsal():
    r = _serve()
    _result_line_shape(r, False)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    man = spec.manifest()
    want = {m["name"] for m in spec.cell_metrics(man, SERVE, "end_to_end")}
    assert set(r["metrics"]) == want


def test_serve_traced_rehearsal():
    r = _serve(trace=True)
    _result_line_shape(r, True)
    assert r["correct"], r["checks"]
    # no device planes on the CPU: the device readers find nothing
    assert "step_mfu" not in r["metrics"]


def test_paper_rehearsal():
    r = _paper()
    _result_line_shape(r, False)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"batch_p95_ms", "setup_s"}
    r = _paper(trace=True)
    _result_line_shape(r, True)
    assert r["correct"], r["checks"]


# -- the timed path broken underneath ----------------------------------------
def test_fault_served_token_altered(monkeypatch):
    from repro.serving.engine.engine import Engine

    orig = Engine._route_current

    def altered(self, decode_slots):
        out = orig(self, decode_slots)
        v = self.cfg.vocab_size
        return {s: ((t + 1) % v, mi, d) for s, (t, mi, d) in out.items()}

    monkeypatch.setattr(Engine, "_route_current", altered)
    r = _serve()
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"][
        "limit"]


def test_fault_decode_returns_state_unchanged(monkeypatch):
    from repro.serving.engine.engine import Engine

    orig = Engine._decode_step_paged

    def unchanged(self, params, tokens, positions, cache_len, active,
                  states, page_table, lm_mean, lm_var):
        mean, var, _, aux = orig(self, params, tokens, positions, cache_len,
                                 active, states, page_table, lm_mean, lm_var)
        return mean, var, states, aux

    monkeypatch.setattr(Engine, "_decode_step_paged", unchanged)
    r = _serve()
    assert not r["correct"]


def test_fault_half_the_batch_left_out(monkeypatch):
    from bench.systems import paper_cnn

    orig = paper_cnn.System.call

    def half(self, images, idx):
        n = len(images) // 2
        out = orig(self, images[:n], idx)
        return tuple(np.concatenate([o, o]) for o in out)

    monkeypatch.setattr(paper_cnn.System, "call", half)
    r = _paper()
    assert not r["correct"]


def test_fault_answer_altered(monkeypatch):
    from bench.systems import paper_cnn

    orig = paper_cnn.System.call

    def altered(self, images, idx):
        mean, var, pred, ent, mi = orig(self, images, idx)
        mean = np.array(mean)
        mean[0, 0] += 0.01 * np.abs(mean).max()
        return mean, var, pred, ent, mi

    monkeypatch.setattr(paper_cnn.System, "call", altered)
    r = _paper()
    assert not r["correct"]


# -- the control: the reference in the precision below the configuration's
# (fp8 operands for bf16 compute, one bf16 pass for LeNet-5's fp32), in the
# program's place, comes out not correct under the harness's own check --
def test_serve_control_fails_a_limit():
    r = _serve(control=True)
    assert r["correct"], r["checks"]              # the program passes ...
    assert r["control_correct"] is False, r["control"]   # ... the control not
    assert any(r["control"][k] > v for k, v in SERVE_LIMITS.items())


def test_paper_control_fails_a_limit():
    r = _paper(control=True)
    assert r["correct"], r["checks"]
    assert r["control_correct"] is False, r["control"]
    assert any(r["control"][k] > v for k, v in PAPER_LIMITS.items())


def test_cell_limits_are_read_from_the_cell_file():
    # null limits (not yet set from chip readings) make a run incorrect
    r = run.run_cell(PAPER, SEED, 0.5, False, t_start=time.perf_counter(),
                     cell_override={"traffic": small.BATCH_TRAFFIC})
    lim = spec.workload(PAPER)["limits"]
    assert set(lim) <= set(r["checks"])
    assert r["correct"] == all(v is not None and r["checks"][k]["value"] <= v
                               for k, v in lim.items())
