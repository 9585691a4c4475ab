"""The trace reduction on a synthetic trace worked out by hand, and on a
small trace recorded on a TPU v5e (a slice of a traced serving window,
``data/serve_trace_small.json.gz``)."""
from __future__ import annotations

import pathlib

import pytest

from bench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _synthetic():
    ops = [["fusion.1", 1 * MS, 2 * MS, ""],
           ["custom-call.7", 2 * MS, 2 * MS, "_dense_kernel"],
           ["fusion.2", 7 * MS, 1 * MS, ""],
           ["custom-call.8", 12 * MS, 1 * MS, "_dense_kernel"]]
    mods = [["jit__decode_step_paged(3)", 1 * MS, 4 * MS],
            ["jit_other(4)", 7 * MS, 1 * MS],
            ["jit__decode_step_paged(3)", 12 * MS, 1 * MS]]
    host = [["bench.window", 0, 20 * MS],
            ["bench.step", 0, 6 * MS],
            ["bench.step", 6 * MS, 8 * MS],
            ["bench.wait", 14 * MS, 6 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.OPS_LINE, "events": ops},
            {"name": tr.MODULES_LINE, "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": host}]}]}


def test_busy_idle_and_programs_by_hand():
    t = tr.Trace(_synthetic())
    # busy: [1, 4] + [7, 8] + [12, 13] ms = 5 ms of a 20 ms window
    assert t.window_s == pytest.approx(0.020)
    assert t.busy_s() == pytest.approx(0.005)
    assert t.busy_within(0, 6 * MS) == pytest.approx(0.003)
    assert t.modules(r"decode_step") == (pytest.approx(0.005), 2)
    # the dense kernel events, by detail, inside the decode programs
    assert t.ops(r"_dense_kernel", module=r"decode_step") == (
        pytest.approx(0.003), 2)
    assert t.ops(r"fusion", module=r"decode_step") == (
        pytest.approx(0.002), 1)
    # by name without the instance number: 3 ms each
    assert sorted(t.top_ops(2)) == [["custom-call", pytest.approx(0.003)],
                                    ["fusion", pytest.approx(0.003)]]


def test_idle_gaps_are_named_by_the_host_span():
    t = tr.Trace(_synthetic())
    gaps = t.idle_gaps(3)
    # longest: 13..20 (wait), then 8..12 (step 2), then 4..7 (step 1/2)
    assert gaps[0] == ["bench.wait", pytest.approx(0.007)]
    assert gaps[1] == ["bench.step", pytest.approx(0.004)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)


def test_metric_readers_on_the_synthetic_trace():
    from bench import spec
    from bench.costs import pfp
    from bench.run import MetricContext

    conf = spec.config("granite-8b")
    ctx = MetricContext(tr.Trace(_synthetic()), pfp.peaks("TPU v5 lite"),
                        conf, spec.workload("granite-8b.chat.steady"),
                        served_flops=1e9)
    host = spec.load_module("metrics", "engine_host_ms_per_step").read(ctx)
    # step 1: 6 ms with 3 ms busy; step 2: 8 ms with 2 ms busy
    assert host == pytest.approx((3 + 6) / 2)
    idle = spec.load_module("metrics", "device_idle_share.serve").read(ctx)
    assert idle == pytest.approx(75.0)
    mfu = spec.load_module("metrics", "step_mfu").read(ctx)
    assert mfu == pytest.approx(100 * 1e9 / (0.005 * 197e12))


def test_a_trace_without_the_window_span_uses_the_device_extent():
    norm = _synthetic()
    norm["planes"][1]["lines"][0]["events"] = []
    t = tr.Trace(norm)
    assert t.window_s == pytest.approx(0.012)


def test_recorded_chip_trace():
    """Three engine steps of ``granite-8b.chat.steady`` traced on a TPU v5e
    (normalised with ``bench.trace.normalise`` and cut to the steps)."""
    from bench import spec
    from bench.costs import pfp
    from bench.run import MetricContext

    t = tr.Trace(tr.load(str(DATA / "serve_trace_small.json.gz")))
    assert list(t.devices) == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(2.9408, rel=1e-3)
    assert 0 < t.busy_s() <= t.window_s
    # the engine's programs by their jit names
    assert t.modules(r"batch_chunk_step")[1] == 3
    assert t.modules(r"decode_step")[1] >= 3
    assert t.ops(r"^%?pfp_dense_pallas\b")[1] > 0
    assert len(t.spans_named("bench.step")) == 3
    gaps = t.idle_gaps(3)
    assert [g[0] for g in gaps] == ["bench.step"] * 3
    assert all(0 < g[1] < 0.01 for g in gaps)
    ctx = MetricContext(t, pfp.peaks("TPU v5 lite"),
                        spec.config("granite-8b"),
                        spec.workload("granite-8b.chat.steady"), 1e12)
    idle = spec.load_module("metrics", "device_idle_share.serve").read(ctx)
    host = spec.load_module("metrics", "engine_host_ms_per_step").read(ctx)
    mfu = spec.load_module("metrics", "step_mfu").read(ctx)
    assert 0 <= idle < 1.0 and 0 < host < 10 and 0 < mfu < 100
