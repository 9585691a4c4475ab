"""Observability layer (ISSUE-8 acceptance surface).

Covers: the unified metrics registry (percentile edge cases, Prometheus
histogram bucket-boundary semantics, deterministic snapshots, text-
export round-trip through our own parser), deterministic request tracing
(byte-identical traces across identical fleet runs), the engine's host
spans on the profiler's clock (read back from a CPU profiler session's
``.xplane.pb``: the span tree, args equal to what the engine did, the
programs' names the benchmark matches, outputs untouched by a recording
session), uncertainty telemetry (band occupancy, OOD alarms, escalation
outcomes, ECE), the export schemas, and two regressions on the
re-plumbed engine/fleet metrics: the summary() key set is stable, and a
fleet's pooled throughput is exactly the sum of its per-replica
throughputs (shared Stopwatch).
"""
import dataclasses
import glob
import json
import math
import re

import jax
import numpy as np
import pytest

from repro.bayes.convert import svi_to_pfp
from repro.configs import reduced_config
from repro.models import lm
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                Stopwatch, parse_prometheus, percentile)
from repro.obs.runmeta import run_metadata
from repro.obs.schema import (METRICS_SCHEMA, TRACE_EVENT_SCHEMA,
                              validate, validate_metrics_payload)
from repro.obs.trace import EVENTS, SPANS, Tracer, span
from repro.obs.uncertainty import UncertaintyTelemetry
from repro.serving.batcher import Request
from repro.serving.engine import (Engine, EngineConfig, RequestScheduler,
                                  RouterConfig, SchedulerConfig,
                                  UncertaintyRouter, run_load)
from repro.serving.fleet import Fleet, FleetConfig

MAX_LEN = 24


@pytest.fixture(scope="module")
def lm_setup():
    cfg = dataclasses.replace(reduced_config("granite-8b"), sigma_init=1e-3)
    params = svi_to_pfp(lm.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


def _engine(cfg, params, *, tracer=None, page_size=None, prefix_sharing=False,
            mi_continue=1e9, mi_abstain=2e9, speculate_k=0, slots=3,
            prefill_budget=6):
    router = UncertaintyRouter(cfg, RouterConfig(mi_continue=mi_continue,
                                                 mi_abstain=mi_abstain))
    return Engine(cfg, params,
                  EngineConfig(slots=slots, max_len=MAX_LEN,
                               num_uncertainty_samples=8, seed=0,
                               page_size=page_size,
                               prefix_sharing=prefix_sharing,
                               speculate_k=speculate_k),
                  router=router,
                  scheduler=RequestScheduler(
                      SchedulerConfig(prefill_chunk=3,
                                      prefill_budget=prefill_budget),
                      max_len=MAX_LEN),
                  tracer=tracer)


def _trace_reqs(n=4, prefix_len=6, tail_len=3, max_new=3):
    system = np.arange(1, prefix_len + 1, dtype=np.int32)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [system, np.full(tail_len, 50 + i, np.int32)]),
                    max_new_tokens=max_new, arrival=float(2 * i))
            for i in range(n)]


# ---------------------------------------------------------------------------
# percentile: nearest-rank edge cases
# ---------------------------------------------------------------------------
class TestPercentile:
    def test_empty(self):
        assert percentile([], 50) == 0.0

    def test_single(self):
        for q in (0, 1, 50, 99, 100):
            assert percentile([7.0], q) == 7.0

    def test_q0_is_min_q100_is_max(self):
        xs = [5.0, 1.0, 3.0, 2.0, 4.0]
        assert percentile(xs, 0) == 1.0
        assert percentile(xs, 100) == 5.0

    def test_nearest_rank(self):
        xs = list(range(1, 11))  # 1..10
        assert percentile(xs, 50) == 5.0   # ceil(0.5*10) = rank 5
        assert percentile(xs, 51) == 6.0
        assert percentile(xs, 99) == 10.0

    def test_unsorted_input(self):
        assert percentile([9.0, 1.0, 5.0], 50) == 5.0


# ---------------------------------------------------------------------------
# Stopwatch: shared-clock semantics
# ---------------------------------------------------------------------------
class TestStopwatch:
    def test_unstarted_reads_zero(self):
        assert Stopwatch().elapsed() == 0.0

    def test_first_start_wins(self):
        sw = Stopwatch()
        sw.start()
        t0 = sw._t0
        sw.start()  # later starts must not re-anchor the run
        assert sw._t0 == t0

    def test_frozen_pins_one_reading(self):
        sw = Stopwatch()
        sw.start()
        with sw.frozen():
            a = sw.elapsed()
            b = sw.elapsed()
            assert a == b
            with sw.frozen():  # re-entrant: inner keeps the outer pin
                assert sw.elapsed() == a
        assert sw._pinned is None


# ---------------------------------------------------------------------------
# metric children + histogram bucket-boundary semantics
# ---------------------------------------------------------------------------
class TestPrimitives:
    def test_counter_monotone(self):
        c = Counter()
        c.inc()
        c.inc(3)
        assert c.value == 4
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_peak(self):
        g = Gauge()
        g.set(5)
        g.set(2)
        assert g.value == 2 and g.peak == 5

    def test_histogram_bounds_must_increase(self):
        with pytest.raises(ValueError):
            Histogram([1.0, 1.0])
        with pytest.raises(ValueError):
            Histogram([2.0, 1.0])

    def test_bucket_boundary_is_inclusive_upper(self):
        """Prometheus semantics: a sample exactly on a bound lands in
        THAT bucket (le is <=), values above every bound overflow."""
        h = Histogram([1.0, 2.0])
        h.observe(1.0)   # == first bound -> first bucket
        h.observe(1.5)
        h.observe(2.0)   # == last bound -> second bucket
        h.observe(2.5)   # -> +Inf overflow
        assert h.counts == [1, 2]
        assert h.overflow == 1
        cum = h.cumulative()
        assert cum == [(1.0, 1), (2.0, 3), (math.inf, 4)]

    def test_histogram_quantile(self):
        h = Histogram([1.0, 2.0, 4.0])
        assert h.quantile(50) == 0.0  # empty
        for v in (0.5, 0.6, 1.5, 3.0):
            h.observe(v)
        assert h.quantile(50) == 1.0   # rank 2 of 4 -> first bucket's bound
        assert h.quantile(100) == 4.0
        h.observe(100.0)               # overflow clamps to last finite bound
        assert h.quantile(100) == 4.0


# ---------------------------------------------------------------------------
# registry: families, labels, snapshots, Prometheus round-trip
# ---------------------------------------------------------------------------
def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("served", "tokens served").inc(7)
    reg.gauge("occupancy", "slots").set(3)
    bands = reg.counter("band", "router bands", labelnames=("band",))
    bands.labels(band="continue").inc(5)
    bands.labels(band="abstain").inc(1)
    reg.histogram("mi", (0.1, 1.0), "mi stream").observe(0.05)
    reg.get("mi").observe(2.0)
    return reg


class TestRegistry:
    def test_factory_idempotent_kind_checked(self):
        reg = MetricsRegistry()
        a = reg.counter("x", "help")
        assert reg.counter("x") is a
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_label_set_enforced(self):
        reg = MetricsRegistry()
        fam = reg.counter("y", labelnames=("band",))
        with pytest.raises(ValueError):
            fam.labels(wrong="continue")
        with pytest.raises(ValueError):
            fam.inc()  # labeled family has no solo child

    def test_snapshot_deterministic(self):
        a, b = _populated_registry(), _populated_registry()
        sa, sb = a.snapshot(), b.snapshot()
        assert json.dumps(sa, sort_keys=True) == json.dumps(sb,
                                                            sort_keys=True)
        assert sa["band"]["values"][0]["labels"] == {"band": "abstain"}

    def test_prometheus_round_trip(self):
        reg = _populated_registry()
        text = reg.to_prometheus(extra_labels={"lane": "r0"})
        parsed = parse_prometheus(text)
        assert parsed["repro_served"]['lane="r0"'] == 7.0
        assert parsed["repro_occupancy"]['lane="r0"'] == 3.0
        assert parsed["repro_band"]['band="continue",lane="r0"'] == 5.0
        # histogram: cumulative le counts + sum/count samples
        assert parsed["repro_mi_bucket"]['lane="r0",le="0.1"'] == 1.0
        assert parsed["repro_mi_bucket"]['lane="r0",le="+Inf"'] == 2.0
        assert parsed["repro_mi_count"]['lane="r0"'] == 2.0
        assert parsed["repro_mi_sum"]['lane="r0"'] == pytest.approx(2.05)

    def test_parse_rejects_malformed(self):
        with pytest.raises(ValueError):
            parse_prometheus("repro_x{lane=\"r0\" 3\n")  # unterminated
        with pytest.raises(ValueError):
            parse_prometheus("repro_x notanumber\n")


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
class TestTracer:
    def test_unknown_event_rejected(self):
        with pytest.raises(ValueError):
            Tracer().emit("engine", 0, "nope")

    def test_jsonl_deterministic_and_schema_valid(self):
        def run():
            t = Tracer()
            lane = t.bind("engine")
            lane.emit(0, "submit", uid=1, accepted=True)
            lane.emit(0, "admit", uid=1, slot=0)
            lane.emit(3, "finish", uid=1, reason="length", tokens=3)
            return t
        a, b = run(), run()
        assert a.to_jsonl() == b.to_jsonl()
        for line in a.to_jsonl().splitlines():
            assert validate(json.loads(line), TRACE_EVENT_SCHEMA) == []


# ---------------------------------------------------------------------------
# host spans on the profiler's clock
# ---------------------------------------------------------------------------
def _profiled(tmp, fn):
    """fn() inside a CPU profiler session; returns its result and the
    session's ``pfp.*`` host spans as dicts (name without the prefix,
    t0, t1 in ns on the trace's clock, args), ordered by start."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    spans = [{"name": e.name[len("pfp."):], "t0": e.start_ns,
              "t1": e.start_ns + e.duration_ns, "args": dict(e.stats)}
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("pfp.")]
    return out, sorted(spans, key=lambda sp: (sp["t0"], -sp["t1"]))


def _named(spans, name):
    return [sp for sp in spans if sp["name"] == name]


def _within(inner, outer):
    return outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]


def _parent(sp, spans, name):
    """The one ``name`` span holding ``sp``."""
    holders = [o for o in _named(spans, name) if o is not sp
               and _within(sp, o)]
    assert len(holders) == 1, (sp, holders)
    return holders[0]


def _outputs(eng):
    return {r.uid: (list(r.generated), [float(m) for m in r.mi_trace])
            for r in eng.finished}


def _serve(cfg, params, reqs, **kw):
    eng = _engine(cfg, params, **kw)
    return eng, run_load(eng, reqs)


def _itl_tokens(eng):
    """Inter-token gaps the run closed: every served token but each
    request's first."""
    return sum(max(len(r.generated) - 1, 0) for r in eng.finished)


@pytest.fixture(scope="module")
def paged_spans(lm_setup, tmp_path_factory):
    """A tiny paged engine run under a profiler session: four requests
    due at once on three slots, after an empty prompt that is refused."""
    cfg, params = lm_setup
    reqs = _trace_reqs()
    for r in reqs:
        r.arrival = 0.0

    def run():
        eng = _engine(cfg, params, page_size=4)
        refused = eng.submit(Request(uid=99, prompt=np.zeros(0, np.int32),
                                     max_new_tokens=2))
        return eng, refused, run_load(eng, reqs)

    (eng, refused, s), spans = _profiled(tmp_path_factory.mktemp("paged"),
                                          run)
    assert not refused
    return eng, s, spans


def _check_round_widths(rounds, s, width_of):
    """Each prefill round's span: ``width`` the rows it ran for its
    ``slots`` (``width_of``), its real ``rows`` within them; the engine's
    summary sums the same rows and widths."""
    assert rounds
    for r in rounds:
        a = r["args"]
        assert a["width"] == width_of[a["slots"]], a
        assert 0 < a["rows"] <= a["width"]
    assert sum(r["args"]["rows"] for r in rounds) == s["prefill_tokens"]
    assert s["prefill_rows_run"] == sum(r["args"]["width"] for r in rounds)
    assert s["prefill_row_share"] == \
        s["prefill_tokens"] / s["prefill_rows_run"]
    assert sum(s["prefill_round_widths"].values()) == len(rounds)


class TestSpans:
    def test_unknown_span_refused(self):
        with pytest.raises(ValueError):
            span("nope")
        assert SPANS == set(EVENTS) | {"step", "sync"}
        with span("step", step=0) as sp:  # no session: a no-op span
            sp.set_metadata(rounds=0, itl_tokens=0)

    def test_step_spans_nest_route_and_sync(self, paged_spans):
        eng, s, spans = paged_spans
        steps = _named(spans, "step")
        assert [sp["args"]["step"] for sp in steps] == list(range(s["steps"]))
        routes = _named(spans, "route")
        assert routes and len(_named(spans, "sync")) == len(routes)
        for sync in _named(spans, "sync"):
            _parent(_parent(sync, spans, "route"), spans, "step")
        for name in ("admit", "prefill_round", "decode_step"):
            for sp in _named(spans, name):
                _parent(sp, spans, "step")
        assert len(_named(spans, "decode_step")) == s["decode_passes"]

    def test_prefill_round_rows_and_width(self, paged_spans):
        eng, s, spans = paged_spans
        rounds = _named(spans, "prefill_round")
        # a round computes its planned slots, padded to a power of two at
        # most the 3 slots, at the chunk width of 3, and carries at most
        # one chunk per slot
        _check_round_widths(rounds, s, {1: 3, 2: 6, 3: 9})
        assert {r["args"]["slots"] for r in rounds} >= {1, 2}
        for step in _named(spans, "step"):
            inside = [r for r in rounds if _within(r, step)]
            assert step["args"]["rounds"] == len(inside)
        assert any(st["args"]["rounds"] == 0
                   for st in _named(spans, "step"))

    def test_three_slot_round_runs_four_rows(self, lm_setup, tmp_path):
        """Three prompts due at once on five slots, with a budget of three
        chunks: the first round carries three slots and runs four rows."""
        cfg, params = lm_setup
        reqs = _trace_reqs(n=3)
        for r in reqs:
            r.arrival = 0.0
        (eng, s), spans = _profiled(tmp_path, lambda: _serve(
            cfg, params, reqs, page_size=4, slots=5, prefill_budget=9))
        rounds = _named(spans, "prefill_round")
        _check_round_widths(rounds, s, {1: 3, 2: 6, 3: 12})
        assert rounds[0]["args"]["slots"] == 3
        assert s["prefill_round_widths"][12] >= 1

    def test_itl_tokens_count_inter_token_gaps(self, paged_spans):
        eng, s, spans = paged_spans
        steps = _named(spans, "step")
        assert sum(st["args"]["itl_tokens"] for st in steps) == \
            _itl_tokens(eng) == s["tokens_generated"] - 4
        # a step that prefilled and a step that only decoded both closed
        # gaps: the share the benchmark reads has both sides
        assert any(st["args"]["rounds"] and st["args"]["itl_tokens"]
                   for st in steps)
        assert any(not st["args"]["rounds"] and st["args"]["itl_tokens"]
                   for st in steps)

    def test_submit_and_admit_spans_per_request(self, paged_spans):
        eng, s, spans = paged_spans
        subs = {sp["args"]["uid"]: sp for sp in _named(spans, "submit")}
        admits = {sp["args"]["uid"]: sp for sp in _named(spans, "admit")}
        assert subs[99]["args"]["accepted"] == 0 and 99 not in admits
        assert set(admits) == {0, 1, 2, 3}
        for uid, adm in admits.items():
            assert subs[uid]["args"]["accepted"] == 1
            assert subs[uid]["t1"] <= adm["t0"]
            assert 0 <= adm["args"]["slot"] < 3
        # four requests due at once on three slots: request 3 waits in
        # the queue until a slot frees, steps after the others' admission
        slots = [admits[u]["args"]["slot"] for u in range(4)]
        assert len(set(slots[:3])) == 3 and slots[3] in slots[:3]
        first = _parent(admits[0], spans, "step")["args"]["step"]
        assert all(_parent(admits[u], spans, "step")["args"]["step"]
                   == first for u in range(3))
        assert _parent(admits[3], spans, "step")["args"]["step"] > first

    def test_contiguous_prefill_rounds(self, lm_setup, tmp_path):
        cfg, params = lm_setup
        (eng, s), spans = _profiled(
            tmp_path, lambda: _serve(cfg, params, _trace_reqs()))
        rounds = _named(spans, "prefill_round")
        # one single-slot pass a chunk, at the static chunk width
        assert rounds and all(r["args"]["width"] == 3 for r in rounds)
        assert sum(r["args"]["rows"] for r in rounds) == s["prefill_tokens"]
        assert sum(st["args"]["rounds"] for st in _named(spans, "step")) \
            == len(rounds)
        assert sum(st["args"]["itl_tokens"]
                   for st in _named(spans, "step")) == _itl_tokens(eng)
        for sync in _named(spans, "sync"):
            _parent(sync, spans, "route")

    def test_escalate_spans_inside_route(self, lm_setup, tmp_path):
        cfg, params = lm_setup
        (eng, s), spans = _profiled(tmp_path, lambda: _serve(
            cfg, params, _trace_reqs(n=2), page_size=4, mi_continue=-1.0))
        esc = _named(spans, "escalate")
        assert esc and s["escalations"] == s["tokens_generated"]
        assert sum(e["args"]["slots"] for e in esc) == s["escalations"]
        for e in esc:
            _parent(e, spans, "route")

    def test_speculative_route_and_defrag(self, lm_setup, tmp_path):
        cfg, params = lm_setup

        def run():
            eng, s = _serve(cfg, params, _trace_reqs(n=2), page_size=4,
                            speculate_k=2)
            eng.defrag()
            return eng, s

        (eng, s), spans = _profiled(tmp_path, run)
        assert s["verify_passes"] > 0
        assert len(_named(spans, "decode_step")) >= s["verify_passes"]
        for sync in _named(spans, "sync"):
            _parent(_parent(sync, spans, "route"), spans, "step")
        assert sum(st["args"]["itl_tokens"]
                   for st in _named(spans, "step")) == _itl_tokens(eng)
        defrag, = _named(spans, "defrag")
        assert all(not _within(defrag, st) for st in _named(spans, "step"))

    def test_outputs_identical_under_a_recording_session(self, lm_setup,
                                                         tmp_path):
        cfg, params = lm_setup
        eng_off, _ = _serve(cfg, params, _trace_reqs(), page_size=4)
        (eng_on, _), spans = _profiled(tmp_path, lambda: _serve(
            cfg, params, _trace_reqs(), page_size=4))
        assert spans and _outputs(eng_on) == _outputs(eng_off)

    def test_step_programs_match_the_benchmark_pattern(self, lm_setup):
        """``bench/metrics/step_mfu.py`` finds the prefill and decode
        programs in the device trace by name: the engine's programs must
        keep names it matches, and the sampling pass a name it does not."""
        from bench import spec

        programs = re.compile(spec.load_module("metrics",
                                               "step_mfu").PROGRAMS)
        cfg, params = lm_setup
        eng = _engine(cfg, params, page_size=4)
        calls = {}
        for attr in ("_batch_chunk_fn", "_decode_fn", "_unc"):
            fn = getattr(eng, attr)

            def record(*args, _fn=fn, _attr=attr):
                calls[_attr] = (_fn, args)
                return _fn(*args)

            setattr(eng, attr, record)
        run_load(eng, _trace_reqs(n=1))
        names = {attr: re.search(r"module @(\S+)",
                                 fn.lower(*args).as_text()).group(1)
                 for attr, (fn, args) in calls.items()}
        assert programs.search(names["_batch_chunk_fn"]), names
        assert programs.search(names["_decode_fn"]), names
        assert not programs.search(names["_unc"]), names


# ---------------------------------------------------------------------------
# uncertainty telemetry
# ---------------------------------------------------------------------------
class TestUncertainty:
    def test_bands_and_ood(self):
        u = UncertaintyTelemetry(MetricsRegistry(), ood_mi=2.0)
        for mi, band in ((0.1, "continue"), (1.0, "escalate"),
                         (2.0, "abstain"), (5.0, "abstain")):
            u.on_decision(mi, band)
        s = u.summary()
        assert s["band_continue"] == 1
        assert s["band_escalate"] == 1
        assert s["band_abstain"] == 2
        assert s["ood_alarms"] == 2  # threshold is inclusive
        assert s["mi_mean"] == pytest.approx((0.1 + 1.0 + 2.0 + 5.0) / 4)

    def test_escalation_outcomes_and_agreement(self):
        u = UncertaintyTelemetry(MetricsRegistry())
        u.on_escalation_outcome(0.5, 7, 0.2, 7, "continue")   # agreed
        u.on_escalation_outcome(0.5, 7, 3.0, 9, "abstain")    # disagreed
        s = u.summary()
        assert s["escalate_continue"] == 1
        assert s["escalate_abstain"] == 1
        assert s["svi_agreement_rate"] == 0.5

    def test_ece_calibrated_vs_miscalibrated(self):
        cal = UncertaintyTelemetry(MetricsRegistry())
        # confident (MI ~ 0 -> confidence ~ 1) and always right: ECE ~ 0
        for _ in range(50):
            cal.on_escalation_outcome(1e-4, 3, 0.0, 3, "continue")
        assert cal.ece() == pytest.approx(0.0, abs=1e-3)
        bad = UncertaintyTelemetry(MetricsRegistry())
        # same confidence but always WRONG: ECE ~ 1
        for _ in range(50):
            bad.on_escalation_outcome(1e-4, 3, 0.0, 4, "abstain")
        assert bad.ece() == pytest.approx(1.0, abs=1e-3)
        assert bad.ece() > cal.ece()

    def test_no_audits_is_zero(self):
        u = UncertaintyTelemetry(MetricsRegistry())
        assert u.ece() == 0.0
        assert u.summary()["svi_agreement_rate"] == 0.0


# ---------------------------------------------------------------------------
# schemas + run metadata
# ---------------------------------------------------------------------------
class TestSchemas:
    def test_trace_event_schema(self):
        ok = {"step": 0, "seq": 1, "lane": "engine", "event": "submit",
              "uid": 3, "accepted": True}
        assert validate(ok, TRACE_EVENT_SCHEMA) == []
        assert validate({"step": 0, "seq": 0, "lane": "engine",
                         "event": "not_an_event"}, TRACE_EVENT_SCHEMA)
        assert validate({"seq": 0, "lane": "engine", "event": "submit"},
                        TRACE_EVENT_SCHEMA)  # missing step
        assert validate({"step": -1, "seq": 0, "lane": "engine",
                         "event": "submit"}, TRACE_EVENT_SCHEMA)

    def test_every_event_name_in_schema_enum(self):
        assert list(EVENTS) == TRACE_EVENT_SCHEMA["properties"]["event"][
            "enum"]

    def test_metrics_payload_schema(self):
        payload = {"meta": run_metadata(), "summary": {"steps": 1},
                   "registries": {"engine": _populated_registry().snapshot()}}
        assert validate_metrics_payload(payload) == []
        assert validate_metrics_payload({"summary": {}, "registries": {},
                                         "meta": {}})  # meta keys missing
        bad = {"meta": run_metadata(), "summary": {},
               "registries": {"engine": {"fam": {"type": "counter"}}}}
        assert validate_metrics_payload(bad)  # family missing help/values

    def test_run_metadata_keys(self):
        meta = run_metadata()
        assert set(meta) >= set(METRICS_SCHEMA["properties"]["meta"]
                                ["required"])
        assert isinstance(meta["interpret_mode"], bool)


# ---------------------------------------------------------------------------
# engine integration: key stability, tracing parity, zero-cost-off
# ---------------------------------------------------------------------------
# The pre-registry EngineMetrics.summary() key set: loadgen, the serving
# benches and the serve CLI all read these — the registry re-plumb must
# never drop one.
ENGINE_SUMMARY_KEYS = {
    "submitted", "rejected", "expired", "admitted", "finished", "completed",
    "abstained", "abstain_rate", "escalations", "escalation_rate",
    "tokens_generated", "prefill_tokens", "steps", "elapsed_s",
    "throughput_tok_s", "p50_latency_steps", "p99_latency_steps",
    "p50_latency_s", "p99_latency_s", "peak_occupancy", "mean_occupancy",
    "final_occupancy", "preemptions", "requeue_overflow", "defrags",
    "peak_page_occupancy", "mean_page_occupancy", "mean_page_fragmentation",
    "final_live_pages", "prefix_hits", "prefix_misses", "prefix_hit_rate",
    "prefix_shared_pages", "prefill_tokens_saved", "prefill_frac_saved",
    "cow_copies", "mean_shared_pages", "final_prefix_held_pages",
    "spec_rounds", "draft_tokens", "accepted_draft_tokens",
    "draft_acceptance_rate", "accepted_tokens_per_verify", "verify_passes",
    "decode_passes", "draft_passes", "svi_passes", "svi_passes_per_step",
    "max_svi_passes_per_step", "mean_escalation_batch",
    "max_escalation_batch", "pfp_passes_per_token",
}
UNCERTAINTY_KEYS = {
    "band_continue", "band_escalate", "band_abstain", "ood_alarms",
    "escalate_continue", "escalate_abstain", "svi_agreement_rate",
    "mi_ece", "mi_mean", "mi_p50", "mi_p99",
}
FLEET_SUMMARY_KEYS = {
    "replicas", "submitted", "rejected", "steps", "route_prefix_hits",
    "route_fallbacks", "route_hit_rate", "route_tokens_matched",
    "per_replica_mean_occupancy", "per_replica_peak_occupancy",
    "final_occupancy", "per_replica_tokens",
    "per_replica_throughput_tok_s", "per_replica_p50_latency_steps",
    "per_replica_p99_latency_steps", "elapsed_s", "throughput_tok_s",
    "tokens_generated", "prefix_hit_rate",
}


class TestEngineIntegration:
    def test_engine_summary_keys_stable(self, lm_setup):
        cfg, params = lm_setup
        eng = _engine(cfg, params)
        s = run_load(eng, _trace_reqs())
        missing = (ENGINE_SUMMARY_KEYS | UNCERTAINTY_KEYS) - set(s)
        assert not missing, f"summary() dropped keys: {sorted(missing)}"
        # every routed token lands in exactly one band
        assert (s["band_continue"] + s["band_escalate"] + s["band_abstain"]
                == s["tokens_generated"])

    def test_legacy_counter_attributes_still_read(self, lm_setup):
        cfg, params = lm_setup
        eng = _engine(cfg, params)
        run_load(eng, _trace_reqs(n=2))
        assert eng.metrics.tokens_generated == 6
        assert eng.metrics.submitted == 2
        with pytest.raises(AttributeError):
            eng.metrics.not_a_counter

    def test_tracing_off_by_default_and_parity_when_on(self, lm_setup):
        """Disabled tracing is the None branch at every emit site; an
        attached tracer must observe, never perturb — same tokens, same
        MI, same summary counters."""
        cfg, params = lm_setup

        def run(tracer):
            eng = _engine(cfg, params, tracer=tracer)
            s = run_load(eng, _trace_reqs())
            outs = {r.uid: (list(r.generated),
                            [float(m) for m in r.mi_trace])
                    for r in eng.finished}
            return eng, s, outs

        eng_off, s_off, out_off = run(None)
        assert eng_off._tracer is None
        tracer = Tracer()
        eng_on, s_on, out_on = run(tracer)
        assert out_on == out_off
        drop = ("elapsed_s", "throughput_tok_s", "p50_latency_s",
                "p99_latency_s")  # wall-clock keys differ run to run
        assert {k: v for k, v in s_on.items() if k not in drop} \
            == {k: v for k, v in s_off.items() if k not in drop}
        events = {e["event"] for e in tracer.events}
        assert events >= {"submit", "admit", "prefill_round", "decode_step",
                          "route", "finish"}
        n_routed = sum(1 for e in tracer.events if e["event"] == "route")
        assert n_routed == s_on["tokens_generated"]

    def test_prometheus_export_from_live_engine(self, lm_setup):
        cfg, params = lm_setup
        eng = _engine(cfg, params)
        s = run_load(eng, _trace_reqs(n=2))
        parsed = parse_prometheus(
            eng.metrics.registry.to_prometheus(extra_labels={"lane": "e"}))
        assert parsed["repro_tokens_generated"]['lane="e"'] \
            == s["tokens_generated"]
        assert parsed["repro_mi_nats_count"]['lane="e"'] \
            == s["tokens_generated"]


class TestFleetIntegration:
    def test_fleet_summary_keys_and_pooled_throughput(self, lm_setup):
        cfg, params = lm_setup
        fleet = Fleet(cfg, params,
                      EngineConfig(slots=3, max_len=MAX_LEN,
                                   num_uncertainty_samples=8, seed=0,
                                   page_size=4, prefix_sharing=True),
                      FleetConfig(replicas=2),
                      router=UncertaintyRouter(
                          cfg, RouterConfig(mi_continue=1e9,
                                            mi_abstain=2e9)),
                      scheduler_config=SchedulerConfig(prefill_chunk=3,
                                                       prefill_budget=6))
        s = run_load(fleet, _trace_reqs(n=5))
        missing = FLEET_SUMMARY_KEYS - set(s)
        assert not missing, f"fleet summary dropped keys: {sorted(missing)}"
        # the shared frozen Stopwatch makes this an identity, not an
        # approximation bounded by start skew
        assert s["throughput_tok_s"] == pytest.approx(
            sum(s["per_replica_throughput_tok_s"]), rel=1e-12)
        assert (s["band_continue"] + s["band_escalate"] + s["band_abstain"]
                == s["tokens_generated"])

    def test_identical_fleet_runs_trace_byte_identical(self, lm_setup):
        cfg, params = lm_setup

        def run():
            tracer = Tracer()
            fleet = Fleet(cfg, params,
                          EngineConfig(slots=3, max_len=MAX_LEN,
                                       num_uncertainty_samples=8, seed=0,
                                       page_size=4, prefix_sharing=True),
                          FleetConfig(replicas=2, disaggregate=True),
                          router=UncertaintyRouter(
                              cfg, RouterConfig(mi_continue=1e9,
                                                mi_abstain=2e9)),
                          scheduler_config=SchedulerConfig(prefill_chunk=3,
                                                           prefill_budget=6),
                          tracer=tracer)
            run_load(fleet, _trace_reqs(n=4))
            return tracer.to_jsonl()

        a = run()
        assert a == run()
        recs = [json.loads(line) for line in a.splitlines()]
        # the common-prefix trace routes every sharer to r0, so r0's two
        # disaggregated lanes must appear; routing itself is on 'fleet'
        assert {r["lane"] for r in recs} >= {"fleet", "r0.prefill",
                                             "r0.decode"}
        assert sum(r["event"] == "route_replica" for r in recs) == 4
        assert sum(r["event"] == "handoff" for r in recs) == 4
        for rec in recs:
            assert validate(rec, TRACE_EVENT_SCHEMA) == []


# ---------------------------------------------------------------------------
# MoE routing counters: reduced on the device, read in the pass's sync
# ---------------------------------------------------------------------------
class TestMoeCounters:
    @pytest.fixture(scope="class")
    def moe_spans(self, tmp_path_factory):
        """A reduced deepseek-moe (8 held of 16 routed experts, top-6) on
        a paged engine under a profiler session."""
        cfg = dataclasses.replace(reduced_config("deepseek-moe-16b"),
                                  num_experts=8, routed_experts=16,
                                  expert_offset=8, top_k=6, sigma_init=1e-3)
        params = svi_to_pfp(lm.init_params(cfg, jax.random.PRNGKey(0)))
        return _profiled(tmp_path_factory.mktemp("moe"), lambda: _serve(
            cfg, params, _trace_reqs(), page_size=4))

    def test_registry_counts_dropless_routing(self, moe_spans):
        (eng, s), _ = moe_spans
        # every real row a pass computes routes to 6 of 16 experts, 8 of
        # them held here
        assert s["moe_assignments"] > 0 and s["moe_assignments"] % 6 == 0
        assert 0 < s["moe_held_assignments"] < s["moe_assignments"]
        assert s["moe_dropped_assignments"] == 0
        assert s["moe_padding_routed"] == 0
        assert s["moe_drop_rate"] == 0
        assert 0 < s["moe_max_expert_load"] <= s["moe_held_assignments"]

    def test_pass_spans_carry_the_counters(self, moe_spans):
        (eng, s), spans = moe_spans
        # A prefill round's counters stay on the device: the route's next
        # pfp.sync reads them (moe_prefill_*); a decode pass's come back
        # in one transfer after it (moe_* on pfp.decode_step). No prefill
        # round waits for the device.
        for sp in _named(spans, "prefill_round"):
            assert not any(k.startswith("moe_") for k in sp["args"])
        reads = [(sp["args"], "moe_prefill_") for sp in _named(spans, "sync")
                 if "moe_prefill_held" in sp["args"]]
        decodes = _named(spans, "decode_step")
        assert reads and decodes
        reads += [(sp["args"], "moe_") for sp in decodes]
        for a, p in reads:
            assert a[p + "dropped"] == 0 and a[p + "pad_routed"] == 0
            assert 0 <= a[p + "max_load"] <= a[p + "held"] <= \
                a[p + "assignments"]
            assert a[p + "touched"] <= a[p + "held"]
        assert sum(a[p + "held"] for a, p in reads) == \
            s["moe_held_assignments"]
        assert sum(a[p + "assignments"] for a, p in reads) == \
            s["moe_assignments"]
        assert max(a[p + "max_load"] for a, p in reads) == \
            s["moe_max_expert_load"]
        assert sum(a[p + "touched"] for a, p in reads) == \
            s["moe_experts_touched"]
        for key, arg in (("moe_decode_held_assignments", "moe_held"),
                         ("moe_decode_experts_touched", "moe_touched")):
            assert s[key] == sum(sp["args"][arg] for sp in decodes)
        # a decode pass routes one row per active slot: padding rows of
        # idle slots get nothing
        for sp in decodes:
            assert sp["args"]["moe_assignments"] == 6 * sp["args"]["active"]

    def test_dense_passes_carry_no_counters(self, paged_spans):
        _, s, spans = paged_spans
        for sp in (_named(spans, "prefill_round") + _named(spans, "sync")
                   + _named(spans, "decode_step")):
            assert not any(k.startswith("moe_") for k in sp["args"])
        assert s["moe_assignments"] == 0
