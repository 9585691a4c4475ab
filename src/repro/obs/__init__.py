"""Observability substrate for the PFP serving stack.

Three orthogonal pieces, all pure host-side bookkeeping (nothing here ever
touches the device path):

  * ``registry`` — Counter/Gauge/Histogram metric families with label
    sets, a shared ``Stopwatch`` wall clock, and Prometheus text export.
    ``EngineMetrics`` and ``FleetMetrics`` are backed by one
    ``MetricsRegistry`` each instead of hand-rolled attribute bags.
  * ``trace`` — the engine's trace layer, two views of one set of
    phases. A deterministic request log: every lifecycle event (submit,
    admit, prefill round, decode step, route, escalate, spec
    draft/verify, COW, preempt/requeue, handoff, finish) keyed on
    ``(engine_step, seq)``, so two identical runs produce byte-identical
    JSONL. And always-on host spans (``span``: ``pfp.step``,
    ``pfp.prefill_round``, ``pfp.route``, ...) written by
    ``jax.profiler.TraceAnnotation`` into a profiler session's
    ``.xplane.pb``, on the device trace's clock, with the step's counts
    (prompt rows, computed rows, inter-token tokens) as their args.
  * ``uncertainty`` — router-band occupancy, escalation-outcome
    counters, abstention-rate and ECE-style calibration over the MI
    stream, and a thresholded OOD alarm.

``runmeta``/``schema``/``validate`` round it out with run provenance
(git sha, device kind, jax versions, interpret mode), a dependency-free
JSON-schema subset validator, and a CLI used by the CI obs-smoke job.
"""
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                Stopwatch, percentile)
from repro.obs.runmeta import run_metadata
from repro.obs.trace import EVENTS, Tracer, span
from repro.obs.uncertainty import UncertaintyTelemetry

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Stopwatch",
    "percentile",
    "Tracer", "EVENTS", "span",
    "UncertaintyTelemetry",
    "run_metadata",
]
