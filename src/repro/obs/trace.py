"""The serving stack's trace layer: a step-keyed event log and host spans
on the profiler's clock.

Event log. Every lifecycle event is a flat dict keyed on ``(step, seq)``:

  * ``step`` — the emitting engine's (or fleet frontend's) logical step
    counter at emission time. The engine clock is the ONLY time base; two
    identical runs therefore produce byte-identical logs.
  * ``seq`` — a per-tracer monotone sequence number breaking ties within
    a step (events of one step keep emission order).
  * ``lane`` — which component emitted it ("engine", "fleet", "r0",
    "r1.prefill", ...). A fleet shares ONE tracer across the frontend
    and every replica so a request's whole journey lands in one stream.
  * ``event`` — one of :data:`EVENTS`; ``uid`` where the event concerns
    one request; free-form payload fields otherwise.

Export: JSONL, one event per line with sorted keys.

Spans. :func:`span` opens a ``jax.profiler.TraceAnnotation`` named
``pfp.<name>``: the profiler's own host event, on the same clock as the
device planes of the ``.xplane.pb`` a profiler session writes, with its
keyword arguments as the event's stats. Spans are always on; with no
session recording one costs about a microsecond. Where the engine also
emits a step-keyed event for the same phase, it emits it inside the span,
so the log and the spans share one set of boundaries.
"""
from __future__ import annotations

import json
import os
from typing import List, Optional

import jax

EVENTS = (
    "submit",        # request offered to a scheduler (accepted flag)
    "expire",        # deadline-expired in the waiting room
    "admit",         # slot allocated (uid, slot, prefix pages/tokens)
    "prefill_round", # one (batched) prefill round (slots, tokens fed)
    "decode_step",   # one lockstep PFP decode / verify pass (active slots)
    "route",         # one routed token (uid, token, mi, decision)
    "escalate",      # SVI second opinion resolved (uid, pfp/svi mi, outcome)
    "spec_draft",    # mean-only draft pass (slots, drafted tokens)
    "spec_verify",   # chunked PFP verify pass (slots, accepted tokens)
    "cow",           # copy-on-write page duplication(s) for a slot
    "preempt",       # slot evicted mid-flight, request requeued
    "requeue_overflow",  # preemption requeue displaced a waiter
    "defrag",        # page pool defragmented
    "route_replica", # fleet frontend picked a replica (uid, replica, match)
    "handoff",       # disaggregated prefill->decode handoff (uid, ticks)
    "finish",        # request left the engine (uid, reason, tokens)
)


class Tracer:
    """Append-only event sink shared by every component of one serving
    stack. Host-side only: one small dict append per event; engines guard
    every call site with ``if tracer is not None`` so a disabled run pays
    nothing at all."""

    def __init__(self):
        self.events: List[dict] = []
        self._seq = 0

    def emit(self, lane: str, step: int, event: str,
             uid: Optional[int] = None, **fields) -> None:
        if event not in EVENTS:
            raise ValueError(f"unknown trace event {event!r}")
        rec = {"step": int(step), "seq": self._seq, "lane": lane,
               "event": event}
        if uid is not None:
            rec["uid"] = int(uid)
        rec.update(fields)
        self._seq += 1
        self.events.append(rec)

    def bind(self, lane: str) -> "LaneTracer":
        """A view of this tracer that stamps ``lane`` on every event —
        what an engine holds, so fleet wiring is just handing each
        replica a differently-named view of one shared tracer."""
        return LaneTracer(self, lane)

    # -- export -------------------------------------------------------------
    def to_jsonl(self) -> str:
        """One event per line, keys sorted — byte-identical across
        identical runs."""
        lines = [json.dumps(rec, sort_keys=True) for rec in self.events]
        return "\n".join(lines) + ("\n" if lines else "")

    def write_jsonl(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            f.write(self.to_jsonl())


class LaneTracer:
    """A lane-stamping view of a shared :class:`Tracer` (see ``bind``)."""

    __slots__ = ("_tracer", "lane")

    def __init__(self, tracer: Tracer, lane: str):
        self._tracer = tracer
        self.lane = lane

    def emit(self, step: int, event: str, uid: Optional[int] = None,
             **fields) -> None:
        self._tracer.emit(self.lane, step, event, uid=uid, **fields)


# Span names: every event phase, the whole engine step, and the host's wait
# for the device's tokens inside a route.
SPANS = frozenset(EVENTS) | {"step", "sync"}


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Host span ``pfp.<name>`` with ``args`` as its stats; use as a
    context manager, and ``set_metadata(**args)`` on what it returns for
    args known only at the span's end. ``name`` is one of :data:`SPANS`."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}")
    return jax.profiler.TraceAnnotation("pfp." + name, **args)


__all__ = ["Tracer", "LaneTracer", "EVENTS", "SPANS", "span"]
