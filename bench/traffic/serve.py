"""Serving traffic: requests with lognormal prompt and output lengths, sent
open loop (Poisson arrivals at a fixed rate).

The schedule is the same on every seed: the lengths are the lognormal's
quantiles at (i + 1/2)/n and the gaps the exponential's, shuffled by a
generator of fixed seed (``SCHEDULE_SEED``). The run's seed draws the
prompts' token ids (and the weights). The engine's work depends on the
lengths and arrivals alone (no stop token, no routing at infinite
thresholds), so every seed gives the same work in the same order, and
runs on different seeds spread as little as runs of one seed: with some
tens of requests in a window, another order per seed would move the
tails by more than any change of the program worth seeing.

Parameters (the cell's ``traffic`` object):
    mode            "open" (the only mode so far)
    rate_rps        offered requests per second
    prompt          {"median", "sigma", "min", "max"} lognormal, tokens
    output          {"median", "sigma", "min", "max"} lognormal, tokens
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import time
from typing import List, Optional

import numpy as np

from bench.stats import percentile

_NORMAL = statistics.NormalDist()
SCHEDULE_SEED = 1


def lognormal_set(n: int, p: dict) -> np.ndarray:
    """n lengths at the lognormal's quantiles (i + 1/2)/n, clipped."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in q])
    x = np.round(p["median"] * np.exp(p["sigma"] * z))
    return np.clip(x, p["min"], p["max"]).astype(np.int64)


def exponential_set(n: int, rate: float) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


@dataclasses.dataclass
class Planned:
    uid: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0                 # seconds after the window's start


@dataclasses.dataclass
class Record:
    plan: Planned
    due: float
    submitted: float
    accepted: bool
    request: object = None
    tokens: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None


def plan(traffic: dict, seed: int, seconds: float, vocab: int,
         ) -> List[Planned]:
    """The run's requests, in due order: enough to cover the window at the
    offered rate with room to spare. The first round(rate * seconds) of
    them take their lengths and gaps from sets of their own size, so the
    window offers the nominal rate and the distributions' quantiles
    whatever the schedule's order."""
    if traffic["mode"] != "open":
        raise ValueError(f"unknown serving traffic mode {traffic['mode']!r}")
    sched = np.random.default_rng(SCHEDULE_SEED)
    rate = traffic["rate_rps"]
    k = max(1, int(round(rate * seconds)))
    n = int(math.ceil(rate * seconds * 1.3)) + 8

    def blocks(make):
        # the k requests the window offers, then the ones after it
        return np.concatenate([sched.permutation(make(k)),
                               sched.permutation(make(n - k))])
    prompts = blocks(lambda m: lognormal_set(m, traffic["prompt"]))
    outputs = blocks(lambda m: lognormal_set(m, traffic["output"]))
    due = np.cumsum(blocks(lambda m: exponential_set(m, rate)))
    rng = np.random.default_rng(seed)
    out = [Planned(uid=i, max_new=int(outputs[i]),
                   prompt=rng.integers(0, vocab, int(prompts[i]),
                                       dtype=np.int64).astype(np.int32))
           for i in range(n)]
    for p, d in zip(out, due):
        p.due = float(d)
    return out


class Driver:
    """Drives one engine through a window of ``seconds`` and keeps, per
    request, when it was due, when it was submitted and when each of its
    tokens reached the host (the end of the ``Engine.step`` that made it).
    ``annotate(name)`` is a context-manager factory for host spans (the
    profiler's TraceAnnotation in a traced run)."""

    def __init__(self, system, engine, annotate):
        self.system = system
        self.engine = engine
        self.annotate = annotate
        self.records: List[Record] = []
        self.steps = 0

    def _submit(self, p: Planned, due: float, t0: float) -> Record:
        from repro.serving.batcher import Request

        req = Request(uid=p.uid, prompt=p.prompt.copy(),
                      max_new_tokens=p.max_new)
        with self.annotate("bench.submit"):
            ok = self.engine.submit(req)
        rec = Record(plan=p, due=due, submitted=time.perf_counter() - t0,
                     accepted=ok, request=req)
        self.records.append(rec)
        return rec

    def run(self, planned: List[Planned], seconds: float) -> None:
        """Run the window: submit each request when due, step the engine,
        note when each token reached the host."""
        live: List[Record] = []
        t0 = time.perf_counter()
        i = 0
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            while i < len(planned) and planned[i].due <= now:
                rec = self._submit(planned[i], planned[i].due, t0)
                if rec.accepted:
                    live.append(rec)
                i += 1
            if not live:
                nxt = planned[i].due if i < len(planned) else seconds
                with self.annotate("bench.wait"):
                    time.sleep(max(0.0, min(nxt, seconds) - now))
                continue
            with self.annotate("bench.step"):
                self.system.step(self.engine)
            self.steps += 1
            t = time.perf_counter() - t0
            still = []
            for rec in live:
                req = rec.request
                while len(rec.tokens) < len(req.generated):
                    rec.tokens.append(t)
                if req.done:
                    rec.finished = t
                else:
                    still.append(rec)
            live = still


def end_to_end(records: List[Record], seconds: float) -> dict:
    """The cell's end-to-end numbers from the window's records.

    ttft: every request due in the window, from its due time to its first
    token; one with no token by the window's end counts at its age then.
    itl: every gap between successive tokens of a request, in the window.
    tokens_per_s: every token delivered in the window over the window."""
    due = [r for r in records if r.due < seconds]
    ttft = []
    for r in due:
        first = r.tokens[0] if r.tokens and r.tokens[0] <= seconds else None
        ttft.append((first if first is not None else seconds) - r.due)
    gaps = [b - a for r in records for a, b in zip(r.tokens, r.tokens[1:])
            if b <= seconds]
    tokens = sum(1 for r in records for t in r.tokens if t <= seconds)
    out = {"tokens_per_s": tokens / seconds}
    if ttft:
        out["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    if gaps:
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    return out


def diagnostics(records: List[Record], seconds: float) -> dict:
    late = [r.submitted - r.due for r in records if r.due < seconds]
    return {
        "due": sum(1 for r in records if r.due < seconds),
        "rejected": sum(1 for r in records if not r.accepted),
        "finished": sum(1 for r in records if r.finished is not None),
        "tokens": sum(len(r.tokens) for r in records),
        "generator_late_p95_ms": 1e3 * percentile(late, 95) if late else 0.0,
        "generator_late_max_ms": 1e3 * max(late) if late else 0.0,
    }


def warm(system, engine, prompt_len: int, vocab: int) -> None:
    """Run every program the window runs once, at its shapes: batched
    prefill (two chunks), decode, uncertainty sampling, the router's
    second opinion where it escalates, and a page defrag (the middle
    request finishes first)."""
    from repro.serving.batcher import Request

    rng = np.random.default_rng(0)
    for uid, n_new in ((10**6, 6), (10**6 + 1, 2), (10**6 + 2, 6)):
        engine.submit(Request(uid=uid, prompt=rng.integers(
            0, vocab, prompt_len).astype(np.int32), max_new_tokens=n_new))
    while not engine.idle:
        system.step(engine)


class Runner:
    """The serving cell's run: warm-up, window, end-to-end numbers and the
    check of the served tokens against the configuration's reference."""

    def __init__(self, system, conf: dict, cell: dict, seed: int,
                 seconds: float, annotate):
        self.system, self.conf, self.cell = system, conf, cell
        self.seed, self.seconds = seed, seconds
        self.traffic = cell["traffic"]
        self.annotate = annotate
        self.planned = plan(self.traffic, seed, seconds, system.vocab_size)
        self.driver = None

    def warm(self) -> None:
        """Warm an engine with a few requests of their own, then serve
        the window on it (idle again, its telemetry reset)."""
        engine = self.system.new_engine()
        warm(self.system, engine,
             prompt_len=self.conf["engine"]["prefill_chunk"] + 1,
             vocab=self.system.vocab_size)
        engine.reset_metrics()
        self.driver = Driver(self.system, engine, self.annotate)

    def run(self) -> None:
        self.driver.run(self.planned, self.seconds)

    @property
    def attempted(self) -> int:
        return sum(1 for r in self.driver.records if r.due < self.seconds)

    @property
    def failed(self) -> int:
        return sum(1 for r in self.driver.records
                   if r.due < self.seconds and not r.accepted)

    def end_to_end(self) -> dict:
        return end_to_end(self.driver.records, self.seconds)

    def diagnostics(self) -> dict:
        return {**diagnostics(self.driver.records, self.seconds),
                "steps": self.driver.steps}

    def served_flops(self) -> float:
        """Analytic PFP operations of what the window served: each prompt
        whose first token came in the window (all its positions), and each
        later token that came in it (the position fed to make it)."""
        from bench.costs import pfp

        tot = 0
        for r in self.driver.records:
            p = len(r.plan.prompt)
            toks = [i for i, t in enumerate(r.tokens) if t <= self.seconds]
            if not toks:
                continue
            if toks[0] == 0:
                tot += pfp.lm_tokens_flops(self.conf, 0, p - 1)
            dec = [i for i in toks if i >= 1]
            if dec:
                tot += pfp.lm_tokens_flops(self.conf, p + dec[0] - 1,
                                           p + dec[-1] - 1)
        return float(tot)

    def release(self) -> None:
        self.driver.engine = None

    def sample(self, target_tokens: int = 384, most: int = 8) -> list:
        """Finished requests to check, drawn from the seed: the one with
        the most served tokens, then others in a seeded order until the
        sample holds ``target_tokens`` served tokens or ``most``
        requests."""
        done = [r for r in self.driver.records
                if r.finished is not None and r.finished <= self.seconds]
        if not done:
            return []
        done.sort(key=lambda r: r.plan.uid)
        first = max(done, key=lambda r: len(r.request.generated))
        rest = [done[i] for i in np.random.default_rng(
            self.seed + 2).permutation(len(done)) if done[i] is not first]
        out, n = [], 0
        for r in [first] + rest:
            if len(out) == most or n >= target_tokens:
                break
            req = r.request
            out.append((req.uid, np.asarray(r.plan.prompt), list(
                req.generated), [float(m) for m in req.mi_trace]))
            n += len(req.generated)
        return out

    def check(self, control: bool = False) -> dict:
        """The numbers compared for ``correct`` (see the reference's
        ``compare``); {'served': 0} where nothing finished."""
        from bench import spec

        served = self.sample()
        if not served:
            return {"finished_checked": 0}
        ref = spec.load_module("reference", self.conf["reference"])
        out = ref.compare(self.conf, self.seed, self.seed,
                          self.conf["engine"]["num_uncertainty_samples"],
                          served, control=control)
        out["finished_checked"] = len(served)
        return out
