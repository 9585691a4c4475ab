"""Find the knee of a serving cell: the highest offered rate whose queue
does not grow over the window. One process, one set of weights, the
cell's traffic at each rate in turn.

    python bench/sweep.py --workload granite-8b.chat.steady \
        --rates 1,1.5,2,2.5,3 --seconds 30 --seed 7

Prints one JSON line per rate: the end-to-end numbers, quantiles of the
gaps between tokens, and at each quarter of the window the backlog
(requests due and not finished) and the requests still waiting for their
first token; the knee is the highest rate at which the waiting do not
grow from the middle of the window to its end. The cell's file then
fixes its rate at about 4/5 of the knee; the benchmark's own runs never
search for one.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import enable_compile_cache, spec  # noqa: F401

from bench.stats import percentile


def backlog(records, t: float) -> int:
    """Requests due by ``t`` and not finished by then."""
    return sum(1 for r in records if r.due <= t and
               (r.finished is None or r.finished > t))


def waiting(records, t: float) -> int:
    """Requests due by ``t`` without a first token by then: the queue
    and the prefills in flight. Below the knee it stays bounded; above
    it, it grows through the window. (The backlog grows below the knee
    too while the window is shorter than the longest requests.)"""
    return sum(1 for r in records if r.due <= t and
               (not r.tokens or r.tokens[0] > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import contextlib

    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = spec.workload(args.workload)
    conf = spec.config(cell["config"])
    traffic = spec.load_module("traffic", cell["traffic"]["kind"])
    system = spec.load_module("systems", conf["system"]).System(
        conf, cell, args.seed)
    for rate in [float(x) for x in args.rates.split(",")]:
        c = {**cell, "traffic": {**cell["traffic"], "rate_rps": rate}}
        runner = traffic.Runner(system, conf, c, args.seed, args.seconds,
                                lambda name: contextlib.nullcontext())
        runner.warm()
        t0 = time.perf_counter()
        runner.run()
        recs = runner.driver.records
        gaps = [b - a for r in recs for a, b in zip(r.tokens, r.tokens[1:])
                if b <= args.seconds]
        print(json.dumps({
            "rate_rps": rate, **runner.end_to_end(), **runner.diagnostics(),
            "itl_ms": {q: 1e3 * percentile(gaps, q) for q in (50, 90, 95,
                                                             99)}
            if gaps else None,
            "backlog": [backlog(recs, args.seconds * f)
                        for f in (0.25, 0.5, 0.75, 1.0)],
            "waiting": [waiting(recs, args.seconds * f)
                        for f in (0.25, 0.5, 0.75, 1.0)],
            "wall_s": time.perf_counter() - t0}), flush=True)
        runner.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
