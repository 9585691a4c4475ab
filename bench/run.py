"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run refuses to start off a TPU, or on fewer chips than the cell asks
for (exit 2, no result). Otherwise it keeps JAX's persistent compilation
cache at ``<checkout>/.jax_cache``, makes the weights on the device from
the seed, warms the cell's own shapes, measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints:

* on standard error, set-up and window diagnostics (compiles inside the
  window, the load generator's lateness), then, last, each number
  compared beside its limit;
* on standard output, last, one JSON object: ``correct``, ``attempted``,
  ``failed``, ``metrics`` (``--trace 0``: the cell's end-to-end metrics;
  ``--trace 1``: its per-layer metrics, read from a profiler trace of the
  window), ``device``, with ``--trace 1`` ``breakdown``, and last
  ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import os  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

# libtpu logs under /tmp unless told otherwise; a run writes nothing there
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import spec  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Compiles and traces JAX reports while installed (monitoring
    events), so the window can show that nothing compiled inside it."""

    def __init__(self):
        self.compiles = 0
        self.traces = 0

    def __call__(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            if "backend_compile" in event:
                self.compiles += 1
            elif "jaxpr_trace" in event:
                self.traces += 1

    @contextlib.contextmanager
    def installed(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader sees."""
    trace: object
    peak: dict
    conf: dict
    cell: dict
    served_flops: float = 0.0


def _annotator(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def enable_compile_cache() -> str:
    """The persistent compilation cache at a fixed path in the checkout;
    every program is kept, however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def _device_info(devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, conf_override: dict = None,
             cell_override: dict = None, limits: dict = None,
             peak_kind: str = None,
             control: bool = None) -> dict:
    """One run of cell ``name``; returns the result object. The overrides
    let a test run a cell's code at a size a CPU can hold;
    ``peak_kind`` names the peaks' row where the device has none (a CPU
    rehearsal); ``control`` not None puts every number compared, limit
    or not, into ``result['numbers']``, and True also the control's (the
    reference in a lower precision in the program's place) into
    ``result['control']``, judged by the same limits into
    ``result['control_correct']`` (``bench/calibrate.py``)."""
    import jax
    from repro.core import dispatch

    man = spec.manifest()
    cell = {**spec.workload(name), **(cell_override or {})}
    conf = {**spec.config(cell["config"]), **(conf_override or {})}
    traffic = spec.load_module("traffic", cell["traffic"]["kind"])
    annotate = _annotator(trace)
    counter = CompileCounter()
    with dispatch.count_fallbacks() as fallbacks:
        system = spec.load_module("systems", conf["system"]).System(
            conf, cell, seed)
        runner = traffic.Runner(system, conf, cell, seed, seconds, annotate)
        runner.warm()
        setup_s = time.perf_counter() - t_start
        log(f"setup_s={setup_s:.3f} cell={name} seed={seed}")
        prof_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(prof_dir)
        try:
            with counter.installed(), annotate("bench.window"):
                runner.run()
        finally:
            if trace:
                jax.profiler.stop_trace()
    devices = jax.devices()
    device = _device_info(devices)
    log("window", " ".join(f"{k}={v}" for k, v in {
        "compiles": counter.compiles, "traces": counter.traces,
        **runner.diagnostics()}.items()))

    result = {"correct": False, "attempted": runner.attempted,
              "failed": runner.failed, "metrics": {}, "device": device}
    if trace:
        from bench import trace as tr
        from bench.costs import pfp
        files = glob.glob(f"{prof_dir}/**/*.xplane.pb", recursive=True)
        norm = tr.normalise(files[0])
        shutil.rmtree(prof_dir, ignore_errors=True)
        t = tr.Trace(norm)
        ctx = MetricContext(t, pfp.peaks(peak_kind or device["kind"]), conf,
                            cell,
                            runner.served_flops())
        for m in spec.cell_metrics(man, name, "per_layer"):
            v = spec.load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        device["busy_s"] = t.busy_s()
        device["window_s"] = t.window_s
        result["breakdown"] = {"device_ops": t.top_ops(10),
                               "idle_gaps": t.idle_gaps(10)}
    else:
        e2e = {**runner.end_to_end(), "setup_s": setup_s}
        for m in spec.cell_metrics(man, name, "end_to_end"):
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}

    system.free()
    runner.release()
    gc.collect()
    numbers = {**runner.check(), "fallbacks": sum(fallbacks.values())}
    if control is not None:
        result["numbers"] = numbers
    lim = limits if limits is not None else cell.get("limits", {})
    if control:
        # the reference in the program's place runs no kernel: 0 fallbacks
        ctrl = {**runner.check(control=True), "fallbacks": 0}
        result["control"] = ctrl
        result["control_correct"] = judge(ctrl, lim)[0]
    result["correct"], checks = judge(numbers, lim)
    for k, c in checks.items():
        print(f"[check] {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    result["checks"] = checks
    return result


def judge(numbers: dict, limits: dict):
    """(correct, checks): each number that has a limit (``fallbacks``
    always, limit 0) beside it, and whether every one is within it.
    A limit whose number is missing (nothing finished to compare), or
    a limit of None, makes the run incorrect."""
    lim = {"fallbacks": 0, **limits}
    checks = {k: {"value": v, "limit": lim.get(k)}
              for k, v in numbers.items() if k in lim}
    correct = bool(checks) and set(lim) <= set(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return correct, checks


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.workload(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX finds {len(devices)} {devices[0].platform} "
              "device(s)", file=sys.stderr)
        return 2
    enable_compile_cache()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
