"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,impl,schedule,derived`` CSV (impl = which
dispatch-registry stack ran; schedule = the tuned Pallas schedule digest
on kernel rows, '-' elsewhere). ``--full`` runs the slow versions
(LeNet-5 training, full batch sweeps); default is the quick profile used
by bench_output.txt.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def _tune_paper_models(*, full: bool, save_path=None) -> None:
    """Warm the global schedule cache for the shape sets the benches
    actually dispatch: the paper MLP at every fig7/table4/table5 batch
    size and LeNet-5 at the table4 profile batch. Wall-clock timing on
    TPU, cost-model ranking elsewhere (rank mode costs no kernel runs,
    so sweeping all batch sizes is cheap)."""
    import jax

    from repro.bayes.convert import svi_to_pfp
    from repro.models.simple import (lenet5_forward, lenet5_init, mlp_forward,
                                     mlp_init)
    from repro.tuning import autotune

    key = jax.random.PRNGKey(0)
    # NB: kept in lockstep with the bench constants (fig7 quick/full batch
    # lists, table4/table5 B=10, d_hidden=100); a shape missing here just
    # means those rows run (and report) the default schedules.
    mlp_batches = [1, 10, 100] + ([4, 16, 64, 256] if full else [])
    lenet_batches = [10] + ([100] if full else [])
    mlp_params = svi_to_pfp(mlp_init(key, d_hidden=100))
    lenet_params = svi_to_pfp(lenet5_init(key))
    targets = [(mlp_forward, mlp_params, jax.random.normal(key, (b, 784)))
               for b in mlp_batches]
    targets += [(lenet5_forward, lenet_params,
                 jax.random.normal(key, (b, 28, 28, 1)))
                for b in lenet_batches]
    total = {}
    for forward, params, batch in targets:
        total.update(autotune(forward, params, batch, verbose=True,
                              save_path=save_path))
    print(f"# tuned {len(total)} (op, shape, dtype) queries", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (e.g. fig7,table5)")
    ap.add_argument("--impl", default=None, choices=["xla", "kernel"],
                    help="PFP operator implementation: flips the dispatch-"
                         "registry default so every bench (including full "
                         "model graphs) runs through the chosen stack")
    ap.add_argument("--tune", action="store_true",
                    help="autotune per-op schedules for the paper models' "
                         "shape sets first (warms the global schedule cache "
                         "— kernel-impl rows then record the tuned schedule "
                         "they ran)")
    ap.add_argument("--schedule-cache", default=None,
                    help="schedule-cache JSON to load before (and save "
                         "after, with --tune) the run")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.impl:
        from repro.core.dispatch import set_default_impl

        set_default_impl(args.impl)

    if args.schedule_cache:
        from repro.tuning import load_global_cache

        load_global_cache(args.schedule_cache)
    if args.tune:
        _tune_paper_models(full=args.full, save_path=args.schedule_cache)

    from benchmarks import (bench_fig5_formulations, bench_fig7_batch_sweep,
                            bench_moe, bench_serving, bench_table1_quality,
                            bench_table2_schedules, bench_table3_maxpool,
                            bench_table4_profiling, bench_table5_processors,
                            bench_tuning)

    benches = {
        "table1": bench_table1_quality,
        "fig5": bench_fig5_formulations,
        "table2": bench_table2_schedules,
        "table3": bench_table3_maxpool,
        "table4": bench_table4_profiling,
        "fig7": bench_fig7_batch_sweep,
        "table5": bench_table5_processors,
        "serving": bench_serving,
        "tuning": bench_tuning,
        "moe": bench_moe,
    }
    from benchmarks.common import CSV_HEADER

    selected = (args.only.split(",") if args.only else list(benches))
    print(CSV_HEADER)
    failures = []
    rows = {}
    for name in selected:
        try:
            rows[name] = benches[name].run(quick=not args.full) or []
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            traceback.print_exc()
    for family in ("serving", "tuning", "moe"):
        if family in rows:
            _write_bench_summary(rows[family], family=family,
                                 full=args.full, impl=args.impl)
    if failures:
        print(f"FAILURES: {[n for n, _ in failures]}", file=sys.stderr)
        sys.exit(1)


def _write_bench_summary(lines, *, family: str, full: bool, impl) -> None:
    """Persist one bench family's rows as results/BENCH_<family>.json — a
    machine-readable artifact (uploaded by CI) so the perf trajectory
    (serving AND autotuner rows) is trackable across PRs instead of
    living only in logs. Every row carries the run metadata (git sha,
    device kind, jax/jaxlib versions, interpret-mode flag), so rows stay
    attributable after CI concatenates artifacts across commits and
    machines."""
    from repro.core.dispatch import resolve_impl
    from repro.obs.runmeta import run_metadata

    meta = run_metadata()

    def parse(line: str) -> dict:
        name, us, impl_col, schedule, derived = line.split(",", 4)
        row = {"name": name, "us_per_call": float(us), "impl": impl_col,
               "schedule": schedule}
        for item in filter(None, derived.split(";")):
            k, _, v = item.partition("=")
            try:
                row[k] = float(v) if "." in v or "e" in v else int(v)
            except ValueError:
                row[k] = v
        row.update(meta)
        return row

    payload = {
        "generated_by": "benchmarks/run.py",
        "unix_time": time.time(),
        "profile": "full" if full else "quick",
        "impl": resolve_impl(impl),
        "meta": meta,
        "rows": [parse(line) for line in lines],
    }
    out = os.path.join("results", f"BENCH_{family}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"# {family} summary -> {out} ({len(payload['rows'])} rows)",
          flush=True)


if __name__ == "__main__":
    main()
