"""Readings from which a cell's limits are set: the numbers compared for
``correct`` on many seeds, and the control's on some of them, in one
process (the weights are made anew per seed; programs compile once).

    python bench/calibrate.py --workload <cell> --seeds 11,12,... \
        --control-seeds 11,12,13 --seconds 20

Prints one JSON line per seed, then one summary line: for each number the
largest program reading and the smallest control reading, and whether
every program run and every control came out correct under the cell's
limits (the harness's own check, ``run.judge``). The control is the
reference computed in the precision below the configuration's (fp8
operands for bf16 compute; ``control_precision`` for fp32) in the
program's place; under sound limits it comes out not correct. A seed is
not a benchmark run; ``bench/run.py`` never reads the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import enable_compile_cache, run_cell, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    prog, ctrl, verdicts = {}, {}, {"program": [], "control": []}
    for s in [int(x) for x in args.seeds.split(",")]:
        t0 = time.perf_counter()
        try:
            r = run_cell(args.workload, s, args.seconds, False, t_start=t0,
                         control=s in controls)
        except Exception as e:  # report the seed, go on with the others
            import traceback
            traceback.print_exc()
            print(json.dumps({"seed": s, "error": repr(e)[:500]}),
                  flush=True)
            continue
        nums = r["numbers"]
        line = {"seed": s, "numbers": nums, "correct": r["correct"],
                "control": r.get("control"),
                "control_correct": r.get("control_correct"),
                "metrics": {k: m["value"] for k, m in r["metrics"].items()},
                "attempted": r["attempted"], "failed": r["failed"],
                "memory_peak_bytes": r["device"]["memory_peak_bytes"],
                "wall_s": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        verdicts["program"].append(r["correct"])
        if "control_correct" in r:
            verdicts["control"].append(r["control_correct"])
        for k, v in nums.items():
            prog[k] = max(prog.get(k, v), v)
        for k, v in (r.get("control") or {}).items():
            ctrl[k] = min(ctrl.get(k, v), v)
    print(json.dumps({"program_max": prog, "control_min": ctrl,
                      "program_all_correct": all(verdicts["program"]),
                      "controls_all_incorrect": not any(verdicts["control"]),
                      "limits": spec.workload(args.workload).get("limits")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
