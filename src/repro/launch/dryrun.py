import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

The two lines above MUST stay the first statements in this module — jax
locks the device count at first init, and only the dry-run wants 512
placeholder devices (tests and benches see the real single CPU device).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Each cell writes results/dryrun/<arch>__<shape>__<mesh>[__<mode>].json with
memory_analysis, cost_analysis FLOPs/bytes and the collective-bytes
breakdown parsed from the optimized HLO (§Roofline reads these).
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import ASSIGNED_ARCHS  # noqa: E402
from repro.configs.base import SHAPES  # noqa: E402
from repro.launch import roofline  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.programs import build_program, cell_is_applicable  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "results", "dryrun")


def _serving_probe(arch: str, formulation: str) -> dict:
    """Tiny deterministic engine run on the reduced config: records the
    abstention/escalation counts the uncertainty router produces, so
    decode cells carry a serving section comparable across PRs. The
    dry-run otherwise only compiles; this is the one executed probe
    (seconds on the reduced config, fixed seeds, XLA stack)."""
    from repro.bayes.convert import svi_to_pfp
    from repro.configs import reduced_config
    from repro.models import lm
    from repro.nn import pjit_hints
    from repro.serving.engine import (Engine, EngineConfig, RouterConfig,
                                      UncertaintyRouter, poisson_trace,
                                      run_load)

    import dataclasses

    # Widen the init posteriors (sigma 5e-2 vs the paper's 1e-4 init) so
    # the probe's MI signal actually exercises the router's three bands.
    cfg = dataclasses.replace(reduced_config(arch), sigma_init=5e-2)
    if not cfg.embed_inputs:
        return {"status": "skipped",
                "reason": "frame-embedding frontend (no token prompts)"}
    try:
        pjit_hints.set_rules(None)  # drop the 512-chip cell shardings
        router_cfg = RouterConfig(mi_continue=0.02, mi_abstain=0.5,
                                  escalate_samples=4)
        params = svi_to_pfp(lm.init_params(cfg, jax.random.PRNGKey(0)))
        engine = Engine(
            cfg, params,
            EngineConfig(slots=2, max_len=16, num_uncertainty_samples=16,
                         formulation=formulation, seed=0),
            router=UncertaintyRouter(cfg, router_cfg,
                                     formulation=formulation))
        trace = poisson_trace(6, rate=0.7, vocab_size=cfg.vocab_size,
                              seed=0, prompt_len=(3, 6),
                              max_new_tokens=(2, 4))
        s = run_load(engine, trace, max_steps=500)
        return {"status": "ok",
                "router": {"mi_continue": router_cfg.mi_continue,
                           "mi_abstain": router_cfg.mi_abstain,
                           "escalate_samples": router_cfg.escalate_samples},
                "requests": s["submitted"],
                "completed": s["completed"],
                "abstained": s["abstained"],
                "escalations": s["escalations"],
                "tokens_generated": s["tokens_generated"],
                "abstain_rate": round(s["abstain_rate"], 4),
                "escalation_rate": round(s["escalation_rate"], 4),
                "final_occupancy": s["final_occupancy"]}
    except Exception as e:  # noqa: BLE001
        return {"status": "error", "error": f"{type(e).__name__}: {e}"}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             mode_override=None, save: bool = True, tag: str = "",
             formulation: str = "srm", serve_params: str = "auto",
             impl: str = None) -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    ok, why = cell_is_applicable(arch, shape_name)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "mode_override": mode_override, "tag": tag}
    if not ok:
        result.update(status="skipped", reason=why)
        print(f"[SKIP] {arch} x {shape_name} x {mesh_name}: {why}")
        return _save(result) if save else result

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        prog = build_program(arch, shape_name, mesh,
                             mode_override=mode_override,
                             formulation=formulation,
                             impl=impl,
                             serve_params=serve_params)
        from repro.tuning import cache as schedule_cache  # noqa: E402

        with mesh:
            jitted = jax.jit(prog.fn, in_shardings=prog.in_shardings,
                             donate_argnums=prog.donate_argnums)
            # Record the dispatch layer's schedule-cache queries made while
            # tracing, so the result JSON names the schedule each kernel-impl
            # op would run (tuned describe() or 'default' on a cache miss).
            with schedule_cache.record_shapes() as sched_queries:
                lowered = jitted.lower(*prog.arg_specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        schedules = {}
        for op, shape_key, dtype, backend in sched_queries:
            hit = schedule_cache.global_cache().get(op, shape_key, dtype,
                                                    backend)
            key = f"{op}|{'x'.join(map(str, shape_key))}|{dtype}"
            schedules[key] = hit.describe() if hit is not None else "default"

        mem = compiled.memory_analysis()
        mem_info = {}
        if mem is not None:
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "generated_code_size_in_bytes",
                         "alias_size_in_bytes"):
                v = getattr(mem, attr, None)
                if v is not None:
                    mem_info[attr] = int(v)
        print(compiled.memory_analysis())

        # XLA's cost_analysis counts while-loop bodies ONCE (a scanned
        # 36-layer model shows ~36x too cheap) — kept for reference only.
        cost = compiled.cost_analysis() or {}
        xla_flops = float(cost.get("flops", 0.0))

        # Trip-count-correct costs: exact dot FLOPs from the jaxpr (global /
        # chips) + collective bytes from the partitioned HLO scaled by while
        # trip counts (per-device already).
        from repro.launch import costs as costlib

        jc = costlib.jaxpr_costs(prog.fn, *prog.arg_specs)
        flops = jc["flops_global"] / chips
        bytes_ = jc["bytes_global"] / chips

        hlo = compiled.as_text()
        coll = costlib.collective_bytes_scaled(hlo)
        coll_operand = float(sum(v["operand"] for v in coll.values()))
        coll_link = float(sum(v["link"] for v in coll.values()))
        # Bottleneck classification uses physical ring-link traffic; the
        # operand-sum (assignment metric) is reported alongside.
        coll_total = coll_link

        terms = roofline.roofline_terms(flops, bytes_, coll_total)
        shape_cfg = SHAPES[shape_name]
        mf = roofline.model_flops(prog.meta, shape_cfg.kind,
                                  shape_cfg.seq_len, shape_cfg.global_batch)
        mf_per_dev = mf / chips
        result.update(
            status="ok",
            chips=chips,
            program=prog.name,
            impl=prog.meta.get("impl"),
            schedules=schedules,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            flops_per_device=flops,
            bytes_per_device=bytes_,
            xla_cost_analysis_flops=xla_flops,
            collective_bytes=coll,
            collective_bytes_operand=coll_operand,
            collective_bytes_link=coll_link,
            collective_bytes_total=coll_total,
            memory_analysis=mem_info,
            model_flops_total=mf,
            model_flops_per_device=mf_per_dev,
            useful_flops_ratio=(mf_per_dev / flops) if flops else None,
            hlo_bytes=len(hlo),
            **terms,
        )
        per_dev_hbm = mem_info.get("argument_size_in_bytes", 0) + \
            mem_info.get("temp_size_in_bytes", 0) + \
            mem_info.get("output_size_in_bytes", 0)
        # XLA:CPU has no native bf16: it materializes f32 copies of bf16
        # tensors and breaks aliasing for them, roughly doubling temp for
        # bf16-dominated programs. tpu_hbm_estimate halves temp as the
        # corrected (still conservative) TPU figure; EXPERIMENTS.md §Dry-run
        # documents this.
        alias = mem_info.get("alias_size_in_bytes", 0)
        tpu_est = mem_info.get("argument_size_in_bytes", 0) + \
            mem_info.get("temp_size_in_bytes", 0) / 2 + \
            max(mem_info.get("output_size_in_bytes", 0) - alias, 0)
        fits = tpu_est < 16e9
        result["hbm_bytes_per_device"] = per_dev_hbm
        result["tpu_hbm_estimate"] = tpu_est
        result["fits_16gb_hbm"] = bool(fits)
        print(f"[OK]  {prog.name} mesh={mesh_name} chips={chips} "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s "
              f"flops/dev={flops:.3e} bytes/dev={bytes_:.3e} "
              f"coll/dev={coll_total:.3e} bottleneck={terms['bottleneck']} "
              f"hbm/dev={per_dev_hbm/1e9:.2f}GB fits={fits}")
    except Exception as e:  # noqa: BLE001
        result.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
        print(f"[ERR] {arch} x {shape_name} x {mesh_name}: {e}")
    if SHAPES[shape_name].kind == "decode":
        result["serving"] = _serving_probe(arch, formulation)
        if result["serving"].get("status") == "ok":
            sv = result["serving"]
            print(f"      serving probe: {sv['completed']} completed, "
                  f"{sv['abstained']} abstained, "
                  f"{sv['escalations']} escalations")
    return _save(result) if save else result


def _save(result: dict) -> dict:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    mode = result.get("mode_override") or ""
    tag = result.get("tag") or ""
    suffix = (f"__{mode}" if mode else "") + (f"__{tag}" if tag else "")
    path = os.path.join(
        RESULTS_DIR,
        f"{result['arch']}__{result['shape']}__{result['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2, default=str)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--mode", default=None,
                    help="override program mode (svi/pfp/deterministic)")
    ap.add_argument("--tag", default="", help="result-file suffix")
    ap.add_argument("--formulation", default="srm", choices=["srm", "var"])
    ap.add_argument("--impl", default=None, choices=["xla", "kernel"],
                    help="PFP operator implementation (core/dispatch.py)")
    ap.add_argument("--serve-params", default="auto",
                    choices=["auto", "tp", "fsdp"])
    ap.add_argument("--schedule-cache", default=None,
                    help="tuned-schedule cache JSON to load (repro.tuning); "
                         "kernel-impl cells then compile with and report the "
                         "tuned block shapes")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    if args.schedule_cache:
        from repro.tuning import load_global_cache

        load_global_cache(args.schedule_cache)

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    statuses = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                r = run_cell(arch, shape, multi_pod=mp,
                             mode_override=args.mode, tag=args.tag,
                             formulation=args.formulation,
                             impl=args.impl,
                             serve_params=args.serve_params)
                statuses.append((arch, shape, r["mesh"], r["status"]))
    bad = [s for s in statuses if s[3] == "error"]
    print(f"\n== {len(statuses)} cells: "
          f"{sum(1 for s in statuses if s[3]=='ok')} ok, "
          f"{sum(1 for s in statuses if s[3]=='skipped')} skipped, "
          f"{len(bad)} errors ==")
    for b in bad:
        print("  ERROR:", b)
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
