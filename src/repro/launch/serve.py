"""Distributed PFP serving driver: the continuous-batching engine on a
(data, model) mesh — the executed version of the decode_* dry-run cells.

Drives ``repro.serving.engine``: Poisson request arrivals, admission-
controlled scheduling, chunked prefill, one probabilistic forward pass per
decode step for the whole slot batch, and uncertainty routing
(continue / escalate-to-SVI / abstain). ``--impl kernel`` flips every PFP
op onto the Pallas kernels via the impl-dispatch registry (interpret mode
on the CPU backend only).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --engine --impl kernel \
      --page-size 16                      # chip: mesh (1, jax.device_count())
  PYTHONPATH=src python -m repro.launch.serve --devices 8 --mesh 2,4 \
      --engine --tokens 8                 # CPU: 8 forced host devices
  PYTHONPATH=src python -m repro.launch.serve --devices 2 \
      --engine --tokens 4                 # CPU interpret-mode smoke
  PYTHONPATH=src python -m repro.launch.serve --devices 2 \
      --impl kernel --save-schedule-db db.json   # tune + persist fleet DB
  PYTHONPATH=src python -m repro.launch.serve --devices 2 \
      --impl kernel --schedule-db db.json --expect-warm-cache  # warm start

Without ``--full-size`` the arch's ``reduced_config`` is served.
"""
import argparse
import dataclasses
import json
import os
import sys
from typing import Callable

import jax
import jax.numpy as jnp

from repro.bayes.convert import svi_to_pfp
from repro.configs import get_config, reduced_config
from repro.launch import sharding as shlib
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.obs.trace import Tracer
from repro.serving.engine import (Engine, EngineConfig, RequestScheduler,
                                  RouterConfig, SchedulerConfig,
                                  UncertaintyRouter, poisson_trace, run_load)
from repro.serving.fleet import Fleet, FleetConfig

_SUMMARY_KEYS = (
    "submitted", "rejected", "expired", "completed", "abstained",
    "escalations", "tokens_generated", "steps", "throughput_tok_s",
    "p50_latency_steps", "p99_latency_steps", "abstain_rate",
    "escalation_rate", "peak_occupancy", "final_occupancy",
)

_PAGED_KEYS = (
    "preemptions", "defrags", "peak_page_occupancy", "mean_page_occupancy",
    "mean_page_fragmentation", "final_live_pages",
)

_PREFIX_KEYS = (
    "prefix_hits", "prefix_hit_rate", "prefix_shared_pages",
    "prefill_tokens_saved", "prefill_frac_saved", "cow_copies",
    "mean_shared_pages", "final_prefix_held_pages",
)

_MOE_KEYS = (
    "moe_assignments", "moe_dropped_assignments", "moe_drop_rate",
    "moe_held_assignments", "moe_experts_touched", "moe_padding_routed",
    "moe_max_expert_load",
)

_SPEC_KEYS = (
    "spec_rounds", "draft_tokens", "accepted_draft_tokens",
    "draft_acceptance_rate", "accepted_tokens_per_verify", "verify_passes",
    "decode_passes", "draft_passes", "svi_passes", "svi_passes_per_step",
    "max_svi_passes_per_step", "mean_escalation_batch",
    "pfp_passes_per_token",
)


_FLEET_KEYS = (
    "replicas", "submitted", "rejected", "expired", "finished", "completed",
    "abstained", "tokens_generated", "prefill_tokens", "steps",
    "route_prefix_hits", "route_fallbacks", "route_hit_rate",
    "route_tokens_matched", "prefix_hits", "prefix_hit_rate",
    "prefill_tokens_saved", "cow_copies", "preemptions", "requeue_overflow",
    "final_occupancy",
)

_DISAGG_KEYS = (
    "handoffs", "p50_handoff_steps", "p99_handoff_steps",
    "decode_steps_during_peer_prefill",
)


def _lane_registries(target):
    """lane -> MetricsRegistry for every telemetry owner in a serving
    stack: a single Engine, or a Fleet frontend plus each replica engine
    (a DisaggPair contributes its prefill and decode engines)."""
    if hasattr(target, "replicas"):  # Fleet
        out = {"fleet": target.metrics.registry}
        for i, rep in enumerate(target.replicas):
            if hasattr(rep, "engines"):  # DisaggPair
                out[f"r{i}.prefill"] = rep.prefill_engine.metrics.registry
                out[f"r{i}.decode"] = rep.decode_engine.metrics.registry
            else:
                out[f"r{i}"] = rep.metrics.registry
        return out
    return {"engine": target.metrics.registry}


def _export_obs(args, target, summary, tracer):
    """Write the run's observability artifacts: the JSONL request log,
    the metrics JSON payload (run metadata + summary + every lane's
    registry snapshot), and the Prometheus text export."""
    if tracer is not None and args.trace_out:
        tracer.write_jsonl(args.trace_out)
        print(f"trace: {len(tracer.events)} events -> {args.trace_out}")
    regs = _lane_registries(target)
    if args.metrics_out:
        from repro.obs.runmeta import run_metadata
        payload = {
            "meta": run_metadata(),
            "summary": summary,
            "registries": {lane: reg.snapshot()
                           for lane, reg in sorted(regs.items())},
        }
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"metrics: -> {args.metrics_out}")
    if args.prom_out:
        # One text exposition over every lane (lane is a label); repeated
        # HELP/TYPE headers from the per-lane exports are deduplicated.
        seen, lines = set(), []
        for lane, reg in sorted(regs.items()):
            for line in reg.to_prometheus(
                    extra_labels={"lane": lane}).splitlines():
                if line.startswith("#"):
                    if line in seen:
                        continue
                    seen.add(line)
                lines.append(line)
        os.makedirs(os.path.dirname(args.prom_out) or ".", exist_ok=True)
        with open(args.prom_out, "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"prometheus: -> {args.prom_out}")


def _run_fleet(args, cfg, params, router, sched_cfg, mesh, dims, max_len,
               build_engine, make_trace, tracer=None):
    """--replicas R: the fleet frontend path. Routed multi-replica output
    must be bit-for-bit (tokens AND MI traces) a single engine's on the
    same trace — every replica runs the baseline's pass shapes and the
    per-(uid, token) keyed sampling makes placement invisible — and every
    replica's pool must drain without a page or hold leak."""
    import numpy as np
    if args.disaggregate and (args.page_size is None
                              or not args.prefix_sharing):
        print("ERROR: --disaggregate requires --page-size and "
              "--prefix-sharing (pages hand off from the prefill engine "
              "to the decode engine through the prefix index)",
              file=sys.stderr)
        return 2
    engine_cfg = EngineConfig(
        slots=args.batch, max_len=max_len, impl=args.impl,
        compute_dtype=jnp.bfloat16, seed=args.seed,
        page_size=args.page_size, page_budget=args.page_budget,
        reserve_pages=not args.optimistic_pages,
        # a defrag inside one engine of a disaggregated pair would remap
        # the peer's tables without permuting its replay snapshot
        auto_defrag=args.page_size is not None and not args.disaggregate,
        prefix_sharing=args.prefix_sharing,
        prefix_retention_pages=args.prefix_retention,
        speculate_k=args.speculate)
    with mesh:
        fleet = Fleet(cfg, params, engine_cfg,
                      FleetConfig(replicas=args.replicas,
                                  disaggregate=args.disaggregate),
                      router=router, scheduler_config=sched_cfg, mesh=mesh,
                      tracer=tracer)
        summary = run_load(fleet, make_trace())
    _export_obs(args, fleet, summary, tracer)

    mode = "disaggregated" if args.disaggregate else "replicated"
    layout = (f"paged/ps={args.page_size}" if args.page_size
              else "contiguous")
    if args.prefix_sharing:
        layout += "/prefix"
    print(f"== fleet summary ({cfg.name}, R={args.replicas} {mode}, "
          f"mesh={dims}, impl={args.impl or 'default'}, kv={layout}) ==")
    for k in _FLEET_KEYS + (_DISAGG_KEYS if args.disaggregate else ()):
        v = summary[k]
        print(f"  {k:22s} {v:.4g}" if isinstance(v, float)
              else f"  {k:22s} {v}")

    # -- per-replica drain + page/hold leak checks --------------------------
    occ = sum(r.active_slots for r in fleet.replicas)
    if occ != 0:
        print(f"ERROR: fleet leaked {occ} occupied slots after drain",
              file=sys.stderr)
        return 1
    for i, rep in enumerate(fleet.replicas):
        rep.pool.check_invariants()
        prefix = getattr(rep, "prefix", None)
        if prefix is not None:
            if prefix.pages_held > prefix.retention_pages:
                print(f"ERROR: replica {i} prefix index holds "
                      f"{prefix.pages_held} pages beyond its retention of "
                      f"{prefix.retention_pages}", file=sys.stderr)
                return 1
            prefix.check_invariants(rep.pool)
        if args.page_size is not None:
            pool = rep.pool
            leaked = [p for p in range(1, pool.num_pages)
                      if pool.page_ref[p] != pool.external_holds[p]]
            if leaked:
                print(f"ERROR: replica {i} page/hold leak on pages "
                      f"{leaked[:8]} ({len(leaked)} total) after drain",
                      file=sys.stderr)
                return 1

    if args.expect_route_hits is not None:
        if summary["route_prefix_hits"] == 0 or \
                summary["route_hit_rate"] < args.expect_route_hits:
            print("ERROR: --expect-route-hits: "
                  f"{summary['route_prefix_hits']} prefix routes at "
                  f"hit-rate {summary['route_hit_rate']:.3f} "
                  f"(floor {args.expect_route_hits})", file=sys.stderr)
            return 1
    if args.disaggregate and summary["handoffs"] == 0:
        print("ERROR: --disaggregate but no prefill->decode handoff "
              "completed (trace drained without disaggregation engaging)",
              file=sys.stderr)
        return 1

    # -- bit-for-bit parity with a single engine ----------------------------
    # The baseline reuses the fleet's exact engine_cfg (NOT build_engine's,
    # which re-enables auto_defrag): with an identical pass signature it
    # shares the replicas' compiled executables, so the comparison can
    # only surface routing/handoff bugs, never compilation variance.
    assert build_engine is not None  # single-engine path's builder, unused
    with mesh:
        single = Engine(cfg, params, engine_cfg, router=router,
                        scheduler=RequestScheduler(sched_cfg,
                                                   max_len=max_len),
                        mesh=mesh)
        run_load(single, make_trace())
    out = lambda reqs: {r.uid: (list(r.generated),  # noqa: E731
                                [float(m) for m in r.mi_trace],
                                r.finish_reason) for r in reqs}
    got, want = out(fleet.finished), out(single.finished)
    if got != want:
        diff = sorted(u for u in set(got) | set(want)
                      if got.get(u) != want.get(u))
        print("ERROR: routed fleet output diverged from the single-engine "
              f"baseline on uids {diff[:8]} (tokens and MI traces must be "
              "bit-for-bit identical)", file=sys.stderr)
        return 1
    assert np is not None  # imported for parity-debug sessions
    print(f"fleet served {summary['completed']} requests "
          f"({summary['tokens_generated']} tokens) across {args.replicas} "
          "replicas — bit-for-bit the single-engine stream, "
          f"{summary['route_prefix_hits']} of them routed to a cached "
          "prefix.")
    return 0


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=None,
                    help="host devices to force on the CPU backend (tests "
                         "of meshes without a chip); an accelerator "
                         "backend ignores it")
    ap.add_argument("--mesh", default=None,
                    help="data,model dims (default: 1,<device count>)")
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--full-size", action="store_true",
                    help="serve the arch's published config instead of "
                         "its reduced CPU-smoke one")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (the continuous batch size)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=8,
                    help="max new tokens per request")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate (requests per engine step)")
    ap.add_argument("--engine", action="store_true", default=True,
                    help="no-op compatibility flag: the continuous-batching "
                         "engine is the only serving path (the pre-engine "
                         "lockstep demo loop was removed)")
    ap.add_argument("--impl", default=None, choices=["xla", "kernel"],
                    help="PFP operator implementation (core/dispatch.py)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged Gaussian KV-cache page size (rows per "
                         "page); default: contiguous per-slot layout")
    ap.add_argument("--page-budget", type=int, default=None,
                    help="usable pages in the pool (default: "
                         "slots * ceil(max_len / page_size))")
    ap.add_argument("--optimistic-pages", action="store_true",
                    help="admit on prompt pages only and claim decode "
                         "pages on demand (may preempt) instead of "
                         "reserving the full prompt+generation need")
    ap.add_argument("--expect-defrag", action="store_true",
                    help="exit nonzero unless the run performed at least "
                         "one page defrag (CI: prove multi-page churn)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="refcounted copy-on-write prefix sharing: index "
                         "finished lineages' pages and map them into new "
                         "requests sharing a prompt prefix (paged only)")
    ap.add_argument("--prefix-retention", type=int, default=None,
                    help="max pages the prefix index may hold for finished "
                         "lineages (default: the whole page budget)")
    ap.add_argument("--common-prefix", type=int, default=0,
                    help="overwrite the first N tokens of every generated "
                         "prompt with one fixed system prefix, so the "
                         "trace exercises prefix sharing")
    ap.add_argument("--expect-prefix-hits", action="store_true",
                    help="exit nonzero unless at least one admission "
                         "mapped shared prefix pages (CI smoke)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="uncertainty-speculative decoding: draft K tokens "
                         "per slot with a mean-only pass and verify the "
                         "block with ONE chunked PFP pass (paged only); "
                         "the run is checked bit-for-bit against a plain "
                         "engine on the same trace")
    ap.add_argument("--expect-moe-drop", action="store_true",
                    help="exit nonzero unless the run recorded MoE routing "
                         "accounting (moe_assignments > 0), no dropped "
                         "assignment and no padding row routed — CI: "
                         "prove the serving path is dropless (moe only)")
    ap.add_argument("--expect-accept-rate", type=float, default=None,
                    metavar="R",
                    help="exit nonzero if the draft acceptance rate falls "
                         "below R (CI: prove speculation actually "
                         "amortizes verify passes)")
    ap.add_argument("--replicas", type=int, default=1, metavar="R",
                    help="serve through a fleet of R data-parallel replica "
                         "engines behind a prefix-routing frontend; the "
                         "routed output is checked bit-for-bit against a "
                         "single-engine baseline on the same trace")
    ap.add_argument("--disaggregate", action="store_true",
                    help="split each replica into a prefill engine and a "
                         "decode engine over one shared page pool (needs "
                         "--page-size and --prefix-sharing): prompts "
                         "prefill on the prefill engine and the pages hand "
                         "off through the prefix index, so decode "
                         "admission never waits behind a long prompt")
    ap.add_argument("--expect-route-hits", type=float, default=None,
                    nargs="?", const=0.0, metavar="RATE",
                    help="exit nonzero unless at least one request was "
                         "routed to a replica's cached prefix (with a "
                         "value: unless the routing prefix hit-rate is "
                         ">= RATE)")
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--mi-continue", type=float, default=0.5)
    ap.add_argument("--mi-abstain", type=float, default=3.0)
    ap.add_argument("--escalate-samples", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    # -- observability ------------------------------------------------------
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the deterministic step-keyed request "
                         "log as JSONL to PATH (the engine's host spans on "
                         "the device clock go to a jax.profiler session's "
                         ".xplane.pb instead)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics payload (run metadata, the "
                         "summary, every lane's registry snapshot) as JSON")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="write every lane's registry in Prometheus text "
                         "exposition format (lane as a label)")
    # -- autoscheduler / warm-start fleet schedule DB -----------------------
    ap.add_argument("--schedule-db", default=None, metavar="PATH",
                    help="preload a persistent tuned-schedule DB "
                         "(repro.tuning fleet cache) before building the "
                         "engine, so every compile starts warm — no "
                         "schedule search on the serving hot path")
    ap.add_argument("--save-schedule-db", default=None, metavar="PATH",
                    help="record every (op, shape, dtype) the run consults, "
                         "tune any missing entry (cost-model 'rank' mode), "
                         "and merge-save the DB to PATH (atomic write; "
                         "concurrent replica writers lose nothing)")
    ap.add_argument("--expect-warm-cache", action="store_true",
                    help="exit nonzero if any tuning-cache consult missed "
                         "during the run (CI: prove a preloaded "
                         "--schedule-db covers the model's full shape set)")
    ap.add_argument("--fuse-ops", action="store_true",
                    help="enable the dispatch fusion pass: eligible "
                         "norm->dense->activation chains run as one fused "
                         "Pallas kernel when a tuned norm_dense_act "
                         "schedule is cached (falls back to the unfused "
                         "chain otherwise)")
    return ap.parse_args(argv)


def init_serving_params(cfg, seed: int, mesh):
    """Random PFP serving params for ``cfg``, placed on ``mesh`` by the
    serve sharding rules. Init and SVI->PFP conversion run as ONE jitted
    program, so the variational (mu, rho) tree never sits on the device
    beside the (mu, srm) tree that is served."""
    def init():
        return svi_to_pfp(lm.init_params(cfg, jax.random.PRNGKey(seed)))

    p_sh = shlib.params_shardings(jax.eval_shape(init), mesh, serve=True)
    return jax.jit(init, out_shardings=p_sh)()


@dataclasses.dataclass
class ServingParts:
    """What one serving run is built from: the engine builder, a trace
    factory and the router/scheduler they share."""
    build_engine: Callable[[int], Engine]   # speculate_k -> Engine
    make_trace: Callable[[], list]           # fresh list of Requests
    router: UncertaintyRouter
    sched_cfg: SchedulerConfig
    max_len: int


def serving_parts(args, cfg, params, mesh, tracer=None) -> ServingParts:
    """The serving path this driver runs, for ``args`` (from
    :func:`parse_args`): ``main`` and ``chip_smoke.py`` both build their
    engines here."""
    max_len = args.prompt_len + args.tokens
    router = UncertaintyRouter(
        cfg, RouterConfig(mi_continue=args.mi_continue,
                          mi_abstain=args.mi_abstain,
                          escalate_samples=args.escalate_samples),
        impl=args.impl)
    sched_cfg = SchedulerConfig(prefill_chunk=args.prefill_chunk,
                                prefill_budget=2 * args.prefill_chunk)
    scheduler = RequestScheduler(sched_cfg, max_len=max_len)

    def make_trace():
        # Regenerable: run_load mutates the Request objects, so the
        # speculative parity check below needs a fresh copy per engine.
        trace = poisson_trace(
            args.requests, args.rate, vocab_size=cfg.vocab_size,
            seed=args.seed,
            prompt_len=(max(2, args.prompt_len // 2), args.prompt_len),
            max_new_tokens=(max(1, args.tokens // 2), args.tokens))
        if args.common_prefix:
            # one fixed system prefix across the whole trace
            # (deterministic), so requests share their leading pages once
            # a donor finishes
            import numpy as np
            system = (np.arange(args.common_prefix, dtype=np.int32)
                      % cfg.vocab_size)
            for r in trace:
                n = min(args.common_prefix, len(r.prompt) - 1)
                r.prompt[:n] = system[:n]
        return trace

    def build_engine(speculate_k):
        engine = Engine(
            cfg, params,
            # bf16 activations, mirroring the decode_* dry-run programs
            # (serving/decode.py) whose executed version this driver is
            EngineConfig(slots=args.batch, max_len=max_len, impl=args.impl,
                         compute_dtype=jnp.bfloat16, seed=args.seed,
                         page_size=args.page_size,
                         page_budget=args.page_budget,
                         reserve_pages=not args.optimistic_pages,
                         auto_defrag=args.page_size is not None,
                         prefix_sharing=args.prefix_sharing,
                         prefix_retention_pages=args.prefix_retention,
                         speculate_k=speculate_k),
            router=router, scheduler=scheduler, mesh=mesh, tracer=tracer)
        # every prefill width compiled now, before any request is timed
        engine.warm_prefill()
        return engine

    return ServingParts(build_engine, make_trace, router, sched_cfg, max_len)


def _serve(args):
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
    else:
        dims = (1, jax.device_count())
    mesh = make_mesh(dims, ("data", "model"))
    cfg = get_config(args.arch) if args.full_size else reduced_config(args.arch)
    params = init_serving_params(cfg, args.seed, mesh)
    tracer = Tracer() if args.trace_out else None
    parts = serving_parts(args, cfg, params, mesh, tracer)
    build_engine, make_trace = parts.build_engine, parts.make_trace

    if args.replicas > 1:
        return _run_fleet(args, cfg, params, parts.router, parts.sched_cfg,
                          mesh, dims, parts.max_len, build_engine, make_trace,
                          tracer)

    with mesh:
        engine = build_engine(args.speculate)
        summary = run_load(engine, make_trace())
    _export_obs(args, engine, summary, tracer)

    layout = (f"paged/ps={args.page_size}" if args.page_size else "contiguous")
    if args.prefix_sharing:
        layout += "/prefix"
    if args.speculate:
        layout += f"/spec-k{args.speculate}"
    print(f"== engine summary ({cfg.name}, mesh={dims}, "
          f"impl={args.impl or 'default'}, kv={layout}) ==")
    keys = _SUMMARY_KEYS + (_MOE_KEYS if cfg.family == "moe" else ()) + \
        (_PAGED_KEYS if args.page_size else ()) + \
        (_PREFIX_KEYS if args.prefix_sharing else ()) + \
        (_SPEC_KEYS if args.speculate else ())
    for k in keys:
        v = summary[k]
        print(f"  {k:22s} {v:.4g}" if isinstance(v, float)
              else f"  {k:22s} {v}")
    # Diagnostics before the assertion-style invariant checks, so a CI
    # failure prints the readable ERROR line instead of a bare traceback.
    if engine.prefix is not None and \
            engine.prefix.pages_held > engine.prefix.retention_pages:
        print(f"ERROR: prefix index holds {engine.prefix.pages_held} pages "
              f"for finished lineages, beyond its retention of "
              f"{engine.prefix.retention_pages}", file=sys.stderr)
        return 1
    engine.pool.check_invariants()
    if engine.prefix is not None:
        engine.prefix.check_invariants(engine.pool)
    if summary["final_occupancy"] != 0:
        print("ERROR: slot pool leaked "
              f"{summary['final_occupancy']} slots", file=sys.stderr)
        return 1
    if args.page_size is not None:
        pool = engine.pool
        held = engine.prefix.pages_held if engine.prefix is not None else 0
        # Refcount-leak check, the paged analogue of the slot-leak check:
        # with every slot drained, the only legitimate references left
        # are the prefix index's holds — any page whose refcount is not
        # exactly its external-hold count leaked a reference (or was
        # freed with one outstanding).
        leaked = [p for p in range(1, pool.num_pages)
                  if pool.page_ref[p] != pool.external_holds[p]]
        if leaked:
            print(f"ERROR: page refcount leak on pages {leaked[:8]} "
                  f"({len(leaked)} total) after drain", file=sys.stderr)
            return 1
        if summary["final_live_pages"] != held:
            print("ERROR: page pool leaked "
                  f"{summary['final_live_pages'] - held} pages beyond the "
                  f"{held} prefix-index holds", file=sys.stderr)
            return 1
    if args.expect_defrag and summary["defrags"] == 0:
        print("ERROR: --expect-defrag but the run never defragged "
              "(page churn too low to exercise the paged pool)",
              file=sys.stderr)
        return 1
    if args.expect_moe_drop:
        if cfg.family != "moe":
            print(f"ERROR: --expect-moe-drop on a non-MoE arch "
                  f"({cfg.name} is family={cfg.family})", file=sys.stderr)
            return 1
        if summary["moe_assignments"] == 0:
            print("ERROR: --expect-moe-drop but the run recorded no MoE "
                  "routing assignments (aux accounting never reached the "
                  "engine metrics)", file=sys.stderr)
            return 1
        print(f"moe routing: {summary['moe_assignments']} assignments, "
              f"{summary['moe_held_assignments']} held, "
              f"{summary['moe_dropped_assignments']} dropped "
              f"(rate {summary['moe_drop_rate']:.4f}), "
              f"{summary['moe_padding_routed']} padding rows routed")
        if summary["moe_dropped_assignments"] or \
                summary["moe_padding_routed"]:
            print("ERROR: --expect-moe-drop: the serving path dropped an "
                  "assignment or routed a padding row", file=sys.stderr)
            return 1
    if args.expect_prefix_hits and summary["prefix_hits"] == 0:
        print("ERROR: --expect-prefix-hits but no admission mapped shared "
              "prefix pages (trace lacks a common prefix, or donors never "
              "finished before sharers arrived)", file=sys.stderr)
        return 1
    if args.speculate:
        # The speculative stream must serve exactly what plain decode
        # serves: tokens and finish reasons bit-for-bit; MI traces within
        # a float tolerance (a K-wide verify and a 1-wide decode pass
        # accumulate their gemms in different orders, and MI's entropy
        # cancellation amplifies those ulps to ~1e-7 — a real
        # verify/rollback bug moves MI by orders of magnitude more).
        import numpy as np
        with mesh:
            plain = build_engine(0)
            run_load(plain, make_trace())
        out = lambda e: {r.uid: (list(r.generated),  # noqa: E731
                                 [float(m) for m in r.mi_trace],
                                 r.finish_reason) for r in e.finished}
        got, want = out(engine), out(plain)
        same = set(got) == set(want) and all(
            (got[u][0], got[u][2]) == (want[u][0], want[u][2])
            and len(got[u][1]) == len(want[u][1])
            and np.allclose(got[u][1], want[u][1], rtol=0.0, atol=2e-5)
            for u in want)
        if not same:
            print("ERROR: speculative decode diverged from plain decode "
                  "(tokens differ, or MI traces beyond 2e-5)",
                  file=sys.stderr)
            return 1
        if args.expect_accept_rate is not None and \
                summary["draft_acceptance_rate"] < args.expect_accept_rate:
            print("ERROR: draft acceptance rate "
                  f"{summary['draft_acceptance_rate']:.3f} below the "
                  f"--expect-accept-rate {args.expect_accept_rate} floor",
                  file=sys.stderr)
            return 1
    print(f"served {summary['completed']} requests "
          f"({summary['tokens_generated']} tokens) — one PFP pass per decode "
          "step; escalations spent SVI samples only on gray-zone tokens.")
    return 0


def _tuning_epilogue(args, queries):
    """Post-run autoscheduler bookkeeping: prove the preloaded DB kept
    the hot path search-free (--expect-warm-cache) and/or persist what
    this run consulted (--save-schedule-db)."""
    from repro.tuning import cache as sched_cache

    counters = sched_cache.consult_counters()
    if args.schedule_db or args.expect_warm_cache or args.save_schedule_db:
        print(f"tuning-cache consults: {counters['consults']} "
              f"({counters['hits']} hits, {counters['misses']} misses)")
    if args.expect_warm_cache and counters["misses"] > 0:
        print("ERROR: --expect-warm-cache but the run missed the tuning "
              f"cache {counters['misses']} times (the schedule DB does not "
              "cover this model's shape set)", file=sys.stderr)
        return 1
    if args.save_schedule_db:
        from repro.tuning import measure as sched_measure

        cache = sched_cache.global_cache()
        tuned = 0
        for op, shape_key, dtype, backend in dict.fromkeys(queries or ()):
            if cache.get(op, shape_key, dtype, backend) is None:
                sched_measure.tune_into_cache(cache, op, shape_key, dtype,
                                              backend, mode="rank")
                tuned += 1
        path = cache.save(args.save_schedule_db)
        print(f"schedule-db: tuned {tuned} new entries, saved "
              f"{len(cache)} total -> {path}")
    return 0


def main():
    import contextlib

    args = parse_args()
    if args.devices:
        # Read when the CPU backend starts, i.e. at the first device query
        # below; accelerator backends ignore it.
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")
    from repro.core import dispatch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.tuning import cache as sched_cache

    enable_compile_cache()

    if args.fuse_ops:
        dispatch.set_fusion(True)
    if args.schedule_db:
        n = len(sched_cache.load_global_cache(args.schedule_db))
        print(f"schedule-db: preloaded {n} tuned entries "
              f"from {args.schedule_db}")
    # Scope the warm-start proof to this run's consults, not import-time
    # warmup some earlier code path may have done.
    sched_cache.consult_counters(reset=True)
    with contextlib.ExitStack() as stack:
        queries = (stack.enter_context(sched_cache.record_shapes())
                   if args.save_schedule_db else None)
        rc = _serve(args)
    if rc == 0:
        rc = _tuning_epilogue(args, queries)
    return rc


if __name__ == "__main__":
    sys.exit(main())
