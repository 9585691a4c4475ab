"""Quickstart: the paper's full pipeline in ~2 minutes on CPU.

1. SVI-train a Bayesian MLP on synthetic Dirty-MNIST   (paper §4)
2. Convert to a PFP deployment artifact                (mu, E[w^2]; §5)
3. One analytic forward pass -> predictions + calibrated uncertainty
4. Show OOD detection: texture images get high epistemic uncertainty.
5. Flip the same model onto the Pallas kernel path     (core/dispatch.py)
6. Autotune per-op kernel schedules for this model     (repro.tuning, §6)
7. Serve an LM through the continuous-batching engine  (repro.serving.engine)
8. Paged Gaussian KV-cache: page-pool decode memory     (EngineConfig(page_size=N))
9. Prefix sharing: refcounted copy-on-write pages for a shared system prompt
10. Speculative decoding gated by the PFP's own uncertainty  (repro.serving)
11. Fleet serving: two disaggregated replicas behind a prefix router
12. Observability: deterministic traces (Perfetto-viewable), metrics
    registry exports, profiler-clock spans, uncertainty telemetry (repro.obs)
13. Warm-start fleet schedule DB: tune once, persist, every replica
    serves warm with zero schedule search on the hot path (repro.tuning)
14. Uncertainty-aware MoE decode: routed top-k experts through the
    grid-level batched-expert kernel, drop accounting on the aux-loss-free
    path (nn/moe.py, kernels/pfp_moe.py)

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.bayes import metrics as bm
from repro.bayes.convert import svi_to_pfp
from repro.bayes.variational import KLSchedule
from repro.core.modes import Mode
from repro.data.dirty_mnist import batches, dirty_mnist
from repro.models.simple import mlp_forward, mlp_init
from repro.nn.module import Context
from repro.training.optimizer import Adam
from repro.training.train_loop import init_train_state, make_svi_train_step


def main():
    print("== 1. SVI training (ELBO + KL annealing, Adam) ==")
    (x_train, y_train), evals = dirty_mnist(n_train=1200, n_eval=300)
    params = mlp_init(jax.random.PRNGKey(0), d_hidden=64, sigma_init=1e-3)

    def fwd(p, batch, ctx):
        return mlp_forward(p, batch["x"], ctx), 0.0

    opt = Adam(learning_rate=3e-3)
    step = jax.jit(make_svi_train_step(
        fwd, opt, num_data=len(x_train),
        kl_schedule=KLSchedule(alpha_max=0.25, anneal_steps=150)))
    state = init_train_state(params, opt)
    for i, (bx, by) in enumerate(
            batches(x_train.reshape(-1, 784), y_train, 100, epochs=25)):
        state, m = step(state, {"x": jnp.asarray(bx),
                                "targets": jnp.asarray(by)},
                        jax.random.PRNGKey(i))
        if i % 100 == 0:
            print(f"  step {i:4d}  loss={float(m['loss']):.3f} "
                  f"nll={float(m['nll']):.3f} kl/n={float(m['kl']):.4f}")

    print("== 2. Convert SVI -> PFP (precompute E[w^2], calibrate) ==")
    pfp_params = svi_to_pfp(state.params, calibration_factor=1.0)

    print("== 3. Single probabilistic forward pass ==")
    ctx = Context(mode=Mode.PFP)
    for split in ("clean", "ambiguous", "ood"):
        imgs = evals[split][0]
        out = mlp_forward(pfp_params, jnp.asarray(imgs.reshape(-1, 784)), ctx)
        m = bm.pfp_predictive_metrics(jax.random.PRNGKey(1), out.mean,
                                      out.var, num_samples=50)
        labels = evals[split][1]
        acc = (np.asarray(m["pred"]) == labels).mean() if labels is not None \
            else float("nan")
        print(f"  {split:10s} acc={acc:.3f}  "
              f"total_unc={float(np.mean(m['total'])):.3f}  "
              f"aleatoric(SME)={float(np.mean(m['aleatoric'])):.3f}  "
              f"epistemic(MI)={float(np.mean(m['mi'])):.3f}")

    print("== 4. OOD detection (AUROC, paper Table 1) ==")

    def unc(split):
        imgs = evals[split][0]
        out = mlp_forward(pfp_params, jnp.asarray(imgs.reshape(-1, 784)), ctx)
        mm = bm.pfp_predictive_metrics(jax.random.PRNGKey(2), out.mean,
                                       out.var, 50)
        return np.asarray(mm["mi"])  # MI = the paper's OOD metric

    print(f"  AUROC(ood vs clean, MI) = "
          f"{bm.auroc(unc('ood'), unc('clean')):.3f}")

    print("== 5. Flipping the kernel path ==")
    # Every PFP op resolves through the impl-dispatch registry
    # (repro.core.dispatch): 'xla' runs the pure-jnp graph, 'kernel' the
    # Pallas TPU kernels (interpret mode off-TPU, so this works on CPU
    # too — slowly, as a correctness demonstration). Flip one forward via
    # the context...
    xs = jnp.asarray(evals["clean"][0][:32].reshape(-1, 784))
    out_k = mlp_forward(pfp_params, xs, Context(mode=Mode.PFP, impl="kernel"))
    out_x = mlp_forward(pfp_params, xs, Context(mode=Mode.PFP, impl="xla"))
    drift = float(jnp.max(jnp.abs(out_k.mean - out_x.mean)))
    print(f"  max |kernel - xla| logit mean drift: {drift:.2e}")
    # ...or flip the whole process when no explicit impl is set:
    from repro.core.dispatch import set_default_impl

    set_default_impl("kernel")
    try:
        out_default = mlp_forward(pfp_params, xs, Context(mode=Mode.PFP))
        print(f"  set_default_impl('kernel') forward ok "
              f"(var mean {float(jnp.mean(out_default.var)):.3e})")
    finally:
        set_default_impl("xla")

    print("== 6. Autotuning per-op schedules (paper §6) ==")
    # The kernel path above ran the fixed default block shapes. The tuner
    # discovers the model's actual (op, shape, dtype) set by tracing one
    # forward (zero FLOPs), searches each op's schedule space (wall clock
    # on TPU, cost-model ranking in interpret mode), and warms the
    # process-global schedule cache the dispatch registry consults.
    from repro.tuning import autotune
    from repro.tuning.cache import consult_digest, reset_global_cache

    chosen = autotune(mlp_forward, pfp_params, xs)
    for (op, shape_key, _, _), sched in chosen.items():
        print(f"  {op:12s} {str(shape_key):18s} -> {sched.describe()}")
    # The next kernel forward picks the tuned schedules up automatically...
    out_t = mlp_forward(pfp_params, xs, Context(mode=Mode.PFP, impl="kernel"))
    print(f"  cached-schedule forward ran: {consult_digest()}")
    # ...and stays at parity with the XLA stack.
    drift_t = float(jnp.max(jnp.abs(out_t.mean - out_x.mean)))
    print(f"  max |tuned kernel - xla| logit mean drift: {drift_t:.2e}")
    reset_global_cache()  # keep the demo hermetic
    # To persist: autotune(..., save_path='schedules.json') and later
    # repro.tuning.load_global_cache('schedules.json') (or run benchmarks
    # via `python benchmarks/run.py --tune --impl kernel`).

    print("== 7. Serving: uncertainty-aware continuous batching ==")
    # The engine (src/repro/serving/engine/, see its README.md) sustains a
    # request stream against a pooled decode batch: admission-controlled
    # scheduling, chunked prefill, ONE probabilistic pass per decode step
    # for the whole batch, and an uncertainty router that turns the free
    # per-token MI signal into continue / escalate-to-SVI / abstain.
    import dataclasses

    from repro.configs import reduced_config
    from repro.models import lm
    from repro.serving.engine import (Engine, EngineConfig, RouterConfig,
                                      UncertaintyRouter, poisson_trace,
                                      run_load)

    lm_cfg = dataclasses.replace(reduced_config("granite-8b"),
                                 sigma_init=5e-2)  # wide posteriors: the
    #                            router's gray zone actually gets traffic
    lm_params = svi_to_pfp(lm.init_params(lm_cfg, jax.random.PRNGKey(0)))
    engine = Engine(
        lm_cfg, lm_params,
        EngineConfig(slots=2, max_len=24, num_uncertainty_samples=16),
        router=UncertaintyRouter(lm_cfg, RouterConfig(
            mi_continue=0.02, mi_abstain=1.5, escalate_samples=4)))
    trace = poisson_trace(5, rate=0.7, vocab_size=lm_cfg.vocab_size,
                          seed=0, prompt_len=(3, 8), max_new_tokens=(2, 4))
    s = run_load(engine, trace)
    print(f"  served {s['completed']} requests / {s['tokens_generated']} "
          f"tokens in {s['steps']} engine steps "
          f"(abstained={s['abstained']}, escalations={s['escalations']})")
    print(f"  p50 latency {s['p50_latency_steps']:.1f} steps, slot pool "
          f"drained: final occupancy {s['final_occupancy']}")
    # `python -m repro.launch.serve --engine` runs this on a (data, model)
    # mesh; `python benchmarks/run.py --only serving` benchmarks it.

    print("== 8. Paged Gaussian KV-cache ==")
    # The same engine with EngineConfig(page_size=N) swaps the per-slot
    # max_len KV mean/variance buffers for a global pool of fixed-size
    # pages (uncertainty-aware paged attention: k_mu/v_mu/v_var page
    # together). Device memory then scales with cached TOKENS, not
    # slots*max_len — more concurrent requests per byte — and decode is
    # bit-for-bit identical to the contiguous layout.
    contiguous_tokens = {r.uid: list(r.generated) for r in engine.finished}
    paged_engine = Engine(
        lm_cfg, lm_params,
        EngineConfig(slots=2, max_len=24, num_uncertainty_samples=16,
                     page_size=4, auto_defrag=True),
        router=UncertaintyRouter(lm_cfg, RouterConfig(
            mi_continue=0.02, mi_abstain=1.5, escalate_samples=4)))
    trace = poisson_trace(5, rate=0.7, vocab_size=lm_cfg.vocab_size,
                          seed=0, prompt_len=(3, 8), max_new_tokens=(2, 4))
    sp = run_load(paged_engine, trace)
    paged_tokens = {r.uid: list(r.generated)
                    for r in paged_engine.finished}
    print(f"  paged (page_size=4) served the same tokens: "
          f"{paged_tokens == contiguous_tokens}")
    print(f"  page pool: peak occupancy "
          f"{sp['peak_page_occupancy']:.0%} of "
          f"{paged_engine.pool.total_pages} pages, "
          f"{sp['defrags']} defrags, {sp['preemptions']} preemptions, "
          f"drained to {sp['final_live_pages']} live pages")
    # `--page-size` on launch/serve.py and bench_serving.py drive this at
    # scale; the occupancy benchmark row shows the paged engine running
    # strictly more concurrent slots at equal device memory.

    print("== 9. Prefix sharing: copy-on-write pages for a system prompt ==")
    # PFP K/V rows are deterministic per (token, position), so requests
    # opening with the SAME system prompt would write identical leading
    # pages. With EngineConfig(prefix_sharing=True) the engine indexes
    # finished lineages' pages in a radix tree and maps them into new
    # requests at refcount+1: prefill runs only on the non-shared suffix
    # (bit-for-bit the same logits — paged attention reads through the
    # table), and a partially-shared boundary page is copied-on-write
    # before the first divergent token lands in it.
    system = np.arange(1, 13, dtype=np.int32)  # a 12-token "system prompt"

    def shared_trace():
        from repro.serving.engine import Request
        return [Request(uid=i,
                        prompt=np.concatenate(
                            [system, np.full(3, 40 + i, np.int32)]),
                        max_new_tokens=3, arrival=float(2 * i))
                for i in range(5)]

    def run_engine(prefix_sharing):
        eng = Engine(
            lm_cfg, lm_params,
            EngineConfig(slots=2, max_len=24, num_uncertainty_samples=16,
                         page_size=4, prefix_sharing=prefix_sharing),
            router=UncertaintyRouter(lm_cfg, RouterConfig(
                mi_continue=0.02, mi_abstain=1.5, escalate_samples=4)))
        summary = run_load(eng, shared_trace())
        return eng, summary

    cold_eng, cold = run_engine(False)
    shared_eng, sh = run_engine(True)
    same = ({r.uid: list(r.generated) for r in cold_eng.finished}
            == {r.uid: list(r.generated) for r in shared_eng.finished})
    print(f"  decode bit-for-bit vs cold prefill: {same}")
    print(f"  prefill tokens: cold={cold['prefill_tokens']} "
          f"shared={sh['prefill_tokens']} "
          f"(saved {sh['prefill_tokens_saved']}, "
          f"{sh['prefill_frac_saved']:.0%} of prefill FLOPs)")
    print(f"  prefix hits {sh['prefix_hits']} "
          f"(hit rate {sh['prefix_hit_rate']:.0%}), "
          f"{sh['cow_copies']} copy-on-write page copies, "
          f"{sh['final_prefix_held_pages']} pages retained for reuse")
    # `launch/serve.py --prefix-sharing --common-prefix K` runs this on a
    # mesh with refcount-leak checks; bench_serving's prefix_reuse row
    # pins the acceptance criteria (bit-for-bit + >= shared-fraction
    # prefill drop + more concurrency at equal page budget).

    print("== 10. Speculative decoding: draft with the mean, verify with "
          "one PFP pass ==")
    # With EngineConfig(speculate_k=K) each decode round drafts K-1 greedy
    # tokens with a mean-only (zero-variance) pass, then verifies the
    # whole block — head token + drafts — with ONE chunked PFP pass
    # through the paged cache. Verified tokens are served while they
    # match the draft and their MI stays under the CONTINUE gate, so one
    # full probabilistic pass amortizes over up to K served tokens. The
    # token stream is bit-for-bit plain decode (uncertainty sampling is
    # keyed per (request, token)); MI traces agree to float precision —
    # the K-wide verify pass accumulates its gemms in a different order
    # than the 1-wide decode pass. Narrow posteriors here keep the mean
    # draft on-distribution so acceptance stays high.
    spec_cfg = dataclasses.replace(lm_cfg, sigma_init=1e-3)
    spec_params = svi_to_pfp(lm.init_params(spec_cfg, jax.random.PRNGKey(0)))

    def run_spec(k):
        eng = Engine(
            spec_cfg, spec_params,
            EngineConfig(slots=2, max_len=24, num_uncertainty_samples=16,
                         page_size=4, speculate_k=k),
            router=UncertaintyRouter(spec_cfg, RouterConfig(
                mi_continue=0.02, mi_abstain=1.5, escalate_samples=4)))
        trace = poisson_trace(5, rate=0.7, vocab_size=spec_cfg.vocab_size,
                              seed=0, prompt_len=(3, 8),
                              max_new_tokens=(4, 8))
        summary = run_load(eng, trace)
        outs = {r.uid: (list(r.generated), [float(m) for m in r.mi_trace])
                for r in eng.finished}
        return outs, summary

    plain_out, plain_s = run_spec(0)
    spec_out, spec_s = run_spec(4)
    same_tokens = {u: v[0] for u, v in spec_out.items()} == \
        {u: v[0] for u, v in plain_out.items()}
    same_mi = all(np.allclose(spec_out[u][1], plain_out[u][1],
                              rtol=0.0, atol=2e-5) for u in plain_out)
    print(f"  speculative (K=4) vs plain decode: tokens bit-for-bit "
          f"{same_tokens}, MI traces within 2e-5 {same_mi}")
    print(f"  draft acceptance {spec_s['draft_acceptance_rate']:.0%}, "
          f"{spec_s['accepted_tokens_per_verify']:.1f} extra tokens per "
          f"verify pass")
    print(f"  full-PFP passes per served token: "
          f"plain={plain_s['pfp_passes_per_token']:.2f} -> "
          f"speculative={spec_s['pfp_passes_per_token']:.2f} (< 1.0: one "
          f"probabilistic pass now serves several tokens)")
    # `launch/serve.py --speculate K --expect-accept-rate R` runs this on
    # a mesh with a built-in parity check; bench_serving's speculative
    # row pins < 1.0 PFP passes per token plus the batched-escalation
    # amortization (at most one SVI pass per engine step).

    print("== 11. Fleet serving: two disaggregated replicas behind a "
          "prefix router ==")
    # A Fleet fronts R replicas with one admission router: each request
    # goes to the replica whose prefix index already caches the longest
    # prefix of its prompt (read-only peek, so routing never perturbs
    # retention), falling back to least-loaded. With disaggregate=True
    # each replica is a prefill engine + decode engine sharing one page
    # pool: the prompt prefills as a shadow request, the prefix index
    # takes refcounted holds on its pages, and the decode engine admits
    # the real request by mapping those pages — prefilling exactly ONE
    # token, so decode admission never waits behind a long prompt.
    # Every replica runs the single engine's pass shapes and sampling is
    # keyed per (request, token), so the routed fleet's tokens AND MI
    # traces are bit-for-bit a single engine's.
    from repro.serving.fleet import Fleet, FleetConfig

    def fleet_trace():
        from repro.serving.engine import Request
        system = np.arange(1, 10, dtype=np.int32)
        return [Request(uid=i,
                        prompt=np.concatenate(
                            [system, np.full(3, 50 + i, np.int32)]),
                        max_new_tokens=4, arrival=float(2 * i))
                for i in range(6)]

    fleet_ecfg = EngineConfig(slots=2, max_len=24,
                              num_uncertainty_samples=16, page_size=4,
                              prefix_sharing=True)
    fleet_router = UncertaintyRouter(spec_cfg, RouterConfig(
        mi_continue=0.02, mi_abstain=1.5, escalate_samples=4))
    single = Engine(spec_cfg, spec_params, fleet_ecfg, router=fleet_router)
    run_load(single, fleet_trace())
    fleet = Fleet(spec_cfg, spec_params, fleet_ecfg,
                  FleetConfig(replicas=2, disaggregate=True),
                  router=fleet_router)
    fs = run_load(fleet, fleet_trace())
    out = lambda e: {r.uid: (list(r.generated),  # noqa: E731
                             [float(m) for m in r.mi_trace])
                     for r in e.finished}
    print(f"  2-replica disaggregated fleet vs single engine: bit-for-bit "
          f"{out(fleet) == out(single)}")
    print(f"  routing: {fs['route_prefix_hits']} requests sent to a cached "
          f"prefix, {fs['route_fallbacks']} least-loaded fallbacks "
          f"(hit rate {fs['route_hit_rate']:.0%})")
    print(f"  disaggregation: {fs['handoffs']} prefill->decode handoffs, "
          f"p50 latency {fs['p50_handoff_steps']:.1f} steps, "
          f"{fs['decode_steps_during_peer_prefill']} decode steps served "
          f"during a peer prefill")
    # `launch/serve.py --replicas R --disaggregate` runs this on a mesh
    # with parity + page/hold-leak checks and a `--expect-route-hits`
    # floor; bench_serving's fleet row pins the acceptance criteria.

    print("== 12. Observability: request log, spans, metrics ==")
    # The whole serving stack instruments through repro.obs. A Tracer
    # records every lifecycle event keyed on (engine step, seq) — the
    # engine's step counter is the only time base, so two identical runs
    # produce byte-identical traces — and a fleet shares ONE tracer
    # across its frontend ('fleet' lane) and replicas ('r0.prefill',
    # 'r0.decode', ...). Metrics live in per-engine registries
    # (Counter/Gauge/Histogram families) with deterministic snapshots
    # and a Prometheus text export; escalations double as free
    # calibration audits (mi_ece) and high-MI tokens count OOD alarms.
    import json as _json

    from repro.obs.trace import Tracer

    tracer = Tracer()
    fleet2 = Fleet(spec_cfg, spec_params, fleet_ecfg,
                   FleetConfig(replicas=2, disaggregate=True),
                   router=fleet_router, tracer=tracer)
    os = run_load(fleet2, fleet_trace())
    events = tracer.events
    kinds = sorted({e["event"] for e in events})
    print(f"  {len(events)} trace events across "
          f"{len({e['lane'] for e in events})} lanes: {', '.join(kinds)}")
    # The same phases are host spans on the profiler's clock (pfp.step,
    # pfp.prefill_round, pfp.route, ...): run the fleet inside
    # jax.profiler.trace(dir) and the .xplane.pb holds them beside the
    # device's programs (create_perfetto_trace=True adds a Perfetto view).
    # Uncertainty telemetry rides the same summaries: band totals pool
    # across replicas by summation; calibration (mi_ece) stays per-engine
    # because an error RATE does not sum.
    dec_s = fleet2.replicas[0].decode_engine.metrics.summary()
    print(f"  router bands: continue={os['band_continue']} "
          f"escalate={os['band_escalate']} abstain={os['band_abstain']}, "
          f"ood_alarms={os['ood_alarms']}, "
          f"r0.decode mi_ece={dec_s['mi_ece']:.3f}")
    # Per-lane Prometheus export (one registry per engine):
    dec0 = fleet2.replicas[0].decode_engine.metrics.registry
    prom = dec0.to_prometheus(extra_labels={"lane": "r0.decode"})
    sample = [ln for ln in prom.splitlines()
              if ln.startswith("repro_tokens_generated")][0]
    print(f"  Prometheus sample: {sample}")
    _ = _json.dumps(tracer.events)  # the log is plain JSON
    # `launch/serve.py --trace-out t.jsonl --metrics-out m.json --prom-out
    # m.prom` exports all of this from a real run, and
    # `python -m repro.obs.validate` schema-checks the artifacts (the CI
    # obs-smoke job's gate).

    print("== 13. Warm-start fleet schedule DB: tune once, serve warm ==")
    # A fleet replica should never search schedules on its hot path. The
    # COLD replica records every (op, shape, dtype, backend) its forward
    # consults, tunes the missing entries (cost-model 'rank' mode here —
    # free; wall-clock on TPU) and atomically merge-saves the per-backend
    # DB — concurrent replicas flushing the same path merge instead of
    # corrupting each other. Every WARM replica preloads the DB and the
    # consult counters prove zero search ever ran.
    import os as _os
    import tempfile as _tempfile

    from repro.tuning import cache as sched_cache
    from repro.tuning import measure as sched_measure

    db_path = _os.path.join(_tempfile.mkdtemp(), "fleet_schedules.json")
    reset_global_cache()
    with sched_cache.record_shapes() as queries:  # --- the cold replica
        mlp_forward(pfp_params, xs, Context(mode=Mode.PFP, impl="kernel"))
    cold = sched_cache.consult_counters()
    cache = sched_cache.global_cache()
    for op, shape_key, dtype, backend in dict.fromkeys(queries):
        if cache.get(op, shape_key, dtype, backend) is None:
            sched_measure.tune_into_cache(cache, op, shape_key, dtype,
                                          backend, mode="rank")
    cache.save(db_path)  # temp-file + atomic rename, merge-on-conflict
    print(f"  cold replica: {cold['misses']} cache misses -> tuned and "
          f"saved {len(cache)} entries to {_os.path.basename(db_path)}")
    reset_global_cache()  # --- a warm replica is a fresh process
    sched_cache.load_global_cache(db_path)
    mlp_forward(pfp_params, xs, Context(mode=Mode.PFP, impl="kernel"))
    warm = sched_cache.consult_counters()
    print(f"  warm replica: {warm['consults']} consults = {warm['hits']} "
          f"hits + {warm['misses']} misses (zero schedule search)")
    assert warm["misses"] == 0, warm
    reset_global_cache()  # keep the demo hermetic
    # launch/serve.py wires this exact flow for real fleets:
    #   serve --impl kernel --fuse-ops --save-schedule-db db.json   (cold)
    #   serve --impl kernel --fuse-ops --schedule-db db.json \
    #         --expect-warm-cache                                   (warm)

    print("== 14. Uncertainty-aware MoE decode (DeepSeek-style routing) ==")
    # A Mixture-of-Experts LM through the same engine: the router picks
    # top-k experts per token on the MEAN path (deterministic control
    # flow), while the Gaussian moments ride through one grouped-expert
    # Pallas call per projection (kernels/pfp_moe.py — each row tile
    # runs Eq. 12 against its own expert). Serving runs the aux-loss-free
    # dropless path: no load-balance loss term in the graph, every
    # assignment computed, and the routing counters surface through the
    # engine's summary (moe_drop_rate reads 0).
    from repro.configs import reduced_config
    from repro.models import lm as lmmod
    from repro.serving.engine import (Engine, EngineConfig, RequestScheduler,
                                      RouterConfig, SchedulerConfig,
                                      UncertaintyRouter, poisson_trace,
                                      run_load)

    moe_cfg = reduced_config("deepseek-moe-16b")
    moe_params = svi_to_pfp(lmmod.init_params(moe_cfg, jax.random.PRNGKey(7)))
    engine = Engine(
        moe_cfg, moe_params,
        EngineConfig(slots=2, max_len=24, seed=0),
        router=UncertaintyRouter(moe_cfg, RouterConfig(escalate_samples=4)),
        scheduler=RequestScheduler(SchedulerConfig(prefill_chunk=8),
                                   max_len=24))
    summary = run_load(engine, poisson_trace(
        4, rate=0.5, vocab_size=moe_cfg.vocab_size, seed=3,
        prompt_len=(4, 8), max_new_tokens=(2, 4)))
    print(f"  served {summary['completed']} requests through "
          f"{moe_cfg.num_experts} experts (top-{moe_cfg.top_k}): "
          f"{summary['moe_assignments']:.0f} routed assignments, "
          f"{summary['moe_dropped_assignments']:.0f} dropped "
          f"(drop rate {summary['moe_drop_rate']:.3f})")
    assert summary["final_occupancy"] == 0  # the MoE pool drains too
    # `--arch deepseek-moe-16b --impl kernel` on launch/serve.py runs this
    # with the batched-expert kernel + tuned dense_batched schedules;
    # ModelConfig(moe_dispatch='a2a') flips dispatch/combine to explicit
    # shard_map all-to-alls on a (data, model) mesh (nn/moe.py).


if __name__ == "__main__":
    main()
