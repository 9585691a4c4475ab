"""Serving-engine benchmark: continuous-batching throughput and latency.

Row families, emitted through benchmarks/common.py:

  serving/decode_step/...     median wall time of one lockstep engine
                              decode step (the whole slot batch) — the
                              engine's hot path, for the contiguous AND
                              the paged Gaussian KV-cache layout;
  serving/loadgen/...         an end-to-end Poisson loadgen run: derived
                              column carries throughput, p50/p99 latency,
                              abstention/escalation rates — paged runs add
                              page-occupancy, fragmentation and preemption
                              counts;
  serving/moe_decode/...      the MoE serving lift: the engine decode loop
                              on the reduced DeepSeek-MoE config (routed
                              top-k experts, aux-loss-free) — derived
                              carries the lockstep decode-step time, the
                              loadgen throughput and the expert-capacity
                              drop accounting (assignments / dropped /
                              drop rate);
  serving/obs_overhead/...    the observability acceptance row: the same
                              loadgen trace with tracing disabled and
                              with a live Tracer + exports — derived
                              carries the enabled/disabled elapsed
                              ratio, pinned < 1.05 under --full;
  serving/occupancy/...       the paged-memory acceptance row: a static
                              engine and a paged engine at the SAME
                              device-memory budget (equal KV rows) under
                              one overload trace — the paged engine
                              sustains strictly more concurrent slots;
  serving/prefix_reuse/...    the prefix-sharing acceptance row: M
                              requests with a common system prompt
                              through a paged engine WITH and WITHOUT the
                              refcounted copy-on-write prefix index —
                              decode bit-for-bit identical, prefill
                              tokens computed drop by >= the shared
                              fraction, and at an equal tight page budget
                              the sharing engine runs strictly more
                              requests concurrently;
  serving/speculative/...     the speculative-decoding acceptance row:
                              mean-only drafts verified by ONE chunked
                              PFP pass against plain paged decode on the
                              same trace — bit-for-bit identical tokens
                              (MI traces within a float tolerance; the
                              pass shapes differ) at < 1.0 full-PFP
                              passes per served token, plus the
                              batched-escalation pair (at most one SVI
                              pass per engine step, strictly fewer SVI
                              passes than sequential second opinions);
  serving/warm_start/...      the fleet warm-start acceptance row: a cold
                              replica (empty tuning cache, tune + persist
                              at startup) vs a warm replica preloading the
                              persisted fleet schedule DB — the derived
                              column carries the tuning-cache consult
                              counters proving zero schedule search on the
                              warm hot path, and the cold/warm
                              startup-to-first-decode wall times.

Quick profile: 32 requests; --full: the acceptance-criteria 200-request
run. ``python benchmarks/bench_serving.py --page-size 4 8 16`` sweeps
loadgen rows over page sizes. Deterministic seeds, so rows are comparable
across PRs. On the XLA stack these are real CPU timings; with ``run.py
--impl kernel`` they run the Pallas interpret path (correctness-only
off-TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, schedule_note, time_fn
from repro.bayes.convert import svi_to_pfp
from repro.configs import reduced_config
from repro.models import lm
from repro.serving.engine import (Engine, EngineConfig, Request,
                                  RequestScheduler, RouterConfig,
                                  SchedulerConfig, UncertaintyRouter,
                                  poisson_trace, run_load)
from repro.serving.fleet import Fleet, FleetConfig

ARCH = "granite-8b"
SLOTS = 4
MAX_LEN = 48
PAGE_SIZE = 8


def _build_engine(cfg, params, *, mi_continue=0.5, mi_abstain=3.0,
                  svi_mi_abstain=None, page_size=None, slots=SLOTS,
                  page_budget=None, reserve_pages=True, prefix_sharing=False,
                  speculate_k=0, batch_escalations=True, tracer=None,
                  impl=None):
    router = UncertaintyRouter(
        cfg, RouterConfig(mi_continue=mi_continue, mi_abstain=mi_abstain,
                          svi_mi_abstain=svi_mi_abstain,
                          escalate_samples=4))
    scheduler = RequestScheduler(
        SchedulerConfig(max_queue=256, prefill_chunk=8, prefill_budget=16),
        max_len=MAX_LEN)
    return Engine(cfg, params,
                  EngineConfig(slots=slots, max_len=MAX_LEN,
                               num_uncertainty_samples=16, seed=0,
                               impl=impl,
                               page_size=page_size, page_budget=page_budget,
                               reserve_pages=reserve_pages,
                               auto_defrag=page_size is not None,
                               prefix_sharing=prefix_sharing,
                               speculate_k=speculate_k,
                               batch_escalations=batch_escalations),
                  router=router, scheduler=scheduler, tracer=tracer)


def _decode_step_row(lines, cfg, params, *, page_size=None):
    engine = _build_engine(cfg, params, page_size=page_size)
    positions = np.full(engine.config.slots, 8, np.int32)
    if page_size is not None:
        for slot in range(engine.config.slots):
            engine.pool.alloc(1000 + slot)
            engine.pool.ensure_capacity(slot, 16)
    lm_mean, lm_var = engine.logit_buffers
    args = [params,
            jnp.zeros((engine.config.slots, 1), jnp.int32),
            jnp.asarray(positions[:, None]),
            jnp.asarray(positions + 1),
            jnp.ones((engine.config.slots,), bool),
            engine.pool.states]
    if page_size is not None:
        args.append(engine.pool.device_table())
    args += [lm_mean, lm_var]
    t_step = time_fn(engine.decode_fn, *args)
    name = ("decode_step" if page_size is None
            else f"decode_step_paged/ps{page_size}")
    lines.append(emit(
        f"serving/{name}/b{engine.config.slots}", t_step,
        f"tok_s={engine.config.slots / t_step:.1f}",
        schedule=schedule_note(engine.decode_fn, *args)))


def _moe_decode_row(lines, *, n_requests):
    """Uncertainty-aware MoE decode: the engine decode loop on the reduced
    DeepSeek-MoE config (routed top-k experts through the dropless
    grouped-expert kernel path on --impl kernel). The derived column
    carries one lockstep decode-step wall time plus the aux-loss-free
    routing accounting (assignments / dropped / drop rate) a loadgen run
    records through the moe_drop_rate gauge."""
    cfg = reduced_config("deepseek-moe-16b")
    params = svi_to_pfp(lm.init_params(cfg, jax.random.PRNGKey(0)))
    engine = _build_engine(cfg, params)
    positions = np.full(engine.config.slots, 8, np.int32)
    args = [params,
            jnp.zeros((engine.config.slots, 1), jnp.int32),
            jnp.asarray(positions[:, None]),
            jnp.asarray(positions + 1),
            jnp.ones((engine.config.slots,), bool),
            engine.pool.states,
            *engine.logit_buffers]
    t_step = time_fn(engine.decode_fn, *args)
    trace = poisson_trace(n_requests, rate=0.5, vocab_size=cfg.vocab_size,
                          seed=1, prompt_len=(4, 16), max_new_tokens=(2, 8))
    s = run_load(engine, trace)
    assert s["final_occupancy"] == 0, "slot leak in MoE loadgen run"
    assert s["moe_assignments"] > 0, "MoE decode recorded no routing aux"
    lines.append(emit(
        f"serving/moe_decode/b{engine.config.slots}", t_step,
        f"tok_s={engine.config.slots / t_step:.1f}"
        f";tput={s['throughput_tok_s']:.1f}tok_s"
        f";moe_assignments={s['moe_assignments']}"
        f";moe_dropped={s['moe_dropped_assignments']}"
        f";drop_rate={s['moe_drop_rate']:.4f}"
        f";experts={cfg.num_experts};top_k={cfg.top_k}",
        schedule=schedule_note(engine.decode_fn, *args)))


def _loadgen_row(lines, cfg, params, *, n_requests, page_size=None,
                 name=None):
    engine = _build_engine(cfg, params, page_size=page_size)
    # warm-up drains a small trace through the SAME engine first, so the
    # measured row reports hot-path throughput, not trace/compile time
    warm = poisson_trace(4, rate=0.5, vocab_size=cfg.vocab_size, seed=9,
                         prompt_len=(4, 16), max_new_tokens=(2, 8))
    run_load(engine, warm)
    engine.reset_metrics()
    trace = poisson_trace(n_requests, rate=0.5, vocab_size=cfg.vocab_size,
                          seed=1, prompt_len=(4, 16),
                          max_new_tokens=(2, 8))
    for r in trace:  # rebase arrivals onto the post-warm-up engine clock
        r.arrival += engine.now
    s = run_load(engine, trace)
    assert s["final_occupancy"] == 0, "slot leak in loadgen run"
    derived = (
        f"tput={s['throughput_tok_s']:.1f}tok_s"
        f";p50_s={s['p50_latency_s']:.3f};p99_s={s['p99_latency_s']:.3f}"
        f";p50_steps={s['p50_latency_steps']:.1f}"
        f";p99_steps={s['p99_latency_steps']:.1f}"
        f";abstain={s['abstain_rate']:.3f}"
        f";escalate={s['escalation_rate']:.3f}"
        f";occupancy={s['mean_occupancy']:.2f}")
    if page_size is not None:
        assert s["final_live_pages"] == 0, "page leak in loadgen run"
        derived += (
            f";page_occ={s['mean_page_occupancy']:.3f}"
            f";page_occ_peak={s['peak_page_occupancy']:.3f}"
            f";page_frag={s['mean_page_fragmentation']:.2f}"
            f";preempt={s['preemptions']};defrag={s['defrags']}")
    lines.append(emit(
        name or f"serving/loadgen/n{n_requests}"
        + ("" if page_size is None else f"/ps{page_size}"),
        s["elapsed_s"], derived))


def _obs_overhead_row(lines, cfg, params, *, n_requests, full):
    """Acceptance row: the SAME warmed Poisson loadgen run with tracing
    disabled (the default engine — every emit site sits behind an
    ``if tracer is not None``) and with a live Tracer attached, the
    JSONL log and the Prometheus text rendered afterwards. The
    derived column carries the enabled/disabled elapsed ratio; --full
    pins it < 1.05 (the quick profile is too short to time stably)."""
    from repro.obs.trace import Tracer

    trace_kw = dict(rate=0.5, vocab_size=cfg.vocab_size,
                    prompt_len=(4, 16), max_new_tokens=(2, 8))

    def run_one(tracer):
        engine = _build_engine(cfg, params, page_size=PAGE_SIZE,
                               tracer=tracer)
        run_load(engine, poisson_trace(4, seed=9, **trace_kw))
        engine.reset_metrics()
        trace = poisson_trace(n_requests, seed=1, **trace_kw)
        for r in trace:
            r.arrival += engine.now
        return engine, run_load(engine, trace)

    _, s_off = run_one(None)
    tracer = Tracer()
    eng_on, s_on = run_one(tracer)
    # export cost is real but off the serving path — rendered here so a
    # pathological exporter would still show up in the bench log
    n_events = len(tracer.events)
    tracer.to_jsonl()
    eng_on.metrics.registry.to_prometheus()
    ratio = s_on["elapsed_s"] / max(s_off["elapsed_s"], 1e-9)
    lines.append(emit(
        f"serving/obs_overhead/n{n_requests}/ps{PAGE_SIZE}",
        s_on["elapsed_s"],
        f"ratio={ratio:.3f};off_s={s_off['elapsed_s']:.3f}"
        f";on_s={s_on['elapsed_s']:.3f};events={n_events}"
        f";tput_on={s_on['throughput_tok_s']:.1f}tok_s"))
    if full:
        assert ratio < 1.05, (
            f"tracing overhead {ratio:.3f} >= 1.05 on the serving loadgen "
            "row — the observability layer is leaking into the hot path")


def _occupancy_row(lines, cfg, params, *, n_requests):
    """Acceptance row: equal device-memory budget (same number of KV rows),
    overload arrivals of short requests — the paged engine runs strictly
    more of them concurrently than the static per-slot layout can."""
    rows_budget = SLOTS * MAX_LEN          # KV rows the static layout pins
    trace_kw = dict(rate=4.0, vocab_size=cfg.vocab_size, seed=3,
                    prompt_len=(4, 8), max_new_tokens=(2, 4))
    static = _build_engine(cfg, params)
    s_static = run_load(static, poisson_trace(n_requests, **trace_kw))
    paged = _build_engine(
        cfg, params, page_size=PAGE_SIZE, slots=4 * SLOTS,
        page_budget=rows_budget // PAGE_SIZE)
    s_paged = run_load(paged, poisson_trace(n_requests, **trace_kw))
    assert s_paged["final_live_pages"] == 0
    lines.append(emit(
        f"serving/occupancy/rows{rows_budget}", s_paged["elapsed_s"],
        f"static_peak={s_static['peak_occupancy']}"
        f";paged_peak={s_paged['peak_occupancy']}"
        f";static_mean={s_static['mean_occupancy']:.2f}"
        f";paged_mean={s_paged['mean_occupancy']:.2f}"
        f";paged_page_occ={s_paged['mean_page_occupancy']:.3f}"
        f";pages={rows_budget // PAGE_SIZE}x{PAGE_SIZE}"))
    assert s_paged["peak_occupancy"] > s_static["peak_occupancy"], (
        "paged engine did not exceed the static layout's concurrency at "
        "equal memory")


def _system_prompt_trace(cfg, *, m, prefix_len, tail_len, max_new):
    """A warm-up donor plus ``m`` concurrent requests, all opening with
    one fixed system prompt. The donor arrives alone (step 0) and the
    sharers at step 1000 — far past its completion — so every sharer can
    map the donor's indexed prefix pages."""
    system = np.arange(1, prefix_len + 1, dtype=np.int32) % cfg.vocab_size
    reqs = [Request(uid=0,
                    prompt=np.concatenate(
                        [system, np.full(tail_len, 900, np.int32)]),
                    max_new_tokens=max_new, arrival=0.0)]
    for i in range(m):
        reqs.append(Request(
            uid=1 + i,
            prompt=np.concatenate(
                [system, np.full(tail_len, 901 + i, np.int32)]),
            max_new_tokens=max_new, arrival=1000.0))
    return reqs


def _prefix_reuse_row(lines, cfg, params, *, m=6):
    """Acceptance row: M requests with a common system prompt, with vs
    without the refcounted copy-on-write prefix index. Pinned here: (1)
    decode is bit-for-bit identical (tokens AND MI traces); (2) prefill
    tokens computed drop by at least the shared fraction; (3) at an equal
    TIGHT page budget the sharing engine runs strictly more requests
    concurrently (a shared page costs its budget once)."""
    ps = 8
    # Deliberately NOT page-aligned: the last shared page is partial, so
    # every sharer's first write copy-on-writes it (cow >= 1 below).
    prefix_len, tail_len, max_new = 3 * ps - 2, 6, 4
    tight = 12           # pages; each request alone needs 4 (32 tokens)

    def run_one(prefix_sharing, budget):
        eng = _build_engine(cfg, params, page_size=ps, slots=2 * SLOTS,
                            page_budget=budget,
                            prefix_sharing=prefix_sharing)
        s = run_load(eng, _system_prompt_trace(
            cfg, m=m, prefix_len=prefix_len, tail_len=tail_len,
            max_new=max_new))
        outs = {r.uid: (list(r.generated), [float(x) for x in r.mi_trace])
                for r in eng.finished}
        return s, outs

    # Reuse claim, roomy budget (no retention churn): every sharer maps
    # the full cached prefix, so prefill tokens computed drop by exactly
    # the shared fraction and decode stays bit-for-bit.
    s_cold, out_cold = run_one(False, None)
    s_share, out_share = run_one(True, None)
    assert out_share == out_cold, (
        "prefix-shared decode diverged from cold-prefill decode")
    saved = s_share["prefill_tokens_saved"]
    shared_frac = m * prefix_len / max(s_cold["prefill_tokens"], 1)
    drop = 1 - s_share["prefill_tokens"] / max(s_cold["prefill_tokens"], 1)
    assert drop >= shared_frac - 1e-9, (
        f"prefill tokens dropped {drop:.3f} < shared fraction "
        f"{shared_frac:.3f}")
    assert s_share["cow_copies"] >= 1, (
        "non-aligned shared prefix must trigger copy-on-write")
    # Concurrency claim, TIGHT equal budget: a shared page costs the
    # budget once, so the sharing engine admits strictly more requests
    # concurrently — and stays bit-for-bit even while its index reclaims
    # pages under pressure.
    t_cold, tout_cold = run_one(False, tight)
    t_share, tout_share = run_one(True, tight)
    assert tout_share == tout_cold, (
        "prefix sharing under page pressure diverged from cold decode")
    assert t_share["peak_occupancy"] > t_cold["peak_occupancy"], (
        "prefix sharing did not raise concurrency at equal page budget")
    lines.append(emit(
        f"serving/prefix_reuse/m{m}/ps{ps}", s_share["elapsed_s"],
        f"bitforbit=1;saved_tokens={saved}"
        f";frac_saved={s_share['prefill_frac_saved']:.3f}"
        f";hits={s_share['prefix_hits']}"
        f";shared_pages={s_share['prefix_shared_pages']}"
        f";cow={s_share['cow_copies']}"
        f";peak_cold={t_cold['peak_occupancy']}"
        f";peak_shared={t_share['peak_occupancy']}"
        f";pages={tight}x{ps}"))


def _speculative_row(lines, cfg, params, *, n_requests, k=4):
    """Acceptance row: uncertainty-speculative decoding (mean-only draft,
    ONE chunked PFP verify per block) against plain paged decode on the
    SAME Poisson trace. Pinned here: (1) token streams bit-for-bit
    identical, MI traces within MI_ATOL (the two sides run
    different-shaped passes — K-wide verify vs 1-wide decode, slot-wide
    batched SVI vs one-at-a-time — and gemm accumulation order is
    shape-dependent on this backend, which MI's entropy cancellation
    amplifies to ~1e-7); (2) < 1.0 full-PFP passes per served token on
    the low-uncertainty trace; (3) escalation amortization — a
    force-escalate pair where batched resolution spends at most ONE SVI
    pass per engine step and strictly fewer passes than the sequential
    second opinion."""
    MI_ATOL = 2e-5

    def same_stream(got, want, what):
        assert set(got) == set(want), f"{what}: request set diverged"
        for uid in want:
            g_tok, g_mi = got[uid]
            w_tok, w_mi = want[uid]
            assert g_tok == w_tok, f"{what}: uid {uid} tokens diverged"
            assert len(g_mi) == len(w_mi) and np.allclose(
                g_mi, w_mi, rtol=0.0, atol=MI_ATOL), (
                f"{what}: uid {uid} MI trace diverged beyond {MI_ATOL}")

    trace_kw = dict(rate=0.5, vocab_size=cfg.vocab_size, seed=5,
                    prompt_len=(4, 16), max_new_tokens=(2, 8))

    def run_one(n=n_requests, **ekw):
        eng = _build_engine(cfg, params, page_size=PAGE_SIZE, **ekw)
        s = run_load(eng, poisson_trace(n, **trace_kw))
        assert s["final_occupancy"] == 0, "slot leak in speculative run"
        assert s["final_live_pages"] == 0, "page leak in speculative run"
        outs = {r.uid: (list(r.generated), [float(x) for x in r.mi_trace])
                for r in eng.finished}
        return s, outs

    s_base, out_base = run_one()
    s_spec, out_spec = run_one(speculate_k=k)
    same_stream(out_spec, out_base, "speculative vs plain paged decode")
    assert s_spec["pfp_passes_per_token"] < 1.0, (
        f"speculation spent {s_spec['pfp_passes_per_token']:.2f} >= 1.0 "
        "full-PFP passes per served token")
    # Escalation amortization under a force-escalate router: sequential
    # second opinions pay one SVI pass per escalation, the batched pass
    # at most one per engine step — bit-for-bit identical streams.
    esc = dict(mi_continue=-1.0, mi_abstain=1e9, svi_mi_abstain=1e9)
    n_esc = max(n_requests // 2, 8)
    e_seq, out_seq = run_one(n_esc, batch_escalations=False, **esc)
    e_bat, out_bat = run_one(n_esc, **esc)
    same_stream(out_bat, out_seq, "batched vs sequential escalation")
    assert e_bat["max_svi_passes_per_step"] <= 1
    assert e_bat["svi_passes"] < e_seq["svi_passes"]
    lines.append(emit(
        f"serving/speculative/k{k}/ps{PAGE_SIZE}", s_spec["elapsed_s"],
        f"tok_bitforbit=1;mi_atol={MI_ATOL:g}"
        f";accept_rate={s_spec['draft_acceptance_rate']:.3f}"
        f";acc_per_verify={s_spec['accepted_tokens_per_verify']:.2f}"
        f";pfp_per_tok={s_spec['pfp_passes_per_token']:.3f}"
        f";base_pfp_per_tok={s_base['pfp_passes_per_token']:.3f}"
        f";spec_rounds={s_spec['spec_rounds']}"
        f";draft_passes={s_spec['draft_passes']}"
        f";svi_seq={e_seq['svi_passes']};svi_bat={e_bat['svi_passes']}"
        f";esc_batch={e_bat['mean_escalation_batch']:.2f}"
        f";max_svi_step={e_bat['max_svi_passes_per_step']}"))


def _fleet_trace(cfg, *, groups, m, prefix_len, tail_len, max_new):
    """``groups`` families of ``m`` requests, each family opening with its
    own fixed system prompt. Members arrive staggered, so while a late
    member's shadow prefill is mid-prompt, earlier members of the same
    family are decoding — the overlap the disaggregation row pins."""
    reqs = []
    uid = 0
    for g in range(groups):
        system = (np.arange(1, prefix_len + 1, dtype=np.int32)
                  + 100 * g) % cfg.vocab_size
        for i in range(m):
            reqs.append(Request(
                uid=uid,
                prompt=np.concatenate(
                    [system, np.full(tail_len, 800 + uid, np.int32)]),
                max_new_tokens=max_new, arrival=float(g + 3 * i)))
            uid += 1
    return sorted(reqs, key=lambda r: (r.arrival, r.uid))


def _fleet_row(lines, cfg, params, *, m=4):
    """Acceptance row: a 2-replica prefill/decode-disaggregated fleet
    against ONE engine on the same trace. Pinned here: (1) routed
    multi-replica decode is bit-for-bit the single engine's — tokens AND
    MI traces, exactly (every replica runs the baseline's pass shapes and
    sampling is keyed per (uid, token), so placement is invisible); (2)
    the prefix router lands >= 50% of requests on a replica that already
    caches their prefix; (3) decode steps proceed WHILE a peer prefill is
    mid-prompt (disaggregated admission never waits behind a long
    prompt); (4) every replica's pool drains without a page/hold leak."""
    ps = 4
    # Unique 9-token tails at prefill_chunk=4: a late member's shadow
    # prefill spans ~3 ticks while its family decodes 6 tokens.
    prefix_len, tail_len, max_new = 3 * ps + 2, 9, 6
    sched_cfg = SchedulerConfig(max_queue=256, prefill_chunk=4,
                                prefill_budget=8)
    router = UncertaintyRouter(
        cfg, RouterConfig(mi_continue=0.5, mi_abstain=3.0,
                          escalate_samples=4))
    ecfg = EngineConfig(slots=SLOTS, max_len=MAX_LEN,
                        num_uncertainty_samples=16, seed=0, page_size=ps,
                        auto_defrag=False, prefix_sharing=True)
    trace_kw = dict(groups=2, m=m, prefix_len=prefix_len,
                    tail_len=tail_len, max_new=max_new)

    def outs(finished):
        return {r.uid: (list(r.generated), [float(x) for x in r.mi_trace],
                        r.finish_reason) for r in finished}

    base = Engine(cfg, params, ecfg, router=router,
                  scheduler=RequestScheduler(sched_cfg, max_len=MAX_LEN))
    run_load(base, _fleet_trace(cfg, **trace_kw))
    want = outs(base.finished)

    fleet = Fleet(cfg, params, ecfg,
                  FleetConfig(replicas=2, disaggregate=True),
                  router=router, scheduler_config=sched_cfg)
    s = run_load(fleet, _fleet_trace(cfg, **trace_kw))
    got = outs(fleet.finished)
    assert got == want, (
        "routed fleet decode diverged from the single-engine baseline")
    assert s["route_hit_rate"] >= 0.5, (
        f"prefix routing hit-rate {s['route_hit_rate']:.2f} < 0.5")
    assert s["decode_steps_during_peer_prefill"] >= 1, (
        "no decode step overlapped a peer prefill — disaggregation never "
        "decoupled admission from prompt length")
    assert s["handoffs"] == len(want), "a prefill->decode handoff was lost"
    assert s["final_occupancy"] == 0, "fleet leaked occupied slots"
    for rep in fleet.replicas:
        rep.pool.check_invariants()
        rep.prefix.check_invariants(rep.pool)
        leaked = [p for p in range(1, rep.pool.num_pages)
                  if rep.pool.page_ref[p] != rep.pool.external_holds[p]]
        assert not leaked, f"page/hold leak after drain: {leaked}"
    lines.append(emit(
        f"serving/fleet/r2_disagg/ps{ps}", s["elapsed_s"],
        f"bitforbit=1;requests={len(want)}"
        f";route_hit_rate={s['route_hit_rate']:.3f}"
        f";route_hits={s['route_prefix_hits']}"
        f";fallbacks={s['route_fallbacks']}"
        f";handoffs={s['handoffs']}"
        f";p50_handoff={s['p50_handoff_steps']:.1f}"
        f";overlap_steps={s['decode_steps_during_peer_prefill']}"
        f";prefix_hit_rate={s['prefix_hit_rate']:.3f}"))


def _warm_start_row(lines, cfg, params):
    """Fleet warm-start acceptance row: a cold replica consults the tuning
    cache with nothing in it (every query a miss) and has to tune + persist
    at startup; a warm replica preloads the persisted fleet schedule DB and
    compiles straight through — the derived column carries the consult
    counters proving ZERO schedule search ran on the warm hot path, plus
    the cold/warm startup-to-first-decode wall times."""
    import os
    import tempfile
    import time as _time

    from repro.tuning import cache as tc
    from repro.tuning import measure as tm

    def first_decode(engine):
        b = engine.config.slots
        feed = jnp.zeros((b, 1), jnp.int32)
        pos = jnp.zeros((b, 1), jnp.int32)
        clen = jnp.zeros(b, jnp.int32)
        active = jnp.zeros(b, bool)
        jax.block_until_ready(engine.decode_fn(
            engine.params, feed, pos, clen, active, engine.pool.states,
            *engine.logit_buffers))

    tmp = tempfile.mkdtemp(prefix="repro-fleetdb-")
    db_path = os.path.join(tmp, "db.json")
    prev_path = os.path.join(tmp, "prev.json")
    # This row owns the global cache for its cold/warm halves; stash the
    # harness's warmed state (run.py --tune) and restore it after.
    tc.global_cache().save(prev_path, merge=False)
    try:
        # cold replica: every consult misses; tune what was consulted and
        # persist the DB (exactly what serve.py --save-schedule-db does)
        tc.reset_global_cache()
        t0 = _time.perf_counter()
        with tc.record_shapes() as queries:
            # the tuning cache only matters on the kernel stack; pin it so
            # the row is meaningful under the default (xla) harness impl
            engine = _build_engine(cfg, params, impl="kernel")
            first_decode(engine)
        t_cold_compile = _time.perf_counter() - t0
        cold = tc.consult_counters()
        cache = tc.global_cache()
        for op, shape_key, dtype, backend in dict.fromkeys(queries):
            if cache.get(op, shape_key, dtype, backend) is None:
                tm.tune_into_cache(cache, op, shape_key, dtype, backend,
                                   mode="rank")
        cache.save(db_path)
        t_cold = _time.perf_counter() - t0
        db_entries = len(cache)

        # warm replica: preload the fleet DB, compile straight through.
        # Drop the cold replica's jit caches first — a real warm replica
        # is a fresh process; without this the warm half would replay the
        # cold executables and never consult (or honestly recompile).
        jax.clear_caches()
        tc.reset_global_cache()
        t0 = _time.perf_counter()
        tc.load_global_cache(db_path)
        engine = _build_engine(cfg, params, impl="kernel")
        first_decode(engine)
        t_warm = _time.perf_counter() - t0
        warm = tc.consult_counters()
        assert warm["consults"] > 0 and warm["misses"] == 0, (
            f"warm replica missed the tuning cache {warm['misses']} of "
            f"{warm['consults']} consults — the fleet DB does not cover "
            "the decode shape set")
        lines.append(emit(
            f"serving/warm_start/b{engine.config.slots}", t_warm,
            f"cold_s={t_cold:.3f};cold_compile_s={t_cold_compile:.3f}"
            f";warm_s={t_warm:.3f}"
            f";startup_speedup={t_cold / max(t_warm, 1e-9):.2f}"
            f";consults={warm['consults']};hits={warm['hits']}"
            f";misses={warm['misses']};cold_misses={cold['misses']}"
            f";db_entries={db_entries}"))
    finally:
        tc.reset_global_cache()
        tc.load_global_cache(prev_path)


def run(quick: bool = True, page_sizes=None):
    lines = []
    cfg = reduced_config(ARCH)
    params = svi_to_pfp(lm.init_params(cfg, jax.random.PRNGKey(0)))
    n_requests = 32 if quick else 200

    # -- hot path: one lockstep decode step over the full slot batch -------
    _decode_step_row(lines, cfg, params)
    _decode_step_row(lines, cfg, params, page_size=PAGE_SIZE)

    # -- end-to-end: Poisson loadgen through the whole engine --------------
    _loadgen_row(lines, cfg, params, n_requests=n_requests)
    for ps in (page_sizes or (PAGE_SIZE,)):
        _loadgen_row(lines, cfg, params, n_requests=n_requests, page_size=ps)

    # -- uncertainty-aware MoE decode: routed experts + drop accounting ----
    _moe_decode_row(lines, n_requests=8 if quick else 32)


    # -- observability cost: tracing on vs off on one loadgen trace --------
    _obs_overhead_row(lines, cfg, params, n_requests=n_requests,
                      full=not quick)

    # -- equal-memory concurrency: static vs paged -------------------------
    _occupancy_row(lines, cfg, params, n_requests=n_requests)

    # -- prefix reuse: refcounted COW sharing vs cold prefill --------------
    _prefix_reuse_row(lines, cfg, params, m=6 if quick else 16)

    # -- speculative decode + amortized escalation -------------------------
    _speculative_row(lines, cfg, params,
                     n_requests=16 if quick else n_requests)

    # -- multi-replica disaggregated fleet vs single engine ----------------
    _fleet_row(lines, cfg, params, m=4 if quick else 8)

    # -- fleet warm-start: preloaded schedule DB, zero hot-path search -----
    _warm_start_row(lines, cfg, params)
    return lines


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--page-size", type=int, nargs="+", default=None,
                    help="sweep loadgen rows over these page sizes")
    args = ap.parse_args()
    run(quick=not args.full, page_sizes=args.page_size)
