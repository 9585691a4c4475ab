"""The continuous-batching serving engine for PFP-BNN language models.

One ``Engine`` owns a fixed decode batch of ``slots`` sequences over a
single parameter pytree:

  submit -> scheduler (admission control, priority/deadline + aging;
            paged engines admit on PAGE budget, not slot count — with
            prefix sharing, a shared page costs the budget once)
         -> state pool (contiguous: zeroed per-slot KV mean/variance rows;
            paged: a page-table row over the shared refcounted Gaussian
            page pool; prefix sharing maps a cached prompt prefix's pages
            into the table at refcount+1 and copies-on-write the
            partially-shared boundary page)
         -> chunked prefill (budgeted prompt tokens per engine step;
            paged engines batch each round's chunks into ONE multi-slot
            pass; prefix-shared slots prefill only the non-shared suffix)
         -> lockstep PFP decode (ONE probabilistic pass per step for the
            whole batch: logit means + variances)
         -> uncertainty router (continue / escalate to SVI / abstain)
         -> eviction on completion or abstention (slot + pages return to
            the pool; optimistic page admission may PREEMPT the youngest
            slot when the pool runs dry — its request is requeued and
            later re-prefilled from prompt + generated, bit-identically)

Per-slot decode state stays on device for a request's whole lifetime; the
host only sees (B,)-sized tokens and mutual-information values each step.
Slots advance independently — each sits at its own position, admissions
and evictions happen mid-flight. The contiguous layout protects parked and
mid-prefill slots with the select-merge in ``models/lm.py``; the paged
layout needs no merge at all — writes from slots that must not advance are
redirected to the pool's trash page by the paged cache insert in
``nn/attention.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.gaussian import is_gaussian
from repro.core.modes import Mode
from repro.models import lm
from repro.nn.module import Context
from repro.obs.trace import Tracer, span
from repro.serving.batcher import Request
from repro.serving.decode import uncertainty_decode
from repro.serving.engine.metrics import EngineMetrics
from repro.serving.engine.prefix import PrefixIndex
from repro.serving.engine.router import (Decision, RouterConfig,
                                         UncertaintyRouter)
from repro.serving.engine.scheduler import (RequestScheduler, SchedulerConfig,
                                            pages_for)
from repro.serving.engine.state import DecodeStatePool, PagedDecodeStatePool

# A pass's MoE routing counters: EngineMetrics.on_moe argument -> the
# key of the models' aux dict (nn.moe.zero_aux) it reads.
_MOE_COUNTERS = {"assignments": "moe_assignments", "held": "moe_held",
                 "max_load": "moe_max_load", "touched": "moe_touched",
                 "pad_routed": "moe_pad_routed", "dropped": "moe_dropped"}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    slots: int = 4
    max_len: int = 64
    num_uncertainty_samples: int = 32
    greedy: bool = True
    eos_id: Optional[int] = None
    formulation: str = "srm"
    impl: Optional[str] = None     # 'xla' | 'kernel' | None = process default
    compute_dtype: Any = None      # None = f32 (CPU tests); serve uses bf16
    seed: int = 0
    auto_compact: bool = False     # contiguous: compact whenever fragmented
    # -- paged Gaussian KV-cache (attention-family models only) -------------
    page_size: Optional[int] = None  # None = contiguous per-slot layout
    page_budget: Optional[int] = None  # usable pages; None = slots *
    #                                    ceil(max_len / page_size) (the
    #                                    contiguous layout's capacity)
    reserve_pages: bool = True     # True: admission reserves the full
    #                                prompt+generation page need (never
    #                                preempts). False: optimistic — pages
    #                                are claimed on demand; exhaustion
    #                                preempts the youngest slot.
    auto_defrag: bool = False      # paged: defrag whenever fragmented
    # -- refcounted prefix sharing (paged engines only) ---------------------
    prefix_sharing: bool = False   # index finished lineages' pages and map
    #                                them copy-on-write into new requests
    #                                sharing a prompt prefix
    prefix_retention_pages: Optional[int] = None  # max pages the prefix
    #                                index may hold after their writers
    #                                finished; None = the whole page budget
    #                                (the index yields pages to admissions
    #                                on demand either way)
    # -- uncertainty-speculative decoding (paged engines only) --------------
    speculate_k: int = 0           # 0 = off. K >= 1: each decode round
    #                                drafts K-1 tokens with a mean-only
    #                                (zero-variance) pass, then verifies the
    #                                K-token block (served-but-unfed head +
    #                                drafts) with ONE chunked PFP pass and
    #                                serves the verified tokens greedily
    #                                while routing says CONTINUE
    batch_escalations: bool = True  # paged: resolve every slot the router
    #                                escalates in a step with ONE lockstep
    #                                N-sample SVI pass (per-(request, token)
    #                                keying makes each slot's second opinion
    #                                match the sequential calls — tokens
    #                                exactly, MI to float precision);
    #                                contiguous engines always go sequential


@dataclasses.dataclass
class _Slot:
    request: Request
    admit_seq: int
    phase: str = "prefill"         # 'prefill' -> 'decode'
    prefill_pos: int = 0
    last_input: Optional[int] = None  # token fed at the step behind the
    #                                   current logits (SVI replay input)
    # Escalation replay while the current logits come from a prefill
    # chunk: (pre-chunk substate, chunk inputs, out_idx). None once a
    # decode step ran — the engine then replays last_input against the
    # pre-decode pool snapshot instead.
    replay: Optional[tuple] = None
    # Tokens this slot prefills: the prompt, plus — after a preemption —
    # the tokens already generated (PFP K/V rows are deterministic per
    # (token, position), so re-prefilling prompt+generated reproduces the
    # evicted pages bit-for-bit and decode continues where it left off).
    prefill_tokens: Optional[np.ndarray] = None
    # First position this slot may WRITE: 0 for a cold slot; the matched
    # prefix length when admission mapped shared pages (rows below it are
    # already cached — the paged insert redirects re-fed writes there to
    # the trash page, and prefill starts here).
    write_start: int = 0
    # Speculative decode: the one token already SERVED (appended to
    # generated, MI recorded) but not yet fed — the head of the next
    # draft+verify block. None when the slot's current logits are fresh
    # (the next step routes them in phase 0 instead).
    pending: Optional[int] = None


# Jitted pass callables shared by every engine with an identical pass
# signature (model config, engine config, router thresholds, mesh) —
# engines reuse ONE set of traced/compiled executables instead of
# re-tracing per instance. Besides skipping recompilation for every
# fleet replica, this makes cross-engine bit-for-bit comparisons
# structural: a replica runs literally the same executables as the
# baseline engine it is checked against, so parity can never hinge on
# the toolchain reproducing identical float schedules across separate
# compilations of the same program.
_SHARED_PASSES: dict = {}


def clear_shared_pass_cache() -> None:
    """Drop the cross-engine jitted-pass cache (tests; frees the first
    owner engine each entry's bound passes keep alive)."""
    _SHARED_PASSES.clear()


class Engine:
    def __init__(self, cfg: ModelConfig, params,
                 config: EngineConfig = EngineConfig(), *,
                 router: Optional[UncertaintyRouter] = None,
                 scheduler: Optional[RequestScheduler] = None,
                 mesh=None, pool=None, prefix: Optional[PrefixIndex] = None,
                 tracer=None, lane: str = "engine"):
        """``pool``/``prefix`` inject SHARED decode state (disaggregated
        serving: a prefill engine and a decode engine over one page pool
        and one prefix index). The injecting owner is responsible for the
        pool's remap-listener wiring — an engine never registers a
        listener on a prefix it did not create, so one defrag remaps the
        shared index exactly once. Slot ownership stays disjoint: each
        engine only ever touches slots its own ``pool.alloc`` returned;
        a peer's slots are inactive rows in this engine's lockstep passes
        (their writes redirect to the trash page)."""
        if not cfg.embed_inputs:
            raise ValueError("engine serves token-prompt models only")
        self.cfg = cfg
        self.params = params
        self.config = config
        self.router = router if router is not None else UncertaintyRouter(
            cfg, RouterConfig(), formulation=config.formulation,
            impl=config.impl)
        self.scheduler = scheduler if scheduler is not None else \
            RequestScheduler(SchedulerConfig(), max_len=config.max_len)
        if self.scheduler.max_len is None:
            self.scheduler.max_len = config.max_len
        if self.scheduler.config.prefill_chunk > config.max_len:
            raise ValueError("prefill_chunk must not exceed max_len")
        # Attention-family models run every prefill chunk at ONE static
        # shape (a fixed-size window sliding to the chunk's end, re-feeding
        # earlier tokens — exact, since PFP k/v rows are deterministic per
        # (token, position)), so the chunk program compiles once. Models
        # with recurrent/SSM carries must see each token exactly once, so
        # they keep exact-length chunks (one trace per distinct length).
        self._static_chunks = all(k in ("attn", "moe", "cross")
                                  for k in cfg.pattern)
        self.paged = config.page_size is not None
        if pool is not None:
            if self.paged != isinstance(pool, PagedDecodeStatePool):
                raise ValueError("injected pool layout does not match "
                                 "config.page_size")
            if pool.num_slots != config.slots or \
                    pool.max_len != config.max_len or \
                    (self.paged and pool.page_size != config.page_size):
                raise ValueError("injected pool geometry does not match "
                                 "the engine config")
            self.pool = pool
        elif self.paged:
            if not self._static_chunks:
                raise ValueError(
                    "paged KV-cache serving supports attention-family "
                    "models only (recurrent/SSM carries have no positional "
                    "validity mask); use the contiguous layout for "
                    f"{cfg.name}")
            self.pool = PagedDecodeStatePool(
                cfg, config.slots, config.max_len, config.page_size,
                num_pages=config.page_budget, mesh=mesh)
        else:
            self.pool = DecodeStatePool(cfg, config.slots, config.max_len,
                                        mesh=mesh)
        self.prefix: Optional[PrefixIndex] = None
        if config.prefix_sharing:
            if not self.paged:
                raise ValueError("prefix_sharing requires the paged "
                                 "Gaussian KV-cache (set page_size)")
            if prefix is not None:
                # shared index: the owner registered the remap listener
                # ONCE — registering again would remap page ids twice per
                # defrag and corrupt the tree
                self.prefix = prefix
            else:
                retention = (config.prefix_retention_pages
                             if config.prefix_retention_pages is not None
                             else self.pool.total_pages)
                self.prefix = PrefixIndex(config.page_size, retention)
                # defrag moves a shared page once; the index's page ids
                # must follow the rewritten tables
                self.pool.add_remap_listener(self.prefix.remap_pages)
        elif prefix is not None:
            raise ValueError("injected prefix index requires "
                             "config.prefix_sharing")
        # (uid, pages, matched) of _page_need's latest index walk, reused
        # by the admission it gated
        self._prefix_match = None
        self.metrics = EngineMetrics()
        # OOD alarms threshold on the router's abstain bound unless the
        # router config pins an explicit one.
        self._ood_mi = (self.router.config.ood_mi
                        if self.router.config.ood_mi is not None
                        else self.router.config.mi_abstain)
        self.metrics.uncertainty.set_ood_threshold(self._ood_mi)
        # Structured tracing: ``tracer`` is a shared obs Tracer (bound to
        # this engine's ``lane``) or an already-bound LaneTracer. None =
        # tracing off — every emit site is guarded, so the disabled
        # engine pays nothing.
        self._tracer = (tracer.bind(lane) if isinstance(tracer, Tracer)
                        else tracer)
        self.finished: List[Request] = []
        self._slots: List[Optional[_Slot]] = [None] * config.slots
        # Pool states as of just BEFORE the latest lockstep decode step —
        # a reference swap, not a copy (the old buffers stay alive one
        # step). Escalation replays against this snapshot so recurrent/SSM
        # carries are not advanced twice.
        self._prev_states = None
        self._admit_seq = 0
        self._step_idx = 0
        # tokens served this step to requests that already had one (the
        # inter-token gaps a step closes; pfp.step's itl_tokens)
        self._itl_tokens = 0
        # MoE routing counters of prefill rounds not yet read (device
        # arrays; read in the route's next sync, _sync)
        self._moe_pending = []
        self._key_unc = jax.random.PRNGKey(config.seed)
        self._key_esc = jax.random.PRNGKey(config.seed + 1)
        v = cfg.vocab_size
        self._lm_mean = jnp.zeros((config.slots, v), jnp.float32)
        self._lm_var = jnp.zeros((config.slots, v), jnp.float32)
        if config.speculate_k:
            if config.speculate_k < 1:
                raise ValueError("speculate_k must be >= 1 (or 0 = off)")
            if not self.paged:
                raise ValueError(
                    "speculative decoding requires the paged Gaussian "
                    "KV-cache (set page_size): the chunked verify pass "
                    "leans on trash-page write redirection to leave "
                    "rejected rows rollback-free")
        # Test hook: fn((B, K-1) drafted tokens) -> replacement array.
        # Forcing drafts to always/never match the verified tokens pins the
        # acceptance extremes in the bit-for-bit parity tests.
        self._draft_override = None

        # Uncertainty sampling is keyed per (request uid, token index), NOT
        # per engine step: a request's MI trace (and sampled tokens, when
        # not greedy) is then invariant to WHEN its tokens decode — so
        # admission order, preemption/resume and prefix sharing (which all
        # shift schedules) cannot perturb routing decisions.
        def _unc_batch(lm_mean, lm_var, base_key, uids, tok_idx):
            def row(mean, var, uid, t):
                key = jax.random.fold_in(jax.random.fold_in(base_key, uid), t)
                out = uncertainty_decode(
                    mean[None, None], var[None, None], key,
                    num_uncertainty_samples=config.num_uncertainty_samples,
                    mi_threshold=self.router.config.mi_abstain,
                    greedy=config.greedy)
                return out.token[0], out.mutual_info[0]

            return jax.vmap(row)(lm_mean, lm_var, uids, tok_idx)

        # Block variant for speculative verify: (B, K, V) logit moments in,
        # (B, K) (token, mi) out. Row (b, i) runs the exact per-token
        # computation of ``_unc_batch`` under key fold_in(fold_in(base,
        # uid), tok0 + i) — the same per-(request, token) derivation — so
        # the verified trace reproduces decoding the block one token at a
        # time (tokens exactly; MI to float precision, since the K-wide
        # verify pass accumulates its gemms in a different order than the
        # 1-wide decode pass).
        def _unc_block_batch(lm_mean, lm_var, base_key, uids, tok0):
            def row(mean, var, uid, t0):
                def one(m, v, i):
                    key = jax.random.fold_in(
                        jax.random.fold_in(base_key, uid), t0 + i)
                    out = uncertainty_decode(
                        m[None, None], v[None, None], key,
                        num_uncertainty_samples=config.
                        num_uncertainty_samples,
                        mi_threshold=self.router.config.mi_abstain,
                        greedy=config.greedy)
                    return out.token[0], out.mutual_info[0]

                idx = jnp.arange(mean.shape[0], dtype=jnp.int32)
                return jax.vmap(one)(mean, var, idx)

            return jax.vmap(row)(lm_mean, lm_var, uids, tok0)

        # The non-speculative passes ignore speculate_k, so a plain engine
        # and a speculative engine that agree on everything else share
        # them; draft/verify close over speculate_k and are keyed by the
        # full config.
        common_sig = ("common", cfg,
                      dataclasses.replace(config, speculate_k=0),
                      self.router.config, mesh)
        shared = _SHARED_PASSES.get(common_sig)
        if shared is None:
            shared = {
                "chunk": jax.jit(self._chunk_step),
                "batch_chunk": jax.jit(self._batch_chunk_step),
                "decode": jax.jit(self._decode_step_paged if self.paged
                                  else self._decode_step),
                "set_row": jax.jit(
                    lambda buf, slot, row: buf.at[slot].set(row)),
                "unc": jax.jit(_unc_batch),
                "unc_block": jax.jit(_unc_block_batch),
            }
            _SHARED_PASSES[common_sig] = shared
        self._chunk_fn = shared["chunk"]
        self._batch_chunk_fn = shared["batch_chunk"]
        self._decode_fn = shared["decode"]
        self._set_row = shared["set_row"]
        self._unc = shared["unc"]
        self._unc_block = shared["unc_block"]
        spec_sig = ("spec", cfg, config, self.router.config, mesh)
        spec = _SHARED_PASSES.get(spec_sig)
        if spec is None:
            spec = {"draft": jax.jit(self._draft_steps),
                    "verify": jax.jit(self._verify_step)}
            _SHARED_PASSES[spec_sig] = spec
        self._draft_fn = spec["draft"]
        self._verify_fn = spec["verify"]

    # -- jitted device programs ---------------------------------------------
    def _ctx(self) -> Context:
        return Context(mode=Mode.PFP, formulation=self.config.formulation,
                       impl=self.config.impl,
                       compute_dtype=self.config.compute_dtype)

    def _split_logits(self, logits):
        if is_gaussian(logits):
            return logits.mean, logits.var
        return logits, jnp.zeros_like(logits)

    def _chunk_step(self, params, inputs, sub, out_idx):
        """One prefill chunk on a single-slot state view: (1, C) tokens in,
        logit (mean, var) at the last *real* token (``out_idx``) + updated
        substate out."""
        logits, new_sub = lm.decode_step(params, self.cfg, inputs, sub,
                                         self._ctx())
        mean, var = self._split_logits(logits)
        mean = jax.lax.dynamic_index_in_dim(mean, out_idx, 1, keepdims=False)
        var = jax.lax.dynamic_index_in_dim(var, out_idx, 1, keepdims=False)
        return (mean.astype(jnp.float32), var.astype(jnp.float32)), new_sub

    def _decode_step(self, params, tokens, positions, cache_len, active,
                     states, lm_mean, lm_var):
        """Lockstep decode for the whole slot batch + select-merge so only
        ``active`` slots observe the state/logit update. The 4th output is
        the MoE aux dict (drop accounting; zeros on dense families) from
        the aux-loss-free decode pass."""
        inputs = {"tokens": tokens, "positions": positions,
                  "cache_len": cache_len}
        logits, aux, new_states = lm.decode_step_with_aux(
            params, self.cfg, inputs, states, self._ctx())
        mean, var = self._split_logits(logits)
        mean = mean[:, -1].astype(jnp.float32)
        var = var[:, -1].astype(jnp.float32)
        merged = lm.select_decode_slots(new_states, states, active)
        return (jnp.where(active[:, None], mean, lm_mean),
                jnp.where(active[:, None], var, lm_var), merged, aux)

    def _decode_step_paged(self, params, tokens, positions, cache_len,
                           active, states, page_table, lm_mean, lm_var):
        """Lockstep decode over the shared page pool. No select-merge: an
        inactive slot's cache_len sits at its position, so the paged
        insert redirects its write to the trash page — the pool is only
        ever touched on ``active`` slots' own pages. The 4th output is the
        MoE aux dict (drop accounting; zeros on dense families)."""
        inputs = {"tokens": tokens, "positions": positions,
                  "cache_len": cache_len, "page_table": page_table}
        logits, aux, new_states = lm.decode_step_with_aux(
            params, self.cfg, inputs, states, self._ctx())
        mean, var = self._split_logits(logits)
        mean = mean[:, -1].astype(jnp.float32)
        var = var[:, -1].astype(jnp.float32)
        return (jnp.where(active[:, None], mean, lm_mean),
                jnp.where(active[:, None], var, lm_var), new_states, aux)

    def _batch_chunk_step(self, params, inputs, states, rows, out_idx, done,
                          lm_mean, lm_var):
        """One batched multi-slot prefill round over the page pool:
        (w, C) window tokens in, one row per planned slot (``rows``: the
        slot of each; pad rows carry the out-of-range slot count), each
        row's logit (mean, var) gathered at its own last-real-token index
        and scattered into its slot's row of the (slots, V) logit buffers
        only where ``done`` (prefill completed this round). The 4th
        output is the MoE aux dict (routing counters; zeros on dense
        families)."""
        logits, aux, new_states = lm.decode_step_with_aux(
            params, self.cfg, inputs, states, self._ctx())
        mean, var = self._split_logits(logits)
        mean = jnp.take_along_axis(
            mean.astype(jnp.float32), out_idx[:, None, None], axis=1)[:, 0]
        var = jnp.take_along_axis(
            var.astype(jnp.float32), out_idx[:, None, None], axis=1)[:, 0]
        dest = jnp.where(done, rows, lm_mean.shape[0])
        return (lm_mean.at[dest].set(mean, mode="drop"),
                lm_var.at[dest].set(var, mode="drop"), new_states, aux)

    def _draft_steps(self, params, head, positions, states, table):
        """K-1 mean-only (zero-variance) draft decode steps over the shared
        page pool: a ``lax.scan`` of :func:`lm.draft_decode_step`, each
        step feeding the previous argmax. Returns the (K-1, B) drafted
        tokens; the scanned state updates (det-mode k/v rows) are DISCARDED
        — only the verify pass's PFP rows ever reach ``pool.states``, so a
        draft can never leave zero-variance rows behind. Rows not drafting
        this round run at position 0 over their own (or the trash) pages;
        their proposals are ignored."""

        def body(carry, i):
            tok, st = carry
            inputs = {"tokens": tok[:, None],
                      "positions": (positions + i)[:, None],
                      "cache_len": positions + i + 1,
                      "page_table": table}
            logits, st = lm.draft_decode_step(params, self.cfg, inputs, st,
                                              self._ctx())
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, st), nxt

        _, drafts = jax.lax.scan(
            body, (head, states),
            jnp.arange(self.config.speculate_k - 1, dtype=jnp.int32))
        return drafts

    def _verify_step(self, params, inputs, states):
        """ONE chunked PFP pass over each slot's (B, K) speculative block —
        the chunked-prefill machinery pointed at decode: logit means AND
        variances for every block position, plus the pool with all fed
        rows' PFP k/v written (``cache_len`` bounds the writable window;
        pad rows land on the trash page). Rows the acceptance scan rejects
        need no rollback — the engine simply leaves ``positions`` at the
        accepted prefix, so stale rows stay masked until re-fed."""
        logits, new_states = lm.decode_step(params, self.cfg, inputs, states,
                                            self._ctx())
        mean, var = self._split_logits(logits)
        return (mean.astype(jnp.float32), var.astype(jnp.float32),
                new_states)

    # -- public API ---------------------------------------------------------
    def submit(self, req: Request) -> bool:
        with span("submit", uid=req.uid) as sp:
            ok = self.scheduler.submit(req, float(self._step_idx))
            sp.set_metadata(accepted=int(ok))
            self.metrics.on_submit(ok)
            if self._tracer is not None:
                self._tracer.emit(self._step_idx, "submit", uid=req.uid,
                                  accepted=ok, prompt_len=len(req.prompt),
                                  max_new=req.max_new_tokens)
        return ok

    def reset_metrics(self) -> None:
        """Fresh telemetry (e.g. after a warm-up run, so throughput rows
        measure the hot path instead of trace/compile time). Compiled
        programs and pool state are kept."""
        self.metrics = EngineMetrics()
        self.metrics.uncertainty.set_ood_threshold(self._ood_mi)

    @property
    def now(self) -> int:
        return self._step_idx

    @property
    def decode_fn(self):
        """The jitted lockstep decode program (public: benchmarks time it
        directly)."""
        return self._decode_fn

    @property
    def logit_buffers(self):
        """Current per-slot next-token logit (mean, var) device buffers."""
        return self._lm_mean, self._lm_var

    @property
    def active_slots(self) -> int:
        """Slots THIS engine owns (a shared pool's ``live`` also counts a
        disaggregated peer's slots; this never does)."""
        return sum(sl is not None for sl in self._slots)

    @property
    def prefilling(self) -> int:
        """This engine's slots still mid-prefill."""
        return sum(sl is not None and sl.phase == "prefill"
                   for sl in self._slots)

    @property
    def decoding(self) -> int:
        """This engine's slots in the decode phase."""
        return sum(sl is not None and sl.phase == "decode"
                   for sl in self._slots)

    @property
    def idle(self) -> bool:
        return len(self.scheduler) == 0 and self.active_slots == 0

    # -- fleet replica protocol ---------------------------------------------
    @property
    def load(self) -> int:
        """Queued + occupying work, the fleet router's fallback metric."""
        return len(self.scheduler) + self.active_slots

    def prefix_peek(self, tokens) -> int:
        """Cached-prefix length for the fleet router: how many leading
        tokens of ``tokens`` this engine's prefix index holds pages for
        (0 without an index). Read-only — never bumps the LRU clock. The
        limit mirrors admission's: the last token is always prefilled."""
        if self.prefix is None or len(tokens) == 0:
            return 0
        return self.prefix.peek(tokens, limit=len(tokens) - 1)

    def run_until_idle(self, max_steps: int = 100_000) -> dict:
        while not self.idle:
            if self._step_idx >= max_steps:
                raise RuntimeError(f"engine not idle after {max_steps} steps")
            self.step()
        return self.metrics.summary()

    # -- the engine step ----------------------------------------------------
    def step(self) -> None:
        with span("step", step=self._step_idx) as sp:
            self._itl_tokens = 0
            now = float(self._step_idx)
            # drain deadline-expired waiters even while the pool is full,
            # so they never hold the bounded admission queue against live
            # traffic
            for e in self.scheduler.drain_expired(now):
                self.metrics.on_expire()
                if self._tracer is not None:
                    self._tracer.emit(self._step_idx, "expire", uid=e.uid)
                self.finished.append(e)
            self._admit(now)
            rounds = self._prefill()
            self._route_and_decode(now)
            self._step_idx += 1
            if self.paged:
                pages = self.pool.page_gauges()
                if self.prefix is not None:
                    pages += (self.pool.shared_pages, self.prefix.pages_held)
                self.metrics.on_step(self.pool.live, pages=pages)
                if self.config.auto_defrag and \
                        self.pool.page_fragmentation():
                    self.defrag()
            else:
                self.metrics.on_step(self.pool.live)
                if self.config.auto_compact and self.pool.fragmentation():
                    self.compact()
            sp.set_metadata(rounds=rounds, itl_tokens=self._itl_tokens)

    def _request_tokens(self, req: Request) -> np.ndarray:
        tokens = np.asarray(req.prompt, np.int32)
        if req.generated:  # re-admission after a preemption
            tokens = np.concatenate(
                [tokens, np.asarray(req.generated, np.int32)])
        return tokens

    def _page_need(self, req: Request) -> int:
        """Pages an admission would actually take from the free list: the
        plain :func:`pages_for` budget minus the FULLY-shared prefix pages
        the index would map at refcount+1 (a shared page is already paid
        for once in the pool). A partially-matched boundary page still
        costs one page — its copy-on-write duplicate. The match is cached
        per uid so the admission that follows a successful pop reuses it
        instead of walking the radix tree a second time."""
        total = pages_for(req, self.pool.page_size,
                          reserve=self.config.reserve_pages)
        tokens = self._request_tokens(req)
        pages, matched = self.prefix.match(tokens, limit=len(tokens) - 1)
        self._prefix_match = (req.uid, pages, matched)
        return total - matched // self.pool.page_size

    def _admit(self, now: float) -> None:
        while self.pool.free_slots:
            if self.paged:
                req, expired = self.scheduler.pop_ready(
                    now, free_pages=self.pool.free_pages,
                    page_size=self.pool.page_size,
                    reserve_pages=self.config.reserve_pages,
                    page_need=(self._page_need if self.prefix is not None
                               else None))
            else:
                req, expired = self.scheduler.pop_ready(now)
            for e in expired:
                self.metrics.on_expire()
                if self._tracer is not None:
                    self._tracer.emit(self._step_idx, "expire", uid=e.uid)
                self.finished.append(e)
            if req is None:
                # The head may be blocked only by pages the prefix index
                # is holding for FINISHED lineages — reclaim LRU leaves
                # (skipping pages live slots still share) and retry.
                if (self.paged and self.prefix is not None
                        and len(self.scheduler)
                        and self.prefix.reclaim(self.pool, 1)):
                    continue
                break
            with span("admit", uid=req.uid) as sp:
                sp.set_metadata(slot=self._admit_request(req, now))

    def _admit_request(self, req: Request, now: float) -> int:
        """Give a popped request a slot: prefix pages mapped, its pages
        reserved. Returns the slot."""
        slot = self.pool.alloc(req.uid)
        tokens = self._request_tokens(req)
        sl = _Slot(request=req, admit_seq=self._admit_seq,
                   prefill_tokens=tokens)
        self._slots[slot] = sl
        if self.prefix is not None:
            # Map the cached prefix into this slot's table and prefill
            # only the non-shared suffix: paged attention reads through
            # the table indirection, so the logits are bit-for-bit a
            # cold prefill's. The limit keeps >= 1 token to prefill
            # (next-token logits come from feeding the last token).
            # pop_ready's _page_need already walked the index for this
            # request; reuse its match (nothing mutates in between).
            if self._prefix_match is not None and \
                    self._prefix_match[0] == req.uid:
                _, pages, matched = self._prefix_match
            else:
                pages, matched = self.prefix.match(
                    tokens, limit=len(tokens) - 1)
            self._prefix_match = None
            self.pool.share(slot, pages)
            self.pool.positions[slot] = matched
            sl.prefill_pos = matched
            sl.write_start = matched
            self.metrics.on_prefix(matched, len(pages))
        if self._tracer is not None:
            extra = ({"shared_pages": len(pages),
                      "matched_tokens": matched}
                     if self.prefix is not None else {})
            self._tracer.emit(self._step_idx, "admit", uid=req.uid,
                              slot=slot, **extra)
        if self.paged and self.config.reserve_pages:
            # pop_ready admitted against the free-page count (prefix
            # pages discounted), so reserving the full prompt +
            # generation need — including the eager copy-on-write of a
            # partially-shared boundary page — cannot fail.
            ok = self._ensure_pages(
                slot, len(req.prompt) + req.max_new_tokens)
            assert ok, "page reservation failed after admission check"
        self._admit_seq += 1
        self.metrics.on_admit(req.uid, req.arrival, now)
        return slot

    def _prefill_pending(self):
        pending = sorted(
            ((sl.admit_seq, slot) for slot, sl in enumerate(self._slots)
             if sl is not None and sl.phase == "prefill"))
        return [(slot, len(self._slots[slot].prefill_tokens)
                 - self._slots[slot].prefill_pos) for _, slot in pending]

    def _prefill(self) -> int:
        """Run this step's prefill plan; returns the rounds (device
        passes) it took."""
        if self.paged:
            return self._prefill_paged()
        plan = self.scheduler.plan_prefill(self._prefill_pending())
        c = self.scheduler.config.prefill_chunk
        for slot, n in plan:
            width = c if self._static_chunks else n
            with span("prefill_round", rows=n, slots=1, width=width):
                self._prefill_chunk(slot, n)
            self.metrics.on_prefill_round(width)
        return len(plan)

    def _prefill_chunk(self, slot: int, n: int) -> None:
        """One slot's next ``n`` prompt tokens as one single-slot pass."""
        sl = self._slots[slot]
        start = sl.prefill_pos
        end = start + n
        prompt = sl.prefill_tokens
        if self._static_chunks:
            # fixed-size window ending at `end`: one compiled shape.
            # Re-fed rows rewrite identical k/v; right-pad rows (only
            # while end < chunk) sit beyond cache_len, so they stay
            # masked until the decode loop overwrites them in the same
            # step their position becomes valid.
            c = self.scheduler.config.prefill_chunk
            lo = max(0, end - c)
            window = prompt[lo:end]
            tokens = np.zeros(c, np.int32)
            tokens[:len(window)] = window
            positions = lo + np.arange(c, dtype=np.int32)
            out_idx = len(window) - 1
        else:
            tokens = prompt[start:end]
            positions = start + np.arange(n, dtype=np.int32)
            out_idx = n - 1
        inputs = {
            "tokens": jnp.asarray(tokens)[None],
            "positions": jnp.asarray(positions)[None],
            "cache_len": jnp.asarray([end], jnp.int32),
        }
        sub = self.pool.take_slot(slot)
        (mean, var), new_sub = self._chunk_fn(
            self.params, inputs, sub, jnp.asarray(out_idx, jnp.int32))
        self.pool.write_slot(slot, new_sub)
        sl.prefill_pos += n
        self.pool.positions[slot] = sl.prefill_pos
        self.metrics.on_prefill(n)
        if self._tracer is not None:
            self._tracer.emit(self._step_idx, "prefill_round",
                              uid=sl.request.uid, slot=slot, tokens=n,
                              pos=sl.prefill_pos)
        if sl.prefill_pos == len(prompt):
            if sl.request.prefill_only:
                # disaggregation: the pages are the product — finish
                # without ever entering the decode phase
                self._finish(slot, "prefill", float(self._step_idx))
                return
            sl.phase = "decode"
            sl.last_input = int(prompt[-1])
            sl.replay = (sub, inputs, out_idx)
            self._lm_mean = self._set_row(self._lm_mean, slot, mean[0])
            self._lm_var = self._set_row(self._lm_var, slot, var[0])

    def _prefill_paged(self) -> int:
        """Batched multi-slot prefill over the shared page pool: every
        round of the scheduler's plan (at most one chunk per slot) runs as
        ONE lockstep pass over just its planned slots, padded to the
        power of two :meth:`_round_width` gives — at most log2(slots) + 1
        compiled shapes, all warmed by :meth:`warm_prefill`."""
        c = self.scheduler.config.prefill_chunk
        rounds = 0
        for rnd in self.scheduler.plan_prefill_rounds(self._prefill_pending()):
            with span("prefill_round") as sp:
                rows, k = self._prefill_round(rnd)
                width = self._round_width(k) * c if k else 0
                sp.set_metadata(rows=rows, slots=k, width=width)
            if k:
                self.metrics.on_prefill_round(width)
                rounds += 1
        return rounds

    def _round_width(self, k: int) -> int:
        """Batch rows of a prefill round carrying ``k`` slots: the least
        power of two >= k, at most the slot count."""
        return min(1 << (k - 1).bit_length(), self.config.slots)

    def _round_widths(self) -> List[int]:
        """Every width :meth:`_round_width` can give (the ladder)."""
        return sorted({self._round_width(k)
                       for k in range(1, self.config.slots + 1)})

    def warm_prefill(self) -> None:
        """Compile the prefill round at every width of the ladder, one
        pass each on pad rows only: cache_len 0 sends every write to the
        trash page, and ``done`` false leaves every logit row as it was,
        so the pool's pages 1 and up and the logit buffers come out
        bit-identical. The outputs are kept, so each later pass sees its
        arguments placed as a served round does; the first width runs
        again last, as a fresh pool and fresh logit buffers are placed
        otherwise than a pass's outputs. Contiguous engines prefill at one
        shape and need none of this."""
        if not self.paged:
            return
        widths = self._round_widths()
        for w in widths + widths[:1]:
            self._launch_round(self._pad_round(w))
        jax.block_until_ready(self._lm_mean)

    def _pad_round(self, w: int) -> dict:
        """Host inputs of a ``w``-row prefill round, every row a pad row:
        slot index ``slots`` (no logit row), cache_len 0 and a zero table
        row (writes reach only the trash page), ``done`` false."""
        c = self.scheduler.config.prefill_chunk
        return {"slots": np.full(w, self.config.slots, np.int32),
                "tokens": np.zeros((w, c), np.int32),
                "positions": np.tile(np.arange(c, dtype=np.int32), (w, 1)),
                "cache_len": np.zeros(w, np.int32),
                "write_start": np.zeros(w, np.int32),
                "out_idx": np.zeros(w, np.int32),
                "done": np.zeros(w, bool)}

    def _launch_round(self, r: dict):
        """One prefill pass over the host inputs ``r``
        (:meth:`_pad_round`'s keys); updates the pool and the logit
        buffers and returns the pass's MoE aux dict."""
        inputs = {k: jnp.asarray(r[k]) for k in
                  ("tokens", "positions", "cache_len", "write_start")}
        inputs["page_table"] = self.pool.device_table(r["slots"])
        self._lm_mean, self._lm_var, self.pool.states, aux = \
            self._batch_chunk_fn(self.params, inputs, self.pool.states,
                                 jnp.asarray(r["slots"]),
                                 jnp.asarray(r["out_idx"]),
                                 jnp.asarray(r["done"]),
                                 self._lm_mean, self._lm_var)
        return aux

    def _count_moe(self, counters, *, decode: bool) -> dict:
        """Passes' MoE routing counters, read from the device, into the
        metrics; returns them summed (the max load maxed) as span args:
        ``moe_<k>`` for a decode pass, ``moe_prefill_<k>`` for prefill
        rounds."""
        args = {}
        prefix = "moe_" if decode else "moe_prefill_"
        for c in counters:
            moe = {name: int(c[k]) for name, k in _MOE_COUNTERS.items()}
            self.metrics.on_moe(decode=decode, **moe)
            for name, v in moe.items():
                key = prefix + name
                args[key] = (max(args.get(key, 0), v) if name == "max_load"
                             else args.get(key, 0) + v)
        return args

    def _sync(self, *arrays):
        """The route's one transfer: ``arrays`` to the host, and with them
        the MoE counters of the prefill rounds since the last sync (kept
        on the device until now) into the metrics and the ``pfp.sync``
        span's args."""
        pending, self._moe_pending = self._moe_pending, []
        with span("sync") as sp:
            got, counters = jax.device_get(
                (arrays, [{k: a[k] for k in _MOE_COUNTERS.values()}
                          for a in pending]))
            sp.set_metadata(**self._count_moe(counters, decode=False))
        return [np.asarray(a) for a in got]

    def _prefill_round(self, rnd):
        """One round of the plan (``(slot, n)`` chunks) as one pass over
        its planned slots, at the width :meth:`_round_width` gives; pad
        rows carry slot index ``slots``, cache_len 0 and a zero table row,
        so their writes reach only the trash page and their logits no
        buffer row. Returns (prompt tokens carried, slots carried); (0, 0)
        = every planned slot was preempted, and nothing ran. An MoE
        pass's routing counters stay on the device until the route's next
        sync reads them (:meth:`_sync`)."""
        c = self.scheduler.config.prefill_chunk
        planned = []
        for slot, n in rnd:
            sl = self._slots[slot]
            if sl is None or sl.phase != "prefill":
                continue  # preempted as a page victim in this step
            end = sl.prefill_pos + n
            if not self._ensure_pages(slot, end) and \
                    not self._make_room(slot, end):
                # pool exhausted and nothing to preempt: bounce this
                # request back to the queue (it keeps its progress)
                self._preempt(slot)
                continue
            planned.append((slot, n, end))
        # A planned slot may have been preempted by a LATER slot's
        # _make_room in the same round: drop it and keep its logit rows
        # untouched.
        planned = [p for p in planned if self._slots[p[0]] is not None]
        if not planned:
            return 0, 0
        r = self._pad_round(self._round_width(len(planned)))
        for i, (slot, n, end) in enumerate(planned):
            sl = self._slots[slot]
            lo = max(0, end - c)
            window = sl.prefill_tokens[lo:end]
            r["slots"][i] = slot
            r["tokens"][i, :len(window)] = window
            r["positions"][i] = lo + np.arange(c, dtype=np.int32)
            r["cache_len"][i] = end
            # The window may re-feed tokens below the shared-prefix
            # boundary — their writes are redirected to the trash page
            # (the shared pages already hold the identical rows).
            r["write_start"][i] = sl.write_start
            r["out_idx"][i] = len(window) - 1
            r["done"][i] = end == len(sl.prefill_tokens)
        pre_states = self.pool.states  # escalation-replay snapshot
        #            (copy-on-write duplicates are already in it: every
        #            _ensure_pages above ran before this reference)
        aux = self._launch_round(r)
        if self.cfg.family == "moe":
            self._moe_pending.append(aux)
        rows = sum(n for _, n, _ in planned)
        if self._tracer is not None:
            self._tracer.emit(self._step_idx, "prefill_round",
                              slots=len(planned), tokens=rows)
        for i, (slot, n, end) in enumerate(planned):
            sl = self._slots[slot]
            sl.prefill_pos = end
            self.pool.positions[slot] = end
            self.metrics.on_prefill(n)
            if r["done"][i]:
                if sl.request.prefill_only:
                    self._finish(slot, "prefill", float(self._step_idx))
                    continue
                sl.phase = "decode"
                sl.last_input = int(sl.prefill_tokens[-1])
                # built on the host: slicing the device inputs would
                # compile one slice program per round width
                row = {k: jnp.asarray(r[k][i:i + 1]) for k in
                       ("tokens", "positions", "cache_len", "write_start")}
                row["page_table"] = self.pool.device_table(r["slots"][i:i + 1])
                sl.replay = (pre_states, row, int(r["out_idx"][i]))
        return rows, len(planned)

    def _route_current(self, decode_slots):
        """Route every listed slot's CURRENT logits: one keyed uncertainty
        pass + one (batched, when paged) SVI resolution of the slots the
        router escalates. Returns {slot: (token, mi, decision)}."""
        uids = np.zeros(self.config.slots, np.int32)
        tok_idx = np.zeros(self.config.slots, np.int32)
        for slot in decode_slots:
            req = self._slots[slot].request
            uids[slot] = req.uid & 0x7FFFFFFF
            tok_idx[slot] = len(req.generated)
        toks, mis = self._unc(self._lm_mean, self._lm_var, self._key_unc,
                              jnp.asarray(uids), jnp.asarray(tok_idx))
        toks, mis = self._sync(toks, mis)
        return self._resolve_escalations(decode_slots, toks, mis)

    def _serve_token(self, req: Request, tok: int, mi: float) -> None:
        """Serve one token: a token after the request's first closes an
        inter-token gap this step."""
        self._itl_tokens += bool(req.generated)
        req.generated.append(tok)
        req.mi_trace.append(mi)
        self.metrics.on_token()

    def _serve_routed(self, slot: int, tok: int, mi: float,
                      decision: Decision, now: float) -> bool:
        """Apply one routed token: abstain finishes the request; otherwise
        the token is served, and finishes it at EOS or its budget. True =
        the slot goes on with ``tok`` as its next input."""
        req = self._slots[slot].request
        if self._tracer is not None:
            self._tracer.emit(self._step_idx, "route", uid=req.uid,
                              token=tok, mi=mi, decision=decision.value,
                              tok_idx=len(req.generated))
        if decision is Decision.ABSTAIN:
            req.mi_trace.append(mi)
            req.abstained = True
            self._finish(slot, "abstain", now)
            return False
        self._serve_token(req, tok, mi)
        if self.config.eos_id is not None and tok == self.config.eos_id:
            self._finish(slot, "eos", now)
        elif len(req.generated) >= req.max_new_tokens:
            self._finish(slot, "length", now)
        else:
            return True
        return False

    def _route_and_decode(self, now: float) -> None:
        if self.config.speculate_k:
            self._route_and_decode_spec(now)
            return
        decode_slots = [slot for slot, sl in enumerate(self._slots)
                        if sl is not None and sl.phase == "decode"]
        if not decode_slots:
            return
        feed = np.zeros(self.config.slots, np.int32)
        active = np.zeros(self.config.slots, bool)
        with span("route", slots=len(decode_slots)):
            resolved = self._route_current(decode_slots)
            for slot in decode_slots:
                tok, mi, decision = resolved[slot]
                if self._serve_routed(slot, tok, mi, decision, now):
                    feed[slot] = tok
                    active[slot] = True
                    self._slots[slot].last_input = tok
        if active.any():
            with span("decode_step") as sp:
                n, moe = self._decode(feed, active)
                sp.set_metadata(active=n, **moe)

    def _decode(self, feed: np.ndarray, active: np.ndarray):
        """One lockstep decode pass feeding ``feed`` to the ``active``
        slots; returns how many it ran (fewer where page pressure
        preempted some, 0 = no pass) and its MoE counters, read in the
        transfer that follows it on MoE families (:meth:`_count_moe`)."""
        if self.paged:
            # Each active slot writes one KV row at its position this
            # step: make sure the covering page exists. Under optimistic
            # admission the pool can run dry — preempt the youngest slot
            # (vLLM-style) until it fits, or bounce the requester itself.
            for slot in np.flatnonzero(active):
                if self._slots[slot] is None:
                    continue  # preempted as a victim earlier in this loop
                pos = int(self.pool.positions[slot])
                if not self._ensure_pages(slot, pos + 1) and \
                        not self._make_room(slot, pos + 1):
                    self._preempt(slot)
            active &= np.asarray([sl is not None for sl in self._slots])
            if not active.any():
                return 0, {}
        positions = self.pool.positions.copy()
        self._prev_states = self.pool.states
        args = (self.params,
                jnp.asarray(feed[:, None]),
                jnp.asarray(positions[:, None]),
                jnp.asarray(positions + active),
                jnp.asarray(active),
                self.pool.states)
        if self.paged:
            self._lm_mean, self._lm_var, self.pool.states, aux = \
                self._decode_fn(*args, self.pool.device_table(),
                                self._lm_mean, self._lm_var)
        else:
            self._lm_mean, self._lm_var, self.pool.states, aux = \
                self._decode_fn(*args, self._lm_mean, self._lm_var)
        self.metrics.on_decode_pass()
        moe = {}
        if self.cfg.family == "moe":
            moe = self._count_moe(jax.device_get(
                [{k: aux[k] for k in _MOE_COUNTERS.values()}]), decode=True)
        n = int(active.sum())
        if self._tracer is not None:
            self._tracer.emit(self._step_idx, "decode_step", active=n)
        self.pool.positions[active] += 1
        for slot in np.flatnonzero(active):
            self._slots[slot].replay = None  # replay via _prev_states now
        return n, moe

    # -- uncertainty-speculative decoding ------------------------------------
    def _route_and_decode_spec(self, now: float) -> None:
        """The speculative decode round, replacing the one-token lockstep:

        phase 0  slots whose current logits are FRESH (just prefetched or
                 escalate-deferred) route them exactly like the plain
                 engine — serve/abstain/escalate under the same keys — but
                 a served token becomes the slot's ``pending`` head
                 instead of a one-token feed;
        phase 1  every slot holding a pending head drafts K-1 more tokens
                 with the mean-only pass and the whole (head + drafts)
                 block is verified by ONE chunked PFP pass; verified
                 tokens are served greedily while the router says
                 CONTINUE and the next draft matches.

        Every served (token, mi) is keyed per (request uid, token index),
        so the generated tokens are bit-for-bit the plain engine's (MI
        traces to float precision — the pass shapes differ) — speculation
        only changes how many full-PFP passes it takes to produce
        them."""
        decode_slots = [slot for slot, sl in enumerate(self._slots)
                        if sl is not None and sl.phase == "decode"]
        if not decode_slots:
            return
        route_slots = [s for s in decode_slots
                       if self._slots[s].pending is None]
        if route_slots:
            with span("route", slots=len(route_slots)):
                resolved = self._route_current(route_slots)
                for slot in route_slots:
                    tok, mi, decision = resolved[slot]
                    if self._serve_routed(slot, tok, mi, decision, now):
                        self._slots[slot].pending = tok
        spec_slots = [s for s in decode_slots
                      if self._slots[s] is not None
                      and self._slots[s].pending is not None]
        if spec_slots:
            self._speculative_round(spec_slots, now)

    def _speculative_round(self, spec_slots, now: float) -> None:
        """Draft K-1 tokens per pending slot, verify the K-token block
        with one chunked PFP pass, accept greedily."""
        with span("decode_step") as sp:
            k = self.config.speculate_k
            b = self.config.slots
            # Per-slot block width: the pending head plus up to K-1
            # drafts, clipped so a fully-accepted block lands exactly on
            # the request's generation budget (fed positions then never
            # pass max_len - 1).
            f_of = {}
            for slot in list(spec_slots):
                sl = self._slots[slot]
                if sl is None:
                    continue  # preempted as a page victim below
                req = sl.request
                # generation budget left (>= 1: a slot at its budget
                # finished in phase 0); a fully-accepted block lands
                # exactly on it
                f = min(k, req.max_new_tokens - len(req.generated))
                pos = int(self.pool.positions[slot])
                if not self._ensure_pages(slot, pos + f) and \
                        not self._make_room(slot, pos + f):
                    self._preempt(slot)
                    continue
                f_of[slot] = f
            live = [s for s in spec_slots
                    if self._slots[s] is not None and s in f_of]
            if not live:
                sp.set_metadata(active=0)
                return

            head = np.zeros(b, np.int32)
            pos0 = np.zeros(b, np.int32)
            for slot in live:
                head[slot] = self._slots[slot].pending
                pos0[slot] = self.pool.positions[slot]
            table = self.pool.device_table()
            drafts = np.zeros((b, max(k - 1, 0)), np.int32)
            if k > 1:
                drafts = np.asarray(self._draft_fn(
                    self.params, jnp.asarray(head), jnp.asarray(pos0),
                    self.pool.states, table)).T      # (K-1, B) -> (B, K-1)
                self.metrics.on_draft_pass(k - 1)
                if self._tracer is not None:
                    self._tracer.emit(self._step_idx, "spec_draft",
                                      slots=len(live), drafted=k - 1)
            if self._draft_override is not None:
                drafts = self._draft_override(drafts)

            # ONE chunked PFP verify pass over every block. Pad rows
            # (beyond a slot's width, or whole rows for slots not
            # speculating) carry positions at/past cache_len or an
            # all-trash table row, so the pool is only written through
            # live blocks' own pages.
            tokens = np.zeros((b, k), np.int32)
            positions = np.tile(np.arange(k, dtype=np.int32), (b, 1))
            cache_len = np.ones(b, np.int32)
            write_start = np.zeros(b, np.int32)
            vtable = np.zeros_like(self.pool.page_table)
            uids = np.zeros(b, np.int32)
            tok0 = np.zeros(b, np.int32)
            fed_of = {}
            for slot in live:
                sl = self._slots[slot]
                f = f_of[slot]
                fed = [int(head[slot])] + [int(t)
                                           for t in drafts[slot, :f - 1]]
                fed_of[slot] = fed
                tokens[slot, :f] = fed
                positions[slot] = pos0[slot] + np.arange(k, dtype=np.int32)
                cache_len[slot] = pos0[slot] + f
                write_start[slot] = sl.write_start
                vtable[slot] = self.pool.page_table[slot]
                uids[slot] = sl.request.uid & 0x7FFFFFFF
                tok0[slot] = len(sl.request.generated)
            inputs = {
                "tokens": jnp.asarray(tokens),
                "positions": jnp.asarray(positions),
                "cache_len": jnp.asarray(cache_len),
                "write_start": jnp.asarray(write_start),
                "page_table": jnp.asarray(vtable, jnp.int32),
            }
            vmean, vvar, self.pool.states = self._verify_fn(
                self.params, inputs, self.pool.states)
            self.metrics.on_verify_pass()
            sp.set_metadata(active=len(live))
        with span("route", slots=len(live)):
            btoks, bmis = self._unc_block(vmean, vvar, self._key_unc,
                                          jnp.asarray(uids), jnp.asarray(tok0))
            tok_np, mi_np = self._sync(btoks, bmis)

            drafted_total = accepted_total = 0
            for slot in live:
                sl = self._slots[slot]
                req = sl.request
                f = f_of[slot]
                fed = fed_of[slot]
                a = f                    # fed rows that stay valid
                finish_reason = None
                defer_row = None
                for i in range(f):
                    mi = float(mi_np[slot, i])
                    tok = int(tok_np[slot, i])
                    decision = self.router.route(mi)
                    if decision is not Decision.ESCALATE:
                        # An ESCALATE row is NOT counted here: it
                        # re-routes next step in phase 0 under the same
                        # (uid, token) key, and _resolve_escalations
                        # counts it there — band totals match the plain
                        # engine's exactly.
                        self.metrics.on_decision(mi, decision.value)
                    if self._tracer is not None:
                        self._tracer.emit(self._step_idx, "route",
                                          uid=req.uid, token=tok, mi=mi,
                                          decision=decision.value,
                                          tok_idx=len(req.generated),
                                          speculative=True)
                    if decision is Decision.ESCALATE:
                        # Stop UNSERVED: row i's logits become the slot's
                        # current logits and next step's phase 0 — same
                        # (uid, token) key, same MI — escalates them into
                        # that step's single batched SVI pass.
                        a = i + 1
                        sl.pending = None
                        sl.last_input = fed[i]
                        defer_row = i
                        break
                    if decision is Decision.ABSTAIN:
                        req.mi_trace.append(mi)
                        req.abstained = True
                        a = i + 1
                        finish_reason = "abstain"
                        break
                    self._serve_token(req, tok, mi)
                    if self.config.eos_id is not None and \
                            tok == self.config.eos_id:
                        a = i + 1
                        finish_reason = "eos"
                        break
                    if len(req.generated) >= req.max_new_tokens:
                        a = i + 1
                        finish_reason = "length"
                        break
                    if i + 1 < f and tok == fed[i + 1]:
                        continue     # draft confirmed; row i+1 stays valid
                    # Draft mismatch (or block exhausted): the verified
                    # token is served but unfed — it heads the next block.
                    # Rows past i are stale; they sit masked past
                    # ``positions`` until re-fed (rollback-to-trash, no
                    # device work).
                    a = i + 1
                    sl.pending = tok
                    sl.last_input = fed[i]
                    break
                drafted_total += f - 1
                accepted_total += a - 1
                self.pool.positions[slot] = int(pos0[slot]) + a
                sl.replay = None
                if defer_row is not None:
                    self._lm_mean = self._set_row(self._lm_mean, slot,
                                                  vmean[slot, defer_row])
                    self._lm_var = self._set_row(self._lm_var, slot,
                                                 vvar[slot, defer_row])
                if finish_reason is not None:
                    self._finish(slot, finish_reason, now)
            self.metrics.on_spec_round(drafted_total, accepted_total)
            if self._tracer is not None:
                self._tracer.emit(self._step_idx, "spec_verify",
                                  slots=len(live), drafted=drafted_total,
                                  accepted=accepted_total)

    # -- escalation ----------------------------------------------------------
    def _resolve_escalations(self, decode_slots, tok_np, mi_np):
        """Route each slot's (token, MI); resolve every ESCALATE with the
        SVI second opinion — ONE lockstep pass at slot width on paged
        engines (``batch_escalations``), a per-slot call otherwise.
        Returns {slot: (token, mi, decision)} with ESCALATE already
        replaced by the second opinion's CONTINUE/ABSTAIN."""
        out = {}
        esc = []
        for slot in decode_slots:
            mi = float(mi_np[slot])
            tok = int(tok_np[slot])
            decision = self.router.route(mi)
            self.metrics.on_decision(mi, decision.value)
            if decision is Decision.ESCALATE:
                esc.append(slot)
            else:
                out[slot] = (tok, mi, decision)
        if not esc:
            return out
        with span("escalate", slots=len(esc)):
            if self.paged and self.config.batch_escalations:
                out.update(self._escalate_batched(esc, tok_np, mi_np))
            else:
                for slot in esc:
                    out[slot] = self._escalate(slot, self._slots[slot],
                                               float(mi_np[slot]),
                                               int(tok_np[slot]))
        return out

    def _escalate_batched(self, esc_slots, pfp_tok_np, pfp_mi_np):
        """ONE lockstep N-sample SVI pass resolving every escalating
        slot's second opinion — the way batched prefill amortizes chunk
        passes. Every row replays the inputs that produced its current
        logits (the stored prefill chunk, or the last fed token padded to
        chunk width with masked rows) against the CURRENT pool: pages are
        refcounted and copy-on-write, so no other slot can have touched
        this slot's rows, and the replay functionally rewrites its own
        window before attending — bit-identical to the sequential replay
        against the pre-step snapshot. Returns {slot: (tok, mi,
        decision)}."""
        b = self.config.slots
        c = self.scheduler.config.prefill_chunk
        tokens = np.zeros((b, c), np.int32)
        positions = np.zeros((b, c), np.int32)
        cache_len = np.ones(b, np.int32)     # idle rows: one trash row
        write_start = np.zeros(b, np.int32)
        table = np.zeros_like(self.pool.page_table)
        uids = np.zeros(b, np.int32)
        tok_idx = np.zeros(b, np.int32)
        out_idx = np.zeros(b, np.int32)
        for slot in esc_slots:
            sl = self._slots[slot]
            self.metrics.on_escalation()
            sl.request.escalated += 1
            uids[slot] = sl.request.uid & 0x7FFFFFFF
            tok_idx[slot] = len(sl.request.generated)
            if sl.replay is not None:
                # logits came from a prefill chunk: replay its stored
                # (1, C) inputs verbatim (widths match — chunks ARE C)
                _, row, oi = sl.replay
                tokens[slot] = np.asarray(row["tokens"][0])
                positions[slot] = np.asarray(row["positions"][0])
                cache_len[slot] = int(np.asarray(row["cache_len"][0]))
                write_start[slot] = int(np.asarray(row["write_start"][0]))
                table[slot] = self.pool.page_table[slot]
                out_idx[slot] = oi
                continue
            # mid-decode: the trailing fed-token window (_replay_window),
            # the SAME construction the sequential path replays — the
            # window widths match, so the only accumulation difference
            # left is the batch width (ulp-level; tokens agree exactly,
            # MI to float precision)
            toks_w, pos_w, clen_w, oi = self._replay_window(slot, sl)
            tokens[slot] = toks_w[0]
            positions[slot] = pos_w[0]
            cache_len[slot] = clen_w[0]
            table[slot] = self.pool.page_table[slot]
            out_idx[slot] = oi
        inputs = {
            "tokens": jnp.asarray(tokens),
            "positions": jnp.asarray(positions),
            "cache_len": jnp.asarray(cache_len),
            "write_start": jnp.asarray(write_start),
            "page_table": jnp.asarray(table, jnp.int32),
        }
        stoks, smis = self.router.second_opinion_batched(
            self.params, inputs, self.pool.states, self._key_esc,
            uids, tok_idx, out_idx)
        self.metrics.on_svi_pass(len(esc_slots))
        stok_np = np.asarray(stoks)
        smi_np = np.asarray(smis)
        out = {}
        for slot in esc_slots:
            mi = float(smi_np[slot])
            decision = (Decision.ABSTAIN if mi >= self.router.svi_mi_abstain
                        else Decision.CONTINUE)
            stok = int(stok_np[slot])
            pfp_mi = float(pfp_mi_np[slot])
            pfp_tok = int(pfp_tok_np[slot])
            self.metrics.on_escalation_outcome(pfp_mi, pfp_tok, mi, stok,
                                               decision.value)
            if self._tracer is not None:
                self._tracer.emit(self._step_idx, "escalate",
                                  uid=self._slots[slot].request.uid,
                                  pfp_mi=pfp_mi, svi_mi=mi,
                                  agree=pfp_tok == stok,
                                  outcome=decision.value, batched=True)
            out[slot] = (stok, mi, decision)
        return out

    def _replay_window(self, slot: int, sl: _Slot):
        """Chunk-style SVI replay window for a mid-decode escalation: the
        last ``prefill_chunk`` fed tokens ending at the slot's position
        (right-padded past cache_len while fewer have been fed) — the SAME
        shape as a stored prefill-chunk replay. One window width keeps
        every escalation replay in one compiled program, which is what
        keeps the batched second opinion aligned with the sequential
        one: this backend's width-1 and width-C passes accumulate
        differently (the residual batch-width difference stays at ulp
        level). Hybrid (recurrent) models keep the exact one-token
        window — re-feeding consumed tokens would advance their carries
        twice — and they never take the batched path (it is paged-only).
        Returns (tokens (1, w), positions (1, w), cache_len (1,),
        out_idx)."""
        pos = int(self.pool.positions[slot])
        c = (self.scheduler.config.prefill_chunk
             if all(b == "attn" for b in self.cfg.pattern) else 1)
        lo = max(0, pos - c)
        window = self._request_tokens(sl.request)[lo:pos]
        tokens = np.zeros(c, np.int32)
        tokens[:len(window)] = window
        positions = lo + np.arange(c, dtype=np.int32)
        return (tokens[None], positions[None],
                np.asarray([pos], np.int32), len(window) - 1)

    def _replay_for(self, slot: int, sl: _Slot):
        """(substate, inputs, out_idx) reproducing the pass that made the
        slot's current logits: the pre-chunk snapshot + chunk inputs right
        after prefill, else the trailing fed-token window against the
        pre-decode pool. Paged engines replay against the WHOLE pre-step
        page pool (there is no per-slot state to extract) with the slot's
        page-table row doing the selection."""
        if sl.replay is not None:
            return sl.replay
        tokens, positions, cache_len, out_idx = self._replay_window(slot, sl)
        inputs = {
            "tokens": jnp.asarray(tokens),
            "positions": jnp.asarray(positions),
            "cache_len": jnp.asarray(cache_len),
        }
        if self.paged:
            inputs["page_table"] = self.pool.device_table(
                np.asarray([slot], np.int32))
            # Speculative mode replays against the CURRENT pool: the
            # verify pass writes several rows per step, so the pre-step
            # snapshot is missing this slot's accepted rows. The replay
            # functionally rewrites its whole window before attending and
            # masks everything past cache_len, so the states swap is exact.
            if self.config.speculate_k:
                return self.pool.states, inputs, out_idx
            return self._prev_states, inputs, out_idx
        sub = lm.take_decode_slots(self._prev_states,
                                   np.asarray([slot], np.int32))
        return sub, inputs, out_idx

    def _escalate(self, slot: int, sl: _Slot, pfp_mi: float, pfp_tok: int):
        """SVI second opinion for one gray-zone token. Returns the final
        (token, mi, decision): serve the SVI token, or abstain when the
        sampled ensemble is still uncertain."""
        self.metrics.on_escalation()
        sl.request.escalated += 1
        sub, inputs, out_idx = self._replay_for(slot, sl)
        # keyed per (request, token), like the PFP uncertainty sampling:
        # escalated second opinions are schedule-invariant too
        key = jax.random.fold_in(
            jax.random.fold_in(self._key_esc, sl.request.uid & 0x7FFFFFFF),
            len(sl.request.generated))
        stok, smi = self.router.second_opinion(
            self.params, inputs, sub, key, out_idx=out_idx)
        self.metrics.on_svi_pass(1)
        mi = float(smi)
        decision = (Decision.ABSTAIN if mi >= self.router.svi_mi_abstain
                    else Decision.CONTINUE)
        self.metrics.on_escalation_outcome(pfp_mi, pfp_tok, mi, int(stok),
                                           decision.value)
        if self._tracer is not None:
            self._tracer.emit(self._step_idx, "escalate", uid=sl.request.uid,
                              pfp_mi=pfp_mi, svi_mi=mi,
                              agree=pfp_tok == int(stok),
                              outcome=decision.value, batched=False)
        return int(stok), mi, decision

    def _finish(self, slot: int, reason: str, now: float) -> None:
        sl = self._slots[slot]
        sl.request.finish(reason)
        if self.prefix is not None:
            # Register the finished lineage: the index takes refcount
            # holds on the pages covering the rows actually written
            # (prompt + generated, minus the final token, which was never
            # fed), so future requests sharing the prefix map them instead
            # of recomputing. Retention is enforced inside insert.
            valid = int(self.pool.positions[slot])
            tokens = self._request_tokens(sl.request)[:valid]
            self.prefix.insert(tokens, self.pool.slot_pages[slot], self.pool)
        self.pool.evict(slot)
        self._slots[slot] = None
        self.finished.append(sl.request)
        self.metrics.on_finish(sl.request, now)
        if self._tracer is not None:
            self._tracer.emit(self._step_idx, "finish", uid=sl.request.uid,
                              reason=reason,
                              tokens=len(sl.request.generated))

    # -- paged page-pressure handling ---------------------------------------
    def _ensure_pages(self, slot: int, upto_len: int) -> bool:
        """Cover positions [0, upto_len) with pages the slot may WRITE:
        capacity (allocate missing pages) plus, under prefix sharing,
        copy-on-write of any still-shared page at or past the slot's
        write_start. False = the free list cannot supply the pages."""
        if not self.pool.ensure_capacity(slot, upto_len):
            return False
        if self.prefix is None:
            return True
        sl = self._slots[slot]
        before = self.pool.cow_copies
        if not self.pool.ensure_writable(slot, sl.write_start, upto_len):
            return False
        copied = self.pool.cow_copies - before
        self.metrics.on_cow(copied)
        if copied and self._tracer is not None:
            self._tracer.emit(self._step_idx, "cow", uid=sl.request.uid,
                              pages=copied)
        return True

    def _preempt(self, slot: int) -> None:
        """Evict ``slot`` mid-flight and requeue its request (with its
        generated tokens — re-prefilling prompt+generated reproduces the
        freed pages bit-for-bit, so decode resumes where it stopped).
        Pages other slots share (or the index holds) survive the evict —
        only this slot's references are released."""
        sl = self._slots[slot]
        self.pool.evict(slot)
        self._slots[slot] = None
        self.metrics.on_preemption()
        if self._tracer is not None:
            self._tracer.emit(self._step_idx, "preempt", uid=sl.request.uid,
                              generated=len(sl.request.generated))
        displaced = self.scheduler.requeue(sl.request, float(self._step_idx))
        if displaced is not None:
            # the requeue displaced the newest un-started waiter to keep
            # the queue depth bounded; account it like a rejection
            self.metrics.on_requeue_overflow()
            if self._tracer is not None:
                self._tracer.emit(self._step_idx, "requeue_overflow",
                                  uid=displaced.uid)
            self.finished.append(displaced)

    def _make_room(self, for_slot: int, upto_len: int) -> bool:
        """Free pages for ``for_slot``: first reclaim prefix-index holds
        on finished lineages (cache eviction beats preemption — nobody is
        computing on those pages), then preempt JUNIOR live slots
        (admitted after it), youngest first, until the capacity fits.
        Youngest-first preserves the scheduler's seniority order under
        page pressure — the same rule vLLM's recompute preemption uses —
        so when ``for_slot`` is itself the youngest there is nobody it
        may evict: return False and let the caller bounce the requester
        instead of inverting seniority."""
        my_seq = self._slots[for_slot].admit_seq
        while not self._ensure_pages(for_slot, upto_len):
            if self.prefix is not None and self.prefix.reclaim(self.pool, 1):
                continue
            victims = [s for s, sl in enumerate(self._slots)
                       if sl is not None and sl.admit_seq > my_seq]
            if not victims:
                return False
            self._preempt(max(victims,
                              key=lambda s: self._slots[s].admit_seq))
        return True

    def defrag(self) -> None:
        """Pack live pages to the pool front; keep the escalation-replay
        snapshot page-aligned with the rewritten tables."""
        if not self.paged:
            raise ValueError("defrag() applies to the paged engine; the "
                             "contiguous engine compacts slots instead")
        with span("defrag"):
            perm = self.pool.defrag()
            if perm is None:
                return
            self.metrics.on_defrag()
            if self._tracer is not None:
                self._tracer.emit(self._step_idx, "defrag")
            if self._prev_states is not None:
                self._prev_states = lm.take_decode_slots(self._prev_states,
                                                         perm)

    def compact(self) -> None:
        """Pack live slots to the front; remap host-side slot records and
        the per-slot logit rows to match."""
        if self.paged:
            raise ValueError("the paged engine has no slot compaction "
                             "(slots are just batch rows); use defrag()")
        with span("defrag"):
            remap = self.pool.compact()
            if not remap:
                return
            new_slots: List[Optional[_Slot]] = [None] * self.config.slots
            perm = np.arange(self.config.slots)
            for old, new in remap.items():
                new_slots[new] = self._slots[old]
                perm[new] = old
            self._slots = new_slots
            self._lm_mean = self._lm_mean[jnp.asarray(perm)]
            self._lm_var = self._lm_var[jnp.asarray(perm)]
            if self._prev_states is not None:
                # keep the escalation-replay snapshot slot-aligned (free
                # rows may duplicate — replay only ever reads live slots)
                self._prev_states = lm.take_decode_slots(self._prev_states,
                                                         perm)
