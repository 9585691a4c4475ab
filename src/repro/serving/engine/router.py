"""Uncertainty routing: serve, escalate, or abstain per decoded token.

The PFP serve step hands the router a per-token mutual-information signal
for free (one analytic pass -> logit means AND variances -> MI). The
router turns it into a serving policy with two thresholds:

    MI <= mi_continue                  CONTINUE  serve the PFP token
    mi_continue < MI < mi_abstain      ESCALATE  run an N-sample SVI
                                                  second-opinion pass
    MI >= mi_abstain                   ABSTAIN   evict ("I don't know")

Escalation is the paper's SVI-vs-PFP ablation recast as a serving policy:
for the gray zone between "confident" and "hopeless", spend N sampled
forward passes (what every token would cost under an SVI server) to get a
reference MI and token. If the SVI second opinion is still uncertain
(``svi_mi_abstain``) the request abstains; otherwise the SVI token is
served. The fallback replays the slot's last input token against a copy
of its decode state, so the pooled KV buffers are never perturbed.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import jax
import jax.numpy as jnp

from repro.bayes.metrics import (predictive_metrics_from_sample_rows,
                                 predictive_metrics_from_samples)
from repro.configs.base import ModelConfig
from repro.core.gaussian import is_gaussian
from repro.core.modes import Mode
from repro.models import lm
from repro.nn.module import Context


class Decision(enum.Enum):
    CONTINUE = "continue"
    ESCALATE = "escalate"
    ABSTAIN = "abstain"


# Process-global cache of compiled SVI second-opinion programs, keyed by
# (variant, cfg, samples, formulation, impl). Every UncertaintyRouter used
# to build (and jit) its own fallback closure, so each new engine — and
# each test building several engines over one model — re-traced and
# re-compiled an identical program. One jitted fn per key fixes that; the
# call WIDTH (the replayed inputs' (1, 1) vs (1, chunk) shape) is the
# remaining cache dimension, and jit's own shape-keyed executable cache
# covers it — so steady-state escalations never retrace.
_FALLBACK_CACHE: dict = {}


def svi_fallback_cache_clear() -> None:
    """Drop the compiled second-opinion programs (tests)."""
    _FALLBACK_CACHE.clear()


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    mi_continue: float = 0.5      # at or below: serve the PFP token
    mi_abstain: float = 2.0       # at or above: abstain immediately
    escalate_samples: int = 8     # SVI samples in the second-opinion pass
    svi_mi_abstain: Optional[float] = None  # default: mi_abstain
    ood_mi: Optional[float] = None  # OOD-alarm threshold for the
    #                                 uncertainty telemetry; default:
    #                                 mi_abstain (routing itself never
    #                                 reads this)


def make_svi_fallback(cfg: ModelConfig, num_samples: int, *,
                      formulation: str = "srm", impl: Optional[str] = None):
    """Jitted N-sample SVI second-opinion pass.

    fallback(params, inputs, sub_state, key, out_idx) -> (token, mi):
    replays the decode inputs ``num_samples`` times with reparameterized
    weight samples (Mode.SVI draws sigma from the converted (mu, srm)
    leaves) against a single-slot state copy, and reduces the sampled
    logits at position ``out_idx`` (the last *real* token of the replayed
    inputs) to a predicted token and mutual information. The replay must
    target the state as it was BEFORE these inputs were consumed — for
    recurrent/SSM carries a replay against the post-step state would apply
    the recurrence twice. The state update is discarded, so the caller's
    pooled buffers keep the PFP-written rows.

    Compiled once per (cfg, samples, formulation, impl) — repeated calls
    (and repeated routers over the same model) return the SAME jitted fn.
    """
    cache_key = ("seq", cfg, num_samples, formulation, impl)
    if cache_key in _FALLBACK_CACHE:
        return _FALLBACK_CACHE[cache_key]

    def fallback(params, inputs, sub_state, key, out_idx):
        def one(k):
            ctx = Context(mode=Mode.SVI, key=k, formulation=formulation,
                          impl=impl)
            logits, _ = lm.decode_step(params, cfg, inputs, sub_state, ctx)
            if is_gaussian(logits):
                logits = logits.mean
            return jax.lax.dynamic_index_in_dim(
                logits, out_idx, 1, keepdims=False).astype(jnp.float32)

        # Samples run one after another: each draws a whole weight sample,
        # and a vmap would hold num_samples of them at once.
        samples = jax.lax.map(one, jax.random.split(key, num_samples))
        m = predictive_metrics_from_samples(samples)        # (N, 1, V) in
        return m["pred"][0], m["mi"][0]

    _FALLBACK_CACHE[cache_key] = jax.jit(fallback)
    return _FALLBACK_CACHE[cache_key]


def make_svi_fallback_batched(cfg: ModelConfig, num_samples: int, *,
                              formulation: str = "srm",
                              impl: Optional[str] = None):
    """Jitted SLOT-BATCHED N-sample SVI second-opinion pass (paged pools).

    batched(params, inputs, states, base_key, uids, tok_idx, out_idx)
    -> (tokens (B,), mis (B,)): every row replays its own escalation
    inputs — (B, C) tokens/positions, (B,) cache_len/write_start, (B, P)
    page-table rows — against ONE shared page pool, with per-row keys
    ``fold_in(fold_in(base_key, uid), tok_idx)`` (the schedule-invariant
    escalation keying). Each row runs the exact per-sample computation of
    :func:`make_svi_fallback`'s fallback, so a row's (token, mi)
    reproduces the sequential second opinion for that slot (tokens
    exactly; MI to float precision, the batch widths differ) — the engine
    collects every slot the router escalates in a step and spends ONE
    lockstep SVI pass on all of them, the way batched prefill amortizes
    chunk passes. Rows not escalating this step carry ``cache_len`` 1 and
    an all-trash page-table row; their outputs are discarded.

    Compiled once per (cfg, samples, formulation, impl); the (B, C) call
    shape is static per engine, so steady-state steps never retrace.
    """
    cache_key = ("batched", cfg, num_samples, formulation, impl)
    if cache_key in _FALLBACK_CACHE:
        return _FALLBACK_CACHE[cache_key]

    def batched(params, inputs, states, base_key, uids, tok_idx, out_idx):
        def row(args):
            tokens, positions, cache_len, write_start, table_row, uid, t = args
            inp = {"tokens": tokens[None], "positions": positions[None],
                   "cache_len": cache_len[None],
                   "write_start": write_start[None],
                   "page_table": table_row[None]}
            key = jax.random.fold_in(jax.random.fold_in(base_key, uid), t)

            def one(k):
                ctx = Context(mode=Mode.SVI, key=k, formulation=formulation,
                              impl=impl)
                logits, _ = lm.decode_step(params, cfg, inp, states, ctx)
                if is_gaussian(logits):
                    logits = logits.mean
                return logits[0].astype(jnp.float32)        # (C, V)

            return jax.lax.map(one, jax.random.split(key, num_samples))

        # Rows and samples run one after another (lax.map, not vmap): each
        # (row, sample) draws a whole weight sample, and at published
        # widths slots x samples of them do not fit device memory.
        samples = jax.lax.map(row, (
            inputs["tokens"], inputs["positions"], inputs["cache_len"],
            inputs["write_start"], inputs["page_table"], uids, tok_idx))
        # (B, N, C, V) -> each row's samples at its own replay out_idx
        samples = jnp.take_along_axis(
            samples, out_idx[:, None, None, None], axis=2)[:, :, 0]
        m = predictive_metrics_from_sample_rows(samples)    # (B, N, V) in
        return m["pred"], m["mi"]

    _FALLBACK_CACHE[cache_key] = jax.jit(batched)
    return _FALLBACK_CACHE[cache_key]


class UncertaintyRouter:
    def __init__(self, cfg: ModelConfig,
                 config: RouterConfig = RouterConfig(), *,
                 formulation: str = "srm", impl: Optional[str] = None):
        self.config = config
        self.svi_mi_abstain = (config.svi_mi_abstain
                               if config.svi_mi_abstain is not None
                               else config.mi_abstain)
        self._fallback_key = (cfg, config.escalate_samples, formulation, impl)
        self._fallback = make_svi_fallback(
            cfg, config.escalate_samples, formulation=formulation, impl=impl)
        self._fallback_batched = None  # built on first batched escalation

    def route(self, mi: float) -> Decision:
        if mi <= self.config.mi_continue:
            return Decision.CONTINUE
        if mi >= self.config.mi_abstain or self.config.escalate_samples <= 0:
            return Decision.ABSTAIN
        return Decision.ESCALATE

    def second_opinion(self, params, inputs, sub_state, key, out_idx=None):
        """(token, mi) from the SVI fallback — the exact jitted function,
        so engine-served escalations are bit-for-bit reproducible.
        ``out_idx`` defaults to the last position of ``inputs``."""
        if out_idx is None:
            out_idx = inputs["tokens"].shape[1] - 1
        return self._fallback(params, inputs, sub_state, key,
                              jnp.asarray(out_idx, jnp.int32))

    def second_opinion_batched(self, params, inputs, states, base_key,
                               uids, tok_idx, out_idx):
        """(tokens (B,), mis (B,)) — ONE lockstep SVI pass resolving every
        escalating slot's second opinion against the shared page pool.
        Row r reproduces ``second_opinion`` for slot r (same per-sample
        program, same per-(request, token) key derivation; batch-width
        accumulation keeps MI equal to float precision)."""
        cfg, samples, formulation, impl = self._fallback_key
        if self._fallback_batched is None:
            self._fallback_batched = make_svi_fallback_batched(
                cfg, samples, formulation=formulation, impl=impl)
        return self._fallback_batched(
            params, inputs, states, base_key,
            jnp.asarray(uids, jnp.int32), jnp.asarray(tok_idx, jnp.int32),
            jnp.asarray(out_idx, jnp.int32))
