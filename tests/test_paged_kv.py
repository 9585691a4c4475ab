"""Paged Gaussian KV-cache tests (ISSUE-4 acceptance surface).

Covers: page-pool invariants under random alloc/free/defrag churn (no page
is ever aliased across slots), paged-vs-contiguous engine decode parity —
bit-for-bit tokens AND mutual-information traces — at page sizes
{1, 16, max_len} on the xla impl and token/decision parity on the kernel
impl, the cache/windowed attention Pallas path (per-batch ``cache_len``
with NO xla fallback), schedule-space registration for the new attention
ops, and preemption under optimistic page admission.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bayes.convert import svi_to_pfp
from repro.configs import reduced_config
from repro.core import dispatch
from repro.core.gaussian import GaussianTensor
from repro.core.modes import Mode
from repro.models import lm
from repro.nn.attention import (KVCache, PagedKVCache, attention_apply,
                                attention_init)
from repro.nn.module import Context
from repro.serving.engine import (Engine, EngineConfig, PagedDecodeStatePool,
                                  RequestScheduler, RouterConfig,
                                  SchedulerConfig, UncertaintyRouter,
                                  pages_for, poisson_trace, run_load)
from repro.serving.batcher import Request

MAX_LEN = 16


@pytest.fixture(scope="module")
def lm_setup():
    cfg = dataclasses.replace(reduced_config("granite-8b"), sigma_init=1e-3)
    params = svi_to_pfp(lm.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


def _engine(cfg, params, *, page_size=None, slots=3, max_len=24,
            router_cfg=None, **ekw):
    router = UncertaintyRouter(
        cfg, router_cfg or RouterConfig(mi_continue=1e9, mi_abstain=2e9))
    scheduler = RequestScheduler(SchedulerConfig(prefill_chunk=3,
                                                 prefill_budget=6))
    return Engine(cfg, params,
                  EngineConfig(slots=slots, max_len=max_len,
                               num_uncertainty_samples=8, seed=0,
                               page_size=page_size, **ekw),
                  router=router, scheduler=scheduler)


def _served(eng, trace, max_steps=600):
    run_load(eng, trace, max_steps=max_steps)
    eng.pool.check_invariants()
    return {r.uid: (list(r.generated), [float(m) for m in r.mi_trace],
                    r.finish_reason) for r in eng.finished}


def _trace(cfg, n=8, seed=4, **kw):
    kw.setdefault("prompt_len", (2, 7))
    kw.setdefault("max_new_tokens", (1, 5))
    return poisson_trace(n, rate=0.8, vocab_size=cfg.vocab_size, seed=seed,
                         **kw)


# ---------------------------------------------------------------------------
# Page-pool invariants
# ---------------------------------------------------------------------------
def test_pool_property_churn_never_aliases_pages(lm_setup):
    """Random alloc / grow / evict / defrag churn: check_invariants
    asserts that no page is ever owned by two slots, tables mirror the
    page lists, and free/live partition the pool exactly."""
    cfg, _ = lm_setup
    pool = PagedDecodeStatePool(cfg, num_slots=4, max_len=MAX_LEN,
                                page_size=2, num_pages=24)
    rng = np.random.default_rng(0)
    next_uid = 0
    for _ in range(300):
        op = rng.choice(["alloc", "grow", "evict", "defrag"])
        live = pool.live_slot_indices()
        if op == "alloc" and pool.free_slots:
            pool.alloc(next_uid)
            next_uid += 1
        elif op == "grow" and live:
            slot = int(rng.choice(live))
            upto = int(rng.integers(1, MAX_LEN + 1))
            if pool.ensure_capacity(slot, upto):
                pool.positions[slot] = upto
        elif op == "evict" and live:
            pool.evict(int(rng.choice(live)))
        elif op == "defrag":
            pool.defrag()
        pool.check_invariants()
    for slot in pool.live_slot_indices():
        pool.evict(slot)
    pool.check_invariants()
    assert pool.live_pages == 0 and pool.free_pages == pool.total_pages


def test_pool_defrag_moves_pages_with_tables(lm_setup):
    """Defrag is a pure permutation: page contents must follow their
    table entries (checked with per-page sentinel values)."""
    cfg, _ = lm_setup
    pool = PagedDecodeStatePool(cfg, num_slots=3, max_len=8, page_size=2)
    for uid, tokens in ((0, 6), (1, 4), (2, 8)):
        slot = pool.alloc(uid)
        assert pool.ensure_capacity(slot, tokens)
    # stamp every page of every leaf with its page index
    n_pages = pool.num_pages

    def stamp(leaf):
        ax = 1 if leaf.ndim == 5 else 0
        shape = [1] * leaf.ndim
        shape[ax] = n_pages
        ids = jnp.arange(n_pages, dtype=leaf.dtype).reshape(shape)
        return jnp.broadcast_to(ids, leaf.shape)

    pool.states = jax.tree_util.tree_map(stamp, pool.states)
    before = {s: list(pool.slot_pages[s]) for s in range(3)}
    pool.evict(1)
    assert pool.page_fragmentation() > 0
    perm = pool.defrag()
    assert perm is not None
    pool.check_invariants()
    assert pool.page_fragmentation() == 0
    # contents followed the tables: page now holding old page p carries
    # sentinel value p
    leaf = jax.tree_util.tree_leaves(pool.states)[0]
    flat = np.asarray(leaf).reshape(leaf.shape[0], -1) if leaf.ndim == 4 \
        else np.asarray(leaf)[0].reshape(leaf.shape[1], -1)
    for slot in (0, 2):
        for j, new_page in enumerate(pool.slot_pages[slot]):
            old_page = before[slot][j]
            assert flat[new_page, 0] == old_page


def test_pool_rejects_infeasible_budget(lm_setup):
    cfg, _ = lm_setup
    with pytest.raises(ValueError):
        PagedDecodeStatePool(cfg, num_slots=2, max_len=16, page_size=4,
                             num_pages=3)  # < one max_len request


def test_paged_state_rejects_recurrent_archs():
    cfg = reduced_config("recurrentgemma-2b")
    with pytest.raises(ValueError):
        lm.init_paged_decode_state(cfg, num_pages=8, page_size=4)
    params_cfg = dataclasses.replace(cfg, sigma_init=1e-3)
    params = svi_to_pfp(lm.init_params(params_cfg, jax.random.PRNGKey(0)))
    with pytest.raises(ValueError):
        _engine(params_cfg, params, page_size=4)


def test_pages_for_budget_math():
    req = Request(uid=0, prompt=np.zeros(5, np.int32), max_new_tokens=4)
    assert pages_for(req, 4) == 3                  # ceil(9/4) reserved
    assert pages_for(req, 4, reserve=False) == 2   # ceil(6/4) to next token
    req.generated = [1, 2]
    assert pages_for(req, 4) == 3                  # reservation unchanged
    assert pages_for(req, 4, reserve=False) == 2   # ceil(8/4)


# ---------------------------------------------------------------------------
# Paged vs contiguous decode parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("page_size", [1, 16, 24])  # 24 == max_len
def test_engine_paged_matches_contiguous_bitforbit(lm_setup, page_size):
    """The acceptance criterion: same tokens AND same MI values at page
    sizes {1, 16, max_len} — the paged xla decode is literally the
    contiguous decode (gather + identical chunked core)."""
    cfg, params = lm_setup
    want = _served(_engine(cfg, params), _trace(cfg))
    got = _served(_engine(cfg, params, page_size=page_size), _trace(cfg))
    assert got == want


def test_engine_paged_auto_defrag_is_transparent(lm_setup):
    cfg, params = lm_setup
    want = _served(_engine(cfg, params, page_size=2), _trace(cfg))
    eng = _engine(cfg, params, page_size=2, auto_defrag=True)
    got = _served(eng, _trace(cfg))
    assert got == want
    assert eng.metrics.defrags > 0


def test_engine_paged_escalation_replay_parity(lm_setup):
    """Escalations replay against the pre-step page pool (batch-1 query,
    full-pool states): SVI second opinions must match the contiguous
    engine's bit-for-bit. Both engines run SEQUENTIAL escalation so the
    two sides execute identically-shaped replay passes — batched vs
    sequential parity (cross-shape, float-tolerance) is pinned in
    tests/test_speculative.py."""
    cfg, params = lm_setup
    esc = RouterConfig(mi_continue=-1.0, mi_abstain=1e9, escalate_samples=2,
                      svi_mi_abstain=1e9)
    want = _served(_engine(cfg, params, router_cfg=esc), _trace(cfg, n=4))
    eng = _engine(cfg, params, page_size=4, router_cfg=esc,
                  batch_escalations=False)
    got = _served(eng, _trace(cfg, n=4))
    assert got == want
    assert eng.metrics.escalations > 0


@pytest.mark.parametrize("page_size", [4])
def test_engine_paged_kernel_impl_parity(lm_setup, page_size):
    """Kernel impl: the paged Pallas kernel and the cache Pallas kernel
    accumulate over different K-block partitions, so raw logits may differ
    in ulps — served tokens and routing decisions must still agree."""
    cfg, params = lm_setup
    trace_kw = dict(n=2, prompt_len=(2, 4), max_new_tokens=(1, 2))
    want = _served(_engine(cfg, params, slots=2, max_len=12, impl="kernel"),
                   _trace(cfg, **trace_kw))
    got = _served(_engine(cfg, params, slots=2, max_len=12, impl="kernel",
                          page_size=page_size), _trace(cfg, **trace_kw))
    assert {u: v[0] for u, v in got.items()} == \
        {u: v[0] for u, v in want.items()}          # same tokens
    assert {u: v[2] for u, v in got.items()} == \
        {u: v[2] for u, v in want.items()}          # same finish reasons


# ---------------------------------------------------------------------------
# Preemption (optimistic page admission)
# ---------------------------------------------------------------------------
def test_engine_preemption_resumes_bitexact_tokens(lm_setup):
    """A preempted slot's request is requeued with its generated tokens;
    re-prefilling prompt+generated reproduces the evicted pages, so the
    greedy continuation is identical to an un-preempted run."""
    cfg, params = lm_setup
    kw = dict(n=8, seed=6, prompt_len=(4, 8), max_new_tokens=(3, 6))
    tight = _engine(cfg, params, page_size=2, reserve_pages=False,
                    page_budget=14, auto_defrag=True)
    run_load(tight, poisson_trace(8, rate=2.0, vocab_size=cfg.vocab_size,
                                  seed=6, prompt_len=(4, 8),
                                  max_new_tokens=(3, 6)), max_steps=1500)
    tight.pool.check_invariants()
    s = tight.metrics.summary()
    assert s["preemptions"] > 0, "budget not tight enough to preempt"
    assert s["final_occupancy"] == 0 and s["final_live_pages"] == 0
    roomy = _engine(cfg, params, page_size=2)
    run_load(roomy, poisson_trace(8, rate=2.0, vocab_size=cfg.vocab_size,
                                  seed=6, prompt_len=(4, 8),
                                  max_new_tokens=(3, 6)), max_steps=1500)
    assert {r.uid: list(r.generated) for r in tight.finished} == \
        {r.uid: list(r.generated) for r in roomy.finished}


def test_engine_preemption_during_batched_prefill(lm_setup):
    """Page exhaustion while a multi-slot prefill round is being planned:
    a slot already staged in the round can itself be preempted as a page
    victim by a later slot's _make_room — the round must drop it cleanly
    (no crash, no writes outside its zeroed table row) and both requests
    must still finish."""
    cfg, params = lm_setup
    eng = _engine(cfg, params, slots=2, max_len=16, page_size=2,
                  page_budget=8, reserve_pages=False)
    for uid in (0, 1):
        eng.submit(Request(uid=uid, prompt=np.full(12, 3 + uid, np.int32),
                           max_new_tokens=2))
    eng.run_until_idle(300)
    eng.pool.check_invariants()
    s = eng.metrics.summary()
    assert s["preemptions"] > 0
    assert sorted(r.uid for r in eng.finished) == [0, 1]
    assert all(len(r.generated) == 2 and r.finish_reason == "length"
               for r in eng.finished)
    assert s["final_occupancy"] == 0 and s["final_live_pages"] == 0


def test_preempted_request_outlives_admission_deadline(lm_setup):
    """The deadline bounds ADMISSION; once admitted (on time) a request
    that gets preempted mid-generation must resume, not expire. The
    deadline-carrying request is submitted SECOND so it is the youngest
    slot — the preemption victim — when the senior slot's decode growth
    drains the pool."""
    cfg, params = lm_setup
    eng = _engine(cfg, params, slots=2, max_len=16, page_size=2,
                  page_budget=12, reserve_pages=False)
    eng.submit(Request(uid=1, prompt=np.full(10, 4, np.int32),
                       max_new_tokens=4))
    eng.submit(Request(uid=0, prompt=np.full(10, 3, np.int32),
                       max_new_tokens=4, deadline=3.0))
    eng.run_until_idle(300)
    reasons = {r.uid: r.finish_reason for r in eng.finished}
    assert eng.metrics.preemptions > 0
    assert reasons[0] == "length", reasons  # resumed, not 'expired'
    assert all(len(r.generated) == 4 for r in eng.finished)


# ---------------------------------------------------------------------------
# Prefill rounds sized to their planned slots
# ---------------------------------------------------------------------------
def _checked_rounds(eng):
    """Wrap ``eng``'s prefill round: each call must leave every page its
    surviving slots do not own (the trash page aside) and the logit row
    of every slot it did not finish bit-identical. Returns the list it
    fills with (slots carried, batch rows run, slots decoding before)."""
    run = eng._prefill_round
    rounds = []

    def checked(rnd):
        pool0 = jax.device_get(eng.pool.states)
        logits0 = [np.asarray(a) for a in eng.logit_buffers]
        was = {s: eng._slots[s].phase for s, _ in rnd
               if eng._slots[s] is not None}
        decoding = eng.decoding
        rows, k = run(rnd)
        alive = [s for s, _ in rnd if eng._slots[s] is not None]
        done = {s for s in alive
                if was.get(s) == "prefill" and eng._slots[s].phase == "decode"}
        owned = {p for s in alive for p in eng.pool.slot_pages[s]}
        keep = [p for p in range(1, eng.pool.num_pages) if p not in owned]
        for a, b in zip(jax.tree_util.tree_leaves(pool0),
                        jax.tree_util.tree_leaves(
                            jax.device_get(eng.pool.states))):
            axis = a.ndim - 4           # (pages, kv heads, page rows, dh)
            assert np.array_equal(np.take(a, keep, axis),
                                  np.take(b, keep, axis))
        others = [s for s in range(eng.config.slots) if s not in done]
        for a, b in zip(logits0, eng.logit_buffers):
            assert np.array_equal(a[others], np.asarray(b)[others])
        rounds.append((k, eng._round_width(k) if k else 0, decoding))
        return rows, k

    eng._prefill_round = checked
    return rounds


def test_bucketed_prefill_round_touches_only_its_slots(lm_setup):
    """Rounds run at the power of two of their planned slots: three slots
    at width 4 (one pad row), one slot alone at width 1 while the others
    decode. No round writes a page its slots do not own or a logit row it
    did not finish, and the tokens are those of the same traffic served
    one request at a time."""
    cfg, params = lm_setup
    eng = _engine(cfg, params, slots=4, max_len=16, page_size=2)
    rounds = _checked_rounds(eng)
    reqs = [Request(uid=u, prompt=np.full(4, 3 + u, np.int32),
                    max_new_tokens=6) for u in range(3)]
    for r in reqs:
        eng.submit(r)
    while eng.prefilling or len(eng.scheduler):
        eng.step()
    late = Request(uid=3, prompt=np.full(5, 6, np.int32), max_new_tokens=2)
    eng.submit(late)
    eng.run_until_idle(300)
    eng.pool.check_invariants()
    assert (2, 2, 0) in rounds and (3, 4, 0) in rounds
    assert any(k == 1 and w == 1 and dec == 3 for k, w, dec in rounds)
    s = eng.metrics.summary()
    assert s["prefill_rows_run"] == 3 * sum(w for _, w, _ in rounds)
    alone = {}
    for r in reqs + [late]:
        one = _engine(cfg, params, slots=4, max_len=16, page_size=2)
        one.submit(Request(uid=r.uid, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens))
        one.run_until_idle(300)
        alone[r.uid] = list(one.finished[0].generated)
    assert {r.uid: list(r.generated) for r in eng.finished} == alone


def test_bucketed_round_drops_a_slot_preempted_by_a_later_one(lm_setup):
    """A round whose later (senior) slot finds no pages preempts the
    junior slot planned before it: the junior is dropped from the round,
    which runs the senior alone at width 1 and leaves the junior's logit
    row and former pages untouched; both requests still finish."""
    cfg, params = lm_setup
    eng = _engine(cfg, params, slots=3, max_len=6, page_size=2,
                  page_budget=3, reserve_pages=False)
    for uid in (0, 1):
        eng.submit(Request(uid=uid, prompt=np.full(4, 3 + uid, np.int32),
                           max_new_tokens=1))
    eng._admit(0.0)
    senior, junior = (eng.pool.owner.index(u) for u in (0, 1))
    rounds = _checked_rounds(eng)
    assert eng._prefill_round([(junior, 3), (senior, 3)]) == (3, 1)
    assert eng._slots[junior] is None and eng.metrics.preemptions == 1
    assert rounds == [(1, 1, 0)]
    eng.run_until_idle(300)
    eng.pool.check_invariants()
    assert sorted(r.uid for r in eng.finished) == [0, 1]
    assert all(len(r.generated) == 1 for r in eng.finished)


def _built_engine(cfg, params):
    """An engine as ``launch/serve.py``'s ``build_engine`` makes it (with
    its warm-up of every prefill width), on four slots; and its mesh."""
    from repro.launch import serve
    from repro.launch.mesh import make_mesh

    args = serve.parse_args(["--impl", "xla", "--page-size", "2",
                             "--batch", "4", "--prompt-len", "8",
                             "--tokens", "3", "--prefill-chunk", "3"])
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh:
        return serve.serving_parts(args, cfg, params, mesh).build_engine(0), \
            mesh


def _serve_four(eng, mesh):
    reqs = [Request(uid=u, prompt=np.full(4, 3 + u, np.int32),
                    max_new_tokens=3) for u in range(4)]
    with mesh:
        for r in reqs:
            eng.submit(r)
        eng.run_until_idle(300)
        eng.submit(Request(uid=9, prompt=np.full(3, 9, np.int32),
                           max_new_tokens=2))
        eng.run_until_idle(300)
    return {r.uid: (list(r.generated), [float(m) for m in r.mi_trace])
            for r in eng.finished}


def test_warm_prefill_leaves_pool_and_logits_bit_identical(lm_setup):
    """The pad-only warm pass, run again mid-serving: the pool's pages 1
    and up and the logit buffers come out bit-identical, and the served
    tokens and MI are those of an engine that never ran it again."""
    cfg, params = lm_setup
    want = _serve_four(*_built_engine(cfg, params))
    eng, mesh = _built_engine(cfg, params)
    with mesh:
        for u in range(4):
            eng.submit(Request(uid=u, prompt=np.full(4, 3 + u, np.int32),
                               max_new_tokens=3))
        eng.step()
        eng.step()
        assert eng.decoding
        pool0 = jax.device_get(eng.pool.states)
        logits0 = [np.asarray(a) for a in eng.logit_buffers]
        eng.warm_prefill()
        for a, b in zip(jax.tree_util.tree_leaves(pool0),
                        jax.tree_util.tree_leaves(
                            jax.device_get(eng.pool.states))):
            idx = [slice(None)] * a.ndim
            idx[a.ndim - 4] = slice(1, None)     # every page but the trash
            assert np.array_equal(a[tuple(idx)], b[tuple(idx)])
        for a, b in zip(logits0, eng.logit_buffers):
            assert np.array_equal(a, np.asarray(b))
        eng.run_until_idle(300)
    eng.submit(Request(uid=9, prompt=np.full(3, 9, np.int32),
                       max_new_tokens=2))
    with mesh:
        eng.run_until_idle(300)
    got = {r.uid: (list(r.generated), [float(m) for m in r.mi_trace])
           for r in eng.finished}
    assert got == want


def test_warm_prefill_compiles_every_round_width(lm_setup):
    """After ``build_engine``'s warm-up, rounds at every width of the
    ladder (1, 2 and 4 rows on four slots) compile nothing."""
    from repro.serving.engine import engine as engine_mod

    cfg, params = lm_setup
    engine_mod.clear_shared_pass_cache()
    eng, mesh = _built_engine(cfg, params)
    compiles = [0]

    def listen(event, duration, **_):
        if event.startswith("/jax/core/compile/") and \
                "backend_compile" in event:
            compiles[0] += 1

    run = eng._batch_chunk_fn
    per_width = {}

    def counted(params, inputs, *rest):
        before = compiles[0]
        out = run(params, inputs, *rest)
        w = inputs["tokens"].shape[0]
        per_width[w] = per_width.get(w, 0) + compiles[0] - before
        return out

    eng._batch_chunk_fn = counted
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        _serve_four(eng, mesh)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert per_width == {1: 0, 2: 0, 4: 0}


def test_scheduler_page_budget_blocks_head(lm_setup):
    """Page admission blocks the queue head rather than skipping it, so
    page pressure cannot invert priority order."""
    s = RequestScheduler(SchedulerConfig(), max_len=32)
    big = Request(uid=0, prompt=np.zeros(8, np.int32), max_new_tokens=8,
                  priority=0)
    small = Request(uid=1, prompt=np.zeros(2, np.int32), max_new_tokens=2,
                    priority=1)
    s.submit(big, now=0)
    s.submit(small, now=0)
    req, _ = s.pop_ready(0, free_pages=2, page_size=4)   # big needs 4
    assert req is None and len(s) == 2
    req, _ = s.pop_ready(0, free_pages=4, page_size=4)
    assert req is not None and req.uid == 0


# ---------------------------------------------------------------------------
# Kernel routing: cache/windowed attention with per-batch cache_len
# ---------------------------------------------------------------------------
def test_cache_attention_kernel_path_no_fallback(monkeypatch):
    """Under impl='kernel', cache attention with per-batch cache_len (and
    a sliding window) must dispatch the registry cache op — the chunked
    XLA fallback is gone for this case."""
    import repro.nn.attention as attn_mod

    calls = []
    real = dispatch.pfp_attention_cache

    def spy(*a, **kw):
        calls.append("cache")
        return real(*a, **kw)

    monkeypatch.setattr(dispatch, "pfp_attention_cache", spy)
    B, H, Hkv, Dh, Dm, S = 2, 4, 2, 8, 32, 16
    params = attention_init(jax.random.PRNGKey(0), Dm, H, Hkv, Dh)
    rng = np.random.default_rng(0)
    x = GaussianTensor.deterministic(
        jnp.asarray(rng.standard_normal((B, 1, Dm)), jnp.float32))
    cache = KVCache(*[jnp.asarray(rng.standard_normal((B, Hkv, S, Dh)),
                                  jnp.float32) for _ in range(3)])
    kw = dict(num_heads=H, num_kv_heads=Hkv, head_dim=Dh,
              positions=jnp.asarray([[5], [3]], jnp.int32),
              cache_len=jnp.asarray([6, 4], jnp.int32), cache=cache)
    out_k, _ = attention_apply(params, x, Context(mode=Mode.PFP,
                                                  impl="kernel"), **kw)
    assert calls == ["cache"], "cache kernel path fell back"
    out_kw, _ = attention_apply(params, x, Context(mode=Mode.PFP,
                                                   impl="kernel"),
                                window=3, **kw)
    assert calls == ["cache", "cache"], "windowed cache path fell back"
    # and it agrees with the xla reference
    out_x, _ = attention_apply(params, x, Context(mode=Mode.PFP,
                                                  impl="xla"), **kw)
    np.testing.assert_allclose(np.asarray(out_k.mean),
                               np.asarray(out_x.mean), rtol=2e-5, atol=2e-5)


def test_paged_attention_op_xla_kernel_parity():
    """Registry-level parity of 'attention_paged' across impls, under a
    shuffled page table and per-batch lengths."""
    rng = np.random.default_rng(2)
    B, H, Hkv, Tq, D, ps, P = 2, 4, 2, 1, 8, 4, 4
    NP = 1 + B * P
    q = jnp.asarray(rng.standard_normal((B, H, Tq, D)), jnp.float32)
    pages = [jnp.asarray(rng.standard_normal((NP, Hkv, ps, D)), jnp.float32)
             for _ in range(2)]
    vv = jnp.asarray(abs(rng.standard_normal((NP, Hkv, ps, D))), jnp.float32)
    table = jnp.asarray(
        rng.permutation(np.arange(1, NP)).reshape(B, P), jnp.int32)
    q_start = jnp.asarray([9, 13], jnp.int32)
    kv_len = q_start + 1
    out = {}
    for impl in ("xla", "kernel"):
        out[impl] = dispatch.pfp_attention_paged(
            q, pages[0], pages[1], vv, table, q_start, kv_len,
            scale=D ** -0.5, impl=impl)
    np.testing.assert_allclose(np.asarray(out["xla"][0]),
                               np.asarray(out["kernel"][0]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out["xla"][1]),
                               np.asarray(out["kernel"][1]),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Tuning registration
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op", ["attention_cache", "attention_paged"])
def test_new_attention_ops_are_tunable(op):
    from repro.tuning import DEFAULT_SCHEDULES, TUNABLE_OPS
    from repro.tuning.measure import make_runner
    from repro.tuning.search import candidates, cost_summary

    assert op in TUNABLE_OPS and op in DEFAULT_SCHEDULES
    shape_key = (2, 4, 2, 8, 32, 16)
    cands = candidates(op, shape_key)
    assert cands and all(cost_summary(op, shape_key, c).fits_vmem
                         for c in cands)
    run = make_runner(op, shape_key)
    want = run(None)  # default schedule
    for sched in cands[:3]:
        got = run(sched)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=sched.describe())
