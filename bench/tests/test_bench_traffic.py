"""The traffic generators and the end-to-end arithmetic, by hand."""
from __future__ import annotations

import numpy as np
import pytest

from bench import spec
from bench.traffic import batch, serve

CHAT = spec.workload("granite-8b.chat.steady")["traffic"]


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3])
def test_every_seed_gets_the_same_work_in_another_order(seed):
    # The schedule (lengths, gaps, their order) is fixed; the run's seed
    # draws only the prompts' token ids.
    a = serve.plan(CHAT, 1, 30.0, 49152)
    b = serve.plan(CHAT, seed, 30.0, 49152)
    assert len(a) == len(b)
    assert [len(p.prompt) for p in a] == [len(p.prompt) for p in b]
    assert [p.max_new for p in a] == [p.max_new for p in b]
    assert [p.due for p in a] == [p.due for p in b]
    assert any(not np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    assert all(0 <= int(t) < 49152 for p in b for t in p.prompt)
    # the window's requests take the distributions' quantiles, shuffled
    k = round(CHAT["rate_rps"] * 30.0)
    assert sorted(p.max_new for p in b[:k]) == list(
        serve.lognormal_set(k, CHAT["output"]))
    assert [p.max_new for p in b[:k]] != sorted(p.max_new for p in b[:k])


def test_lengths_follow_the_lognormal_and_its_clips():
    x = serve.lognormal_set(1001, CHAT["prompt"])
    assert np.median(x) == CHAT["prompt"]["median"]
    assert x.min() >= 32 and x.max() <= 2048
    o = serve.lognormal_set(1001, CHAT["output"])
    assert np.median(o) == 96 and o.min() >= 8 and o.max() <= 512
    g = serve.exponential_set(10000, 2.0)
    assert g.mean() == pytest.approx(0.5, rel=0.01)


def test_only_the_open_loop_is_planned():
    with pytest.raises(ValueError):
        serve.plan({**CHAT, "mode": "closed"}, 3, 10.0, 100)


def _rec(due, tokens, accepted=True):
    p = serve.Planned(uid=0, prompt=np.zeros(4, np.int32), max_new=8)
    return serve.Record(plan=p, due=due, submitted=due, accepted=accepted,
                        tokens=list(tokens))


def test_end_to_end_by_hand():
    recs = [_rec(0.0, [0.5, 0.7, 1.0]),      # ttft 0.5, gaps 0.2, 0.3
            _rec(1.0, [1.2, 2.5]),           # ttft 0.2, gap 1.3 (out)
            _rec(1.5, []),                   # no token: its age, 0.5
            _rec(2.5, [])]                   # due after the window
    out = serve.end_to_end(recs, 2.0)
    assert out["tokens_per_s"] == pytest.approx(4 / 2.0)
    assert out["ttft_p95_ms"] == pytest.approx(500.0)
    assert out["itl_p95_ms"] == pytest.approx(300.0)
    d = serve.diagnostics(recs, 2.0)
    assert d["due"] == 3 and d["tokens"] == 5


def test_images_mix_and_repeat_per_seed():
    a, b = batch.images(6, 3), batch.images(6, 3)
    assert a.shape == (6, 28, 28, 1) and np.array_equal(a, b)
    assert not np.array_equal(a, batch.images(6, 4))
    assert 0.0 <= a.min() and a.max() <= 1.0
    recs = [batch.Record(i, 0, 0.1 * i, 0.1 * i + 0.01 * (i + 1))
            for i in range(20)]
    assert batch.end_to_end(recs, 10.0)["batch_p95_ms"] == pytest.approx(
        190.0)
