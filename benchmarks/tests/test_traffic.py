import json
import os

import numpy as np
import pytest

from harness import stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "reasoning", "rag"])
def test_schedule_is_the_mixs_own_and_the_seed_draws_the_ids(name):
    m = mix(name)
    a = traffic.schedule(m, 50)
    assert a == traffic.schedule(m, 50)
    other = dict(m, arrivals=dict(m["arrivals"], order_seed=12345))
    c = traffic.schedule(other, 50)
    assert a != c
    # another order_seed is the same lengths and gaps in another order
    for key in ("prompt_len", "max_new"):
        assert sorted(getattr(r, key) for r in a) == sorted(
            getattr(r, key) for r in c
        )
    assert a[-1].due == pytest.approx(c[-1].due)
    assert all(x.due <= y.due for x, y in zip(a, a[1:]))
    ids = traffic.prompt_ids(5, 3, 100, 32768)
    assert (ids == traffic.prompt_ids(5, 3, 100, 32768)).all()
    assert (ids != traffic.prompt_ids(6, 3, 100, 32768)).any()
    assert 0 <= ids.min() and ids.max() < 32768


def test_chat_matches_the_issue():
    s = traffic.schedule(mix("chat"), 50)
    assert len(s) == 99  # 1.8 / s over 5 s of ramp-in and 50 s
    p = [r.prompt_len for r in s]
    o = [r.max_new for r in s]
    assert max(p) <= 2048 and max(o) <= 450
    assert stats.percentile(p, 50) == pytest.approx(627, rel=0.08)
    assert sum(1 for x in p if x > 2032) >= 3
    assert stats.percentile(p, 5) == pytest.approx(173, rel=0.2)
    assert np.mean(p) == pytest.approx(773, rel=0.08)
    assert np.mean(o) == pytest.approx(120, rel=0.08)
    gaps = np.diff([r.due for r in s])
    assert gaps.std() / gaps.mean() == pytest.approx(0.9, abs=0.08)
    assert -5.0 < s[0].due < 0 and 49 < s[-1].due < 50


def test_reasoning_and_rag_match_the_issue():
    s = traffic.schedule(mix("reasoning"), 50)
    assert len(s) == 89
    assert sum(1 for r in s if r.due < -9.9) == 48  # the opening burst
    p = [r.prompt_len for r in s]
    o = [r.max_new for r in s]
    assert min(p) >= 113 and max(p) <= 512
    assert min(o) >= 256 and max(o) == 1024
    assert stats.percentile(o, 50) == pytest.approx(638, rel=0.05)
    assert max(a + b for a, b in zip(sorted(p), [1024] * len(p))) <= 1536
    s = traffic.schedule(mix("rag"), 50)
    assert len(s) == 110
    p = [r.prompt_len for r in s]
    assert min(p) >= 1009 and max(p) <= 2048
    assert np.mean([r.max_new for r in s]) == pytest.approx(40, abs=1.5)
    # few padded lengths: one prefill program each
    assert len({-(-x // 16) for x in p}) <= 8
    assert np.mean(p) == pytest.approx(1524, rel=0.02)


@pytest.mark.parametrize("name,token_s", [("chat", 0.068), ("rag", 0.075)])
def test_order_seed_is_the_median_order_of_a_replay(name, token_s):
    """How a mix's ``order_seed`` was chosen (PERF.md, section 4): of the
    orders 0..39, the one whose tokens inside the window, in a plain replay
    (first token 0.7 s after a request is due, then one each ``token_s``,
    the cell's measured mean gap), is the median. Which long answers
    straddle the window's ends moves that count by a tenth either way, and
    the mix keeps an order that is typical, neither its best nor its worst."""
    m = mix(name)

    def tokens_in_window(order):
        s = traffic.schedule(
            dict(m, arrivals=dict(m["arrivals"], order_seed=order)), 50)
        return sum(
            1 for r in s for k in range(r.max_new)
            if 0 <= r.due + 0.7 + k * token_s < 50
        )

    counts = {o: tokens_in_window(o) for o in range(40)}
    ranked = sorted(counts, key=counts.get)
    assert ranked.index(m["arrivals"]["order_seed"]) in (19, 20, 21, 22)
    assert counts[ranked[-1]] > 1.05 * counts[ranked[0]]
