import json
import os

import numpy as np
import pytest

from harness import metrics, served, spec, stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TRAFFIC = os.path.join(BENCH, "traffic")


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
    assert len(s) == 300  # 5.0 / s over 10 s of ramp-in and 50 s
    assert sum(1 for r in s if r.due < -9.95) == 48  # the opening burst, 1 ms apart
    assert sum(r.max_new for r in s) == 196_973
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


def replay_slots(schedule, slots, step_s, seconds, prefill_s_per_ktok=0.05):
    """A plain replay of a schedule through ``slots`` slots at a fixed step
    interval: a replay, not a measurement. Every step each live slot gives
    one token; a free slot takes the oldest request that is due, whose
    prefill holds every slot up (``prefill_s_per_ktok`` of its prompt) and
    gives its first token. Returns the tokens stamped inside the window,
    the requests due and not yet taken up at the close, and the most
    requests in flight (offered less finished) at any step."""
    reqs = sorted(schedule, key=lambda r: r.due)
    t, due, queue, live = reqs[0].due, 0, [], []
    tokens = finished = in_flight = 0
    while t < seconds:
        while due < len(reqs) and reqs[due].due <= t:
            queue.append(reqs[due])
            due += 1
        while queue and len(live) < slots:
            r = queue.pop(0)
            t += prefill_s_per_ktok * r.prompt_len / 1000.0
            tokens += 0 <= t < seconds
            live.append(r.max_new - 1)
        tokens += len(live) * (0 <= t < seconds)
        live = [left - 1 for left in live]
        finished += live.count(0)
        live = [left for left in live if left > 0]
        in_flight = max(in_flight, due - finished)
        t += step_s
    return {"tokens": tokens, "queued": len(queue), "in_flight": in_flight}


def rank_orders(m, slots, step_s, prefill_s_per_ktok=0.05):
    """The orders 0..39 of a mix by their tokens inside the window in a
    replay, fewest first, and the counts: how an ``order_seed`` is chosen."""
    def tokens(order):
        s = traffic.schedule(
            dict(m, arrivals=dict(m["arrivals"], order_seed=order)), 50)
        return replay_slots(s, slots, step_s, 50, prefill_s_per_ktok)["tokens"]

    counts = {o: tokens(o) for o in range(40)}
    return sorted(counts, key=lambda o: (counts[o], o)), counts


# the parent's measured step interval, then what a v5e allows this model
# soon: PR 30's decode kernel read 11.3 ms (ledger, PR 30)
REASONING_STEP_S = (0.0366, 0.030, 0.025, 0.020, 0.015, 0.0113, 0.011)


def test_reasoning_schedule_outlasts_the_engine():
    """A cell judged on ``tokens_per_s`` above its knee needs a schedule
    that outlasts the engine: as the step interval shortens the tokens
    inside the window never fall and requests are still queued at the
    close. The schedule it replaced (89 requests) reads 834 tokens/s at
    36.6 ms and 610 at 11.3 with none queued, which is what refused PR 30.
    At the slowest interval the requests in flight stay under the router's
    ``serve_admission_max_inflight``, past which it sheds."""
    from ray_tpu.config import cfg

    s = traffic.schedule(mix("reasoning"), 50)
    runs = [replay_slots(s, 32, step_s, 50) for step_s in REASONING_STEP_S]
    counts = [r["tokens"] for r in runs]
    assert counts == sorted(counts) and counts[-1] > 3 * counts[0]
    assert all(r["queued"] > 0 for r in runs)
    assert runs[0]["queued"] > 0.6 * len(s)
    assert runs[0]["in_flight"] <= cfg.serve_admission_max_inflight - 10
    assert replay_slots(s, 32, 0.009, 50)["queued"] == 0  # spent by here
    m = mix("reasoning")
    old = traffic.schedule(
        dict(m, arrivals=dict(m["arrivals"], rate_per_s=1.48, order_seed=0)), 50)
    spent = [replay_slots(old, 32, step_s, 50) for step_s in (0.0366, 0.0113)]
    assert spent[1]["tokens"] < 0.75 * spent[0]["tokens"]
    assert spent[0]["queued"] == spent[1]["queued"] == 0


def test_reasoning_order_seed_is_the_median_order_at_a_full_batch():
    """``reasoning``'s ``order_seed`` by the same rule as the others',
    replayed through its 32 slots at the measured step interval. At a full
    batch the order decides only how many admissions hold the slots up, so
    the forty orders lie within a hundredth of each other: what the order
    moved in the spent schedule (a tenth) is gone."""
    m = mix("reasoning")
    ranked, counts = rank_orders(m, 32, REASONING_STEP_S[0])
    assert ranked.index(m["arrivals"]["order_seed"]) in (19, 20, 21, 22)
    assert counts[ranked[-1]] < 1.01 * counts[ranked[0]]


# measured (my chip runs, PR 38, the traced runs of the reloaded cell): a
# decode step's interval is 25.9-26.3 ms on the device and 6.8-7.2 on the
# host. What a prefill costs the slots, in seconds per 1,000 prompt tokens,
# is the cost at which the replay through the cell's 64 slots reads the
# measured 907 tokens/s (908 here; 133 queued and 197 in flight at the close
# against 144 and 175-186): the slots that the hand-over leaves empty (batch
# 47 of 64) are in it; through 47 slots the same fit gives 52. Then PR 37's
# step (ledger, PR 37: 13.6 ms on the device and 4.1 on the host)
MIXED_STEP_S, MIXED_PREFILL_S_PER_KTOK = 0.033, 0.085
MIXED_STEP_S_PR37 = 0.0177


def test_mixed_is_the_issues_mix():
    """``mixed`` since PR 38: the lengths it had (twelve padded prompt
    lengths, 256-7,680 log-uniform; answers 128-512), 64 at once when the
    ramp-in starts and then 4.5 arrivals a second to the close."""
    cell = spec.load_cell("mimo-v2.5-l7-ep16.mixed")
    a = cell.mix["arrivals"]
    s = traffic.schedule(cell.mix, 50)
    assert (len(s), sum(r.max_new for r in s)) == (334, 92_515)
    assert (len(s) - a["initial_burst"]) / (a["ramp_in_s"] + 50) == pytest.approx(4.5)
    assert sum(1 for r in s if r.due < -a["ramp_in_s"] + 0.064) == 64
    p, o = [r.prompt_len for r in s], [r.max_new for r in s]
    assert min(p) >= 256 - 16 and max(p) <= 7680
    assert len({-(-x // 16) for x in p}) <= 12
    assert np.mean(p) == pytest.approx(2164, rel=0.01)
    assert stats.percentile(p, 50) == pytest.approx(1216, rel=0.02)
    assert min(o) >= 128 and max(o) <= 512
    assert max(a + b for a, b in zip(p, o)) <= (
        cell.cfg["deployment"]["max_context_tokens"])


def test_mixed_schedule_outlasts_the_engine():
    """What the mix's ``what`` says. Through the cell's 64 slots, from the
    measured step interval down to PR 37's 17.7 ms at the measured cost of
    a prefill, the tokens inside the window never fall and requests are
    queued at the close at every one; at the slowest the requests in flight
    stay 20 under the router's ``serve_admission_max_inflight``, through
    the 46 slots the engine fills too. The schedule it replaced (192
    requests) has none queued at either speed and reads fewer tokens at the
    faster one once prefill costs 50 ms per 1,000 tokens, which is what
    refused PR 37; the new one is spent only there, and still reads half
    as much again as at today's speed."""
    from ray_tpu.config import cfg

    m = mix("mixed")
    s = traffic.schedule(m, 50)
    steps = (MIXED_STEP_S, 0.028, 0.024, 0.020, MIXED_STEP_S_PR37)
    runs = [replay_slots(s, 64, x, 50, MIXED_PREFILL_S_PER_KTOK) for x in steps]
    counts = [r["tokens"] for r in runs]
    assert counts == sorted(counts) and counts[-1] > 1.2 * counts[0]
    assert all(r["queued"] > 0.2 * len(s) for r in runs)
    filled = replay_slots(s, 46, MIXED_STEP_S, 50, MIXED_PREFILL_S_PER_KTOK)
    for r in (runs[0], filled):
        assert r["in_flight"] <= cfg.serve_admission_max_inflight - 20
    spent = replay_slots(s, 64, MIXED_STEP_S_PR37, 50, 0.050)
    assert spent["queued"] == 0 and spent["tokens"] > 1.5 * counts[0]
    old = traffic.schedule(
        dict(m, arrivals=dict(m["arrivals"], rate_per_s=3.2, order_seed=37)), 50)
    assert len(old) == 192
    was = [replay_slots(old, 64, MIXED_STEP_S, 50, MIXED_PREFILL_S_PER_KTOK),
           replay_slots(old, 64, MIXED_STEP_S_PR37, 50, 0.050)]
    assert was[0]["queued"] == was[1]["queued"] == 0
    assert was[1]["tokens"] < 0.9 * was[0]["tokens"]


def test_mixed_order_seed_is_the_median_order_at_the_measured_interval():
    """``mixed``'s ``order_seed`` by the same rule, replayed through its
    64 slots at the measured step interval and cost of a prefill. With
    prompts of 256-7,680 in one queue the order moves the count by a
    tenth: which long prompts hold the slots up inside the window."""
    m = mix("mixed")
    ranked, counts = rank_orders(m, 64, MIXED_STEP_S, MIXED_PREFILL_S_PER_KTOK)
    assert ranked.index(m["arrivals"]["order_seed"]) in (19, 20, 21, 22)
    assert counts[ranked[-1]] > 1.08 * counts[ranked[0]]


def test_schedule_unspent_reader_on_a_toy_run():
    """Four requests sent by the close: two with a token inside the window,
    one whose first token came after the close, one with none. One sent
    after the close and one never sent are not counted."""
    read = spec.load_reader("schedule_unspent_pct", BENCH)

    def client(sent, stamps):
        return served.Client(traffic.Request(0, 0.0, 8, 4), 0.0, np.zeros(8),
                             sent=sent, stamps=stamps)

    def run(clients):
        return metrics.Run(
            cfg={}, mix={}, base=BENCH, peaks={}, t_open=100.0, t_close=150.0,
            setup_s=1.0, clients=clients, decode_log=[], prefill_log=[],
            window_compiles=0, memory_peak_bytes=None)

    clients = [
        client(95.0, [96.0, 120.0]), client(140.0, [149.9]),
        client(149.0, [150.0, 150.1]), client(149.5, []),
        client(150.2, []), client(None, []),
    ]
    assert read(run(clients)) == pytest.approx(50.0)
    assert read(run(clients[:2])) == 0.0  # a spent schedule reads 0
    assert read(run(clients[4:])) is None  # nothing sent: nothing to read
