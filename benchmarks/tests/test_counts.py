import json
import os
from types import SimpleNamespace as NS

import pytest

from harness import metrics, peaks, spec

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
FILES = {
    "mistral-7b-v0.3-l16": os.path.join(BENCH, "configs", "mistral-7b-v0.3-l16.json"),
    "internlm2-1.8b": os.path.join(BENCH, "configs", "internlm2-1.8b.json"),
    "toy-gqa": os.path.join(HERE, "toy", "configs", "toy-gqa.json"),
}


def cfg(name):
    with open(FILES[name]) as f:
        return json.load(f)


def family(c):
    return spec.load_family(c, BENCH)


# Taken from the parent of PR 28 (the harness's own counts, before they moved behind
# the family's name): (batch, sum of contexts) -> (flops, bytes) of a decode
# step, context -> a decoded token's flops, prompt length -> a prefill's
# flops; the three readers on ``synthetic_run`` below.
PINNED = {
    "mistral-7b-v0.3-l16": {
        "step": [(18, 16200, 134706364416, 8310767616),
                 (5, 7431, 38186778624, 7735123968),
                 (32, 40007, 242415828992, 9872015360)],
        "token": [(0, 7247757312), (1000, 7509901312), (2499, 7902855168)],
        "prefill": [(1, 7248019456), (1000, 7110793363456),
                    (2048, 14843943845888)],
        "readers": (45.425633992673994, 0.43770223600406094, 3.7804878048780486),
    },
    "internlm2-1.8b": {
        "step": [(32, 22400, 113170710528, 5604245504),
                 (29, 18861, 102278037504, 5256040448),
                 (1, 113, 3421175808, 3410169856)],
        "token": [(0, 3398959104), (630, 3522822144), (1535, 3700752384)],
        "prefill": [(1, 3399155712), (241, 733907976192),
                    (508, 1559906353152)],
        "readers": (22.517519316239316, 0.1926874169177665, 3.7804878048780486),
    },
    "toy-gqa": {
        "step": [(4, 200, 34373632, 8808448), (1, 5, 8409088, 8401408),
                 (3, 97, 25563136, 8594944)],
        "token": [(0, 8388608), (17, 8458240), (127, 8908800)],
        "prefill": [(1, 8392704), (17, 109678592), (64, 413270016)],
        "readers": (0.05432912332112332, 0.0003191303796954315, 3.7804878048780486),
    },
}


def uneven(batch, total):
    """``batch`` context lengths that all differ where they can and sum to
    ``total``."""
    head = list(range(1, batch))
    return head + [total - sum(head)]


def synthetic_run(c):
    """Forty decode steps of 1-7 live contexts, one step with none, six
    streams of which some began before the window opened."""
    top = c["deployment"]["max_context_tokens"]
    log = [
        (10.0 + 0.05 * i,
         tuple(1 + (37 * i + 11 * j) % (top - 1) for j in range(1 + i % 7)))
        for i in range(40)
    ] + [(11.0, ())]
    clients = [
        NS(request=NS(prompt_len=5 + (13 * k) % (top // 2)),
           stamps=[9.5 + 0.4 * k + 0.1 * j for j in range(3 + 5 * k)])
        for k in range(6)
    ]
    return metrics.Run(
        cfg=c, mix={}, base=BENCH, peaks=peaks.PEAKS["TPU v5 lite"],
        t_open=10.0, t_close=12.0, setup_s=1.0, clients=clients,
        decode_log=log, prefill_log=[], window_compiles=0,
        memory_peak_bytes=None, capture=(10.0, 12.5),
        trace=NS(program=lambda name: (0.8, 40)),
    )


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_and_their_readers_are_the_parents_to_the_digit(name):
    c, pin = cfg(name), PINNED[name]
    fam = family(c)
    for batch, total, flops, nbytes in pin["step"]:
        assert sum(uneven(batch, total)) == total
        assert fam.decode_step_work(c, uneven(batch, total)) == (flops, nbytes)
        assert fam.decode_step_work(c, tuple(reversed(uneven(batch, total)))) == (
            flops, nbytes)
    for context, flops in pin["token"]:
        assert fam.decode_token_flops(c, context) == flops
    for length, flops in pin["prefill"]:
        assert fam.prefill_flops(c, length) == flops
    run = synthetic_run(c)
    got = tuple(
        spec.load_reader(m, BENCH)(run)
        for m in ("decode_step_roofline", "serve_mfu_pct", "decode_batch_mean")
    )
    assert got == pin["readers"]


def test_parameter_counts_match_the_published_models():
    m = cfg("mistral-7b-v0.3-l16")
    counts = family(m)
    # 16 layers x 218.1 M + embedding and head 2 x 134.2 M
    assert counts.block_params(m) == 16 * (
        4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    )
    assert counts.total_params(m) == pytest.approx(3.758e9, rel=1e-3)
    assert counts.kv_bytes_per_token(m) == 64 * 1024
    i = cfg("internlm2-1.8b")
    assert counts.total_params(i) == pytest.approx(1.889e9, rel=1e-3)
    assert counts.kv_bytes_per_token(i) == 96 * 1024
    # the whole 32-layer model would be 7.25 B: the cut is depth alone
    full = dict(m, num_hidden_layers=32)
    assert counts.total_params(full) == pytest.approx(7.248e9, rel=1e-3)


def test_decode_step_is_bound_by_bytes_at_these_batches():
    pk = peaks.peaks_for("TPU v5 lite")
    m = cfg("mistral-7b-v0.3-l16")
    counts = family(m)
    flops, nbytes = counts.decode_step_work(m, [900] * 18)
    # weights 7.25 GB read once + 18 x 900 x 64 KiB of K and V
    assert nbytes == pytest.approx(7.25e9 + 18 * 901 * 65536, rel=2e-3)
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    t_flops = flops / pk["bf16_flops_per_s"]
    assert t_bytes > 5 * t_flops
    assert peaks.least_seconds(flops, nbytes, pk) == t_bytes
    assert 0.009 < t_bytes < 0.011  # about 10 ms against 55 ms measured
    i = cfg("internlm2-1.8b")
    flops, nbytes = counts.decode_step_work(i, [700] * 32)
    assert peaks.least_seconds(flops, nbytes, pk) == pytest.approx(
        (3.4e9 + 32 * 701 * 98304) / 819e9, rel=0.02
    )


def test_prefill_and_decode_flops():
    m = cfg("mistral-7b-v0.3-l16")
    counts = family(m)
    p = counts.block_params(m) + counts.head_params(m)
    assert counts.decode_token_flops(m, 0) == 2 * p
    # attention: 4 x layers x heads x head_dim per key
    assert counts.decode_token_flops(m, 1000) - 2 * p == 4 * 16 * 32 * 128 * 1000
    t = 2048
    assert counts.prefill_flops(m, t) == (
        2 * counts.block_params(m) * t
        + 4 * 16 * 32 * 128 * t * (t + 1) // 2
        + 2 * counts.head_params(m)
    )


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")
