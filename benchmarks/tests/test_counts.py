import json
import os

import pytest

from harness import counts, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_parameter_counts_match_the_published_models():
    m = cfg("mistral-7b-v0.3-l16")
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
    flops, nbytes = counts.decode_step_work(m, 18, 18 * 900)
    # weights 7.25 GB read once + 18 x 900 x 64 KiB of K and V
    assert nbytes == pytest.approx(7.25e9 + 18 * 901 * 65536, rel=2e-3)
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    t_flops = flops / pk["bf16_flops_per_s"]
    assert t_bytes > 5 * t_flops
    assert counts.least_seconds(flops, nbytes, pk) == t_bytes
    assert 0.009 < t_bytes < 0.011  # about 10 ms against 55 ms measured
    i = cfg("internlm2-1.8b")
    flops, nbytes = counts.decode_step_work(i, 32, 32 * 700)
    assert counts.least_seconds(flops, nbytes, pk) == pytest.approx(
        (3.4e9 + 32 * 701 * 98304) / 819e9, rel=0.02
    )


def test_prefill_and_decode_flops():
    m = cfg("mistral-7b-v0.3-l16")
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
