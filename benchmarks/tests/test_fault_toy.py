"""One token altered where it is produced comes out as ``correct`` false,
on a dense toy and on a toy with windowed layers and experts (the key names
of ``mimo-v2.5-l7-ep16``), and a dense configuration's family builds the
weights and the model it has built since PR 28. The program's
``decode_step`` returns ``((tokens, counts), pool_k, pool_v)`` and its
``ModelConfig`` describes a stack by position (PR 29); the cases that
pinned the program as it was before that went with PR 31.

The windowed toy (``toy_moe/``) runs in float32: at its widths bfloat16
reads gaps up to 0.77 beside a gross limit of 1.0 and int8's, so the rung
below would not come out apart. Four seeds (1101-1104, 8 s, 504 tokens,
CPU): the program's every gap 0; int8's mean gap 0.0136-0.0197 and share
over 0.03 9.5-12.5 %; limits 0.0009 and 1.2 %, the dense toy's."""
import os
import zlib

import numpy as np
import pytest

import run as bench_run
from harness import check, peaks, spec

from test_counts import BENCH, cfg
from test_families import DENSE_BLOCK, PINNED

HERE = os.path.dirname(os.path.abspath(__file__))
TOYS = {
    "dense": ("toy-gqa.toy", os.path.join(HERE, "toy", "BENCHMARK.json")),
    "windowed_experts": (
        "toy-moe-window.toy", os.path.join(HERE, "toy_moe", "BENCHMARK.json")),
}


def toy_run(toy, seed, seconds, control=False):
    import jax

    cell = spec.load_cell(*TOYS[toy])
    return bench_run.run_cell(
        cell, seed, seconds, False, jax.devices()[:1],
        peaks.PEAKS["TPU v5 lite"], control=control,
    )


def test_the_windowed_toy_passes_and_its_control_does_not():
    res = toy_run("windowed_experts", 1105, 8.0, control=True)
    c = res["compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert c["compared_tokens"]["value"] > 300
    assert c["window_compiles"] == {"value": 0, "limit": 0}
    assert c["control_correct"]["value"] is False
    assert c["control_tail_share"]["value"] > c["tail_share"]["limit"]
    assert c["control_mean_gap"]["value"] > c["mean_gap"]["limit"]


@pytest.mark.parametrize("toy", sorted(TOYS))
def test_one_altered_token_comes_out_not_correct(monkeypatch, toy):
    """The timed path broken underneath: one token of the whole run, a live
    slot's at the twentieth decode step or the first after it that has
    one, is altered where it is produced."""
    from ray_tpu.llm.continuous import ContinuousBatchingEngine

    real = ContinuousBatchingEngine._build_fns
    calls = {"n": 0}

    def broken_build(engine):
        real(engine)
        decode = engine._decode_step

        def altered(*a, **kw):
            (nxt, counts), k, v = decode(*a, **kw)
            calls["n"] += 1
            live = [i for i, s in enumerate(engine.slots) if s.active]
            if calls["n"] >= 20 and live and not calls.get("altered"):
                i = live[0]
                nxt = nxt.at[i].set((nxt[i] + 1) % engine.cfg.vocab_size)
                calls["altered"] = True
            return (nxt, counts), k, v

        engine._decode_step = altered

    monkeypatch.setattr(ContinuousBatchingEngine, "_build_fns", broken_build)
    res = toy_run(toy, 31, 4.0)
    assert calls["altered"]
    assert res["correct"] is False and res["failed"] == 0
    c = res["compared"]
    assert c["gross_gaps"] == {"value": 1, "limit": 0, "over": check.GROSS_OVER}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_a_dense_family_builds_the_model_it_built(name):
    """The fields a dense configuration sets are the parent of PR 28's;
    every field since is at its default, which is that model: one uniform
    stack, the head size derived, ``rms_eps`` 1e-6."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm

    c, pin = cfg(name), PINNED[name]
    fam = spec.load_family(c, BENCH)
    shapes = jax.eval_shape(lambda: fam.make_weights(c, 7))
    assert jax.tree_util.tree_map(lambda s: s.shape, shapes) == {
        "embed": (pin["vocab"], pin["d"]), "ln_f": (pin["d"],),
        "head": (pin["d"], pin["vocab"]),
        "blocks": {
            leaf: (pin["layers"], *(pin[k] for k in dims))
            for leaf, dims in DENSE_BLOCK.items()
        },
    }
    assert {s.dtype for s in jax.tree_util.tree_leaves(shapes)} == {
        jnp.dtype("bfloat16")}
    for seed, crc in pin.get("crc", {}).items():  # the seeded weights, bit for bit
        leaves = jax.tree_util.tree_leaves(fam.make_weights(c, seed))
        assert sum(zlib.crc32(np.asarray(x).tobytes()) for x in leaves) == crc
    model = fam.model_config(c)
    assert model == tfm.ModelConfig(
        vocab_size=pin["vocab"], d_model=pin["d"], n_layers=pin["layers"],
        n_heads=pin["n_heads"], n_kv_heads=pin["n_kv_heads"],
        d_ff=pin["ff"], max_seq_len=pin["max_seq_len"],
        rope_theta=1000000.0, n_experts=0, expert_capacity_factor=1.25,
        dtype=jnp.dtype("bfloat16"), sp_attention="ring", remat=False,
    )
    assert model.uniform and model.rms_eps == 1e-6
    assert model.head_dim == model.v_head_dim == model.rotary_dim
    assert model.head_dim == pin["d"] // pin["n_heads"]
    assert [(r.key, r.start, r.count, r.attn.name) for r in
            model.layer_runs()] == [(None, 0, pin["layers"], "full")]
    model.require_uniform_dense("a dense configuration")
    with pytest.raises(ValueError, match="another head size"):
        fam.model_config(dict(c, head_dim=c["head_dim"] + 64))
