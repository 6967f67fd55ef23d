"""What the benchmark knows of an architecture lies behind the ``family``
and ``reference`` names of a configuration's file: the move of the dense
GQA decoder behind them pinned against the parent of PR 28, and a second
family that exists only in a temporary directory driven through a whole
run. (``test_fault_toy`` holds a dense family to the weights and the model
it built, on the program as it is now.)"""
import json
import os

import pytest

import run as bench_run
from harness import peaks, spec

from test_counts import BENCH, cfg

ROOT = spec.REPO_ROOT
DENSE_BLOCK = {  # leaf -> shape after the leading axis of layers
    "ln1": ("d",), "ln2": ("d",), "w_down": ("ff", "d"), "w_gate": ("d", "ff"),
    "w_up": ("d", "ff"), "wk": ("d", "kv"), "wo": ("q", "d"), "wq": ("d", "q"),
    "wv": ("d", "kv"),
}
# d, q = heads x head size, kv = KV heads x head size, ff, layers, vocab,
# the program's max_seq_len; the CRC32s of ``make_weights(toy, seed)``'s
# leaves, summed, as the parent of PR 28 made them
PINNED = {
    "mistral-7b-v0.3-l16": dict(
        d=4096, q=4096, kv=1024, ff=14336, layers=16, vocab=32768,
        n_heads=32, n_kv_heads=8, max_seq_len=2560),
    "internlm2-1.8b": dict(
        d=2048, q=2048, kv=1024, ff=8192, layers=24, vocab=92544,
        n_heads=16, n_kv_heads=8, max_seq_len=1536),
    "toy-gqa": dict(
        d=256, q=256, kv=128, ff=768, layers=4, vocab=4096,
        n_heads=8, n_kv_heads=4, max_seq_len=128,
        crc={1101: 21975381266, 3_000_000_007: 22026468805}),
}


def test_a_configuration_without_its_files_is_refused(tmp_path):
    c = cfg("toy-gqa")
    for key, load in (("family", spec.load_family),
                      ("reference", spec.load_reference)):
        with pytest.raises(SystemExit, match=key):
            load({k: v for k, v in c.items() if k != key}, BENCH)
        with pytest.raises(SystemExit, match="no file"):
            load(dict(c, **{key: "absent"}), BENCH)
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "half.py").write_text(
        "def make_weights(cfg, seed): ...\n")
    with pytest.raises(SystemExit, match="model_config"):
        spec.load_family(dict(c, family="half"), str(tmp_path))


def test_engine_kwargs_are_handed_over_as_given(monkeypatch):
    """``deployment.engine_kwargs`` reaches ``build_llm_deployment`` beside
    the six keys there are; absent, nothing is added."""
    import ray_tpu
    import ray_tpu.llm

    from harness import probes, served

    class Built(Exception):
        pass

    def build(model, params, **kwargs):
        raise Built(kwargs)

    monkeypatch.setattr(ray_tpu, "init", lambda **kw: None)
    monkeypatch.setattr(ray_tpu.llm, "build_llm_deployment", build)
    c = cfg("toy-gqa")
    six = {"name": "llm", "engine": "continuous", "max_batch": 4,
           "page_size": 16, "n_pages": 64}
    for extra in ({}, {"prefix_cache": False, "sized_for_a_later_cache": 3}):
        dep = dict(c["deployment"], **({"engine_kwargs": extra} if extra else {}))
        engine_probes = probes.EngineProbes()
        sv = served.Served(dict(c, deployment=dep), None, engine_probes, BENCH)
        try:
            with pytest.raises(Built) as built:
                sv.__enter__()
        finally:
            engine_probes.uninstall()
        kwargs = dict(built.value.args[0])
        assert kwargs.pop("tokenizer") is sv.tok
        assert kwargs == {**six, **extra}


# -- a family that no file under benchmarks/ knows ---------------------------
# Its configuration names its sizes in words of its own, so that a harness
# that read a model's shape by key would fail on it. Its count of a decode
# step caps every context at a window, which needs the contexts one by one.
WINDOWED_FAMILY = '''
import jax
import jax.numpy as jnp

SHAPES = {
    "ln1": ("width",), "ln2": ("width",), "wq": ("width", "q"),
    "wk": ("width", "kv"), "wv": ("width", "kv"), "wo": ("q", "width"),
    "w_gate": ("width", "ffn"), "w_up": ("width", "ffn"),
    "w_down": ("ffn", "width"),
}


def _sizes(cfg):
    return dict(cfg, q=cfg["heads"] * cfg["head_size"],
                kv=cfg["kv_heads"] * cfg["head_size"])


def make_weights(cfg, seed):
    n, dt = _sizes(cfg), jnp.dtype(cfg["torch_dtype"])
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))

    def leaf(i, shape, scale):
        if len(shape) - (shape[0] == n["depth"]) == 1:
            return jnp.ones(shape, dt)
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        return (x * scale).astype(dt)

    @jax.jit
    def build():
        blocks = {
            name: leaf(i, (n["depth"], *(n[k] for k in dims)), n[dims[0]] ** -0.5)
            for i, (name, dims) in enumerate(sorted(SHAPES.items()))
        }
        return {
            "embed": leaf(100, (n["vocab_size"], n["width"]), 0.02),
            "blocks": blocks,
            "ln_f": jnp.ones((n["width"],), dt),
            "head": leaf(101, (n["width"], n["vocab_size"]), n["width"] ** -0.5),
        }

    return jax.block_until_ready(build())


def model_config(cfg):
    from ray_tpu.models import transformer as tfm

    model = tfm.ModelConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["width"],
        n_layers=cfg["depth"], n_heads=cfg["heads"],
        n_kv_heads=cfg["kv_heads"], d_ff=cfg["ffn"],
        max_seq_len=cfg["deployment"]["max_context_tokens"],
        rope_theta=float(cfg["theta"]), dtype=jnp.dtype(cfg["torch_dtype"]),
    )
    if model.head_dim != cfg["head_size"]:
        raise ValueError("the program derives another head size")
    return model


def decode_step_work(cfg, contexts):
    kept = sum(min(c, cfg["window"]) for c in contexts)
    return 1000 * kept, 10 * kept


def decode_token_flops(cfg, context):
    return 1000 * min(context, cfg["window"])


def prefill_flops(cfg, prompt_len):
    return 1000 * prompt_len
'''

# The plain reference of the same mathematics under the temporary family's
# own key names: the dense decoder's, found by path, not by import.
WINDOWED_REFERENCE = '''
import importlib.util

_spec = importlib.util.spec_from_file_location("dense_for_windowed", {dense!r})
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)


def reference_logits(params, cfg, tokens, rows, quant=None):
    return _dense.logits_at(
        params, tokens, rows, heads=cfg["heads"], kv_heads=cfg["kv_heads"],
        theta=float(cfg["theta"]), eps=float(cfg["eps"]), quant=quant,
    )
'''

FAMILY_READER = '''
from harness import spec


def read(run):
    family = spec.load_family(run.cfg, run.base)
    steps = [
        family.decode_step_work(run.cfg, ctxs)[0]
        for t, ctxs in run.decode_log if run.t_open <= t < run.t_close
    ]
    return sum(steps) / len(steps) if steps else None
'''


def snapshot(top):
    seen = {}
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            st = os.stat(os.path.join(d, f))
            seen[os.path.join(d, f)] = (st.st_size, st.st_mtime_ns)
    return seen


def test_a_family_is_added_with_files_alone_and_run(tmp_path):
    """``test_spec.test_a_cell_is_added_with_files_alone`` taken as far as
    a run: family, reference, configuration, mix, cell and reader are new
    files under a directory of their own, ``BENCHMARK.json`` has one more
    entry of each, and the whole run comes out correct through them."""
    import jax

    before = snapshot(BENCH)
    base = tmp_path / "extra"
    for d in ("configs", "traffic", "workloads", "layer_metrics", "families",
              "references"):
        (base / d).mkdir(parents=True)
    toy = os.path.join(os.path.dirname(__file__), "toy")
    (base / "families" / "windowed.py").write_text(WINDOWED_FAMILY)
    (base / "references" / "windowed_plain.py").write_text(
        WINDOWED_REFERENCE.format(
            dense=os.path.join(BENCH, "references", "dense_gqa.py")))
    dense = cfg("toy-gqa")
    (base / "configs" / "later.json").write_text(json.dumps({
        "name": "later", "family": "windowed", "reference": "windowed_plain",
        "width": 256, "depth": 4, "heads": 8, "kv_heads": 4, "head_size": 32,
        "ffn": 768, "window": 4, "theta": 1e6, "eps": 1e-6,
        "vocab_size": 4096, "torch_dtype": "bfloat16",
        "deployment": dense["deployment"],
    }))
    with open(os.path.join(toy, "traffic", "toy.json")) as f:
        (base / "traffic" / "bursty.json").write_text(f.read())
    with open(os.path.join(toy, "workloads", "toy-gqa.toy.json")) as f:
        check = json.load(f)["check"]
    (base / "workloads" / "later.bursty.json").write_text(json.dumps(
        {"config": "later", "traffic": "bursty", "why": "x", "check": check}))
    (base / "layer_metrics" / "family_step_flops.py").write_text(FAMILY_READER)
    (base / "layer_metrics" / "serve_mfu_pct.later.json").write_text(
        '{"same_as": "serve_mfu_pct"}')
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "later", "source": "x", "reduced": [], "why": "x",
        "file": os.path.relpath(base / "configs" / "later.json", ROOT)})
    bench["workloads"].append({
        "name": "later.bursty", "config": "later", "traffic": "bursty",
        "chips": 1, "why": "x"})
    bench["per_layer"] += [
        {"name": "family_step_flops", "unit": "flop", "better": "lower",
         "source": "program_counter", "layer": "kernels",
         "moves": "tokens_per_s", "workloads": ["later.bursty"]},
        {"name": "serve_mfu_pct.later", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "whole step",
         "moves": "tokens_per_s", "workloads": ["later.bursty"]},
    ]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))

    cell = spec.load_cell("later.bursty", str(path))
    assert not set(cell.cfg) & {
        "hidden_size", "num_attention_heads", "intermediate_size",
        "num_key_value_heads", "head_dim", "num_hidden_layers"}
    # a seed no other run of these tests has: the program's prefix cache
    # outlives a deployment and tells weights apart by their first leaf,
    # which is a norm's vector of ones (PERF.md, Open questions), so two
    # runs of one process on one seed's prompts must not differ in weights
    res = bench_run.run_cell(
        cell, 71, 3.0, True, jax.devices()[:1], peaks.PEAKS["TPU v5 lite"])
    assert res["correct"] is True and res["failed"] == 0
    c = res["compared"]
    assert c["compared_tokens"]["value"] > 50
    assert c["gross_gaps"]["value"] == 0
    got = res["metrics"]
    # every prompt of the mix is longer than the window of 4, so the
    # temporary family counts 1000 x 4 for each live slot of a step: its
    # own count, over contexts taken one by one, and nobody else's
    assert got["family_step_flops"]["value"] == pytest.approx(
        4000 * got["decode_batch_mean"]["value"], rel=1e-12)
    assert got["family_step_flops"]["unit"] == "flop"
    # the benchmark's own reader of the whole step's share, on the temporary
    # family's counts: a request is at most 1000 x (64 prompt tokens + 4 x 24
    # answered), and under 40 requests fit the window; one token of the dense
    # decoder alone would be 8.4 MFLOP
    mfu = got["serve_mfu_pct.later"]["value"]
    assert 0 < mfu < 100.0 * 40 * 160_000 / (3.0 * 197e12)
    assert snapshot(BENCH) == before
