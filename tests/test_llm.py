"""LLM engine / batch processor / serving tests."""
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm import (
    ContinuousBatchingEngine,
    GenerationConfig,
    LLMProcessor,
    build_llm_deployment,
)
from ray_tpu.models import transformer as tfm

CFG = tfm.ModelConfig(
    vocab_size=258,
    d_model=32,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=64,
    max_seq_len=128,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def engine():
    return ContinuousBatchingEngine(CFG, max_batch=4, page_size=8, n_pages=64)


def test_generate_shapes_and_determinism(engine):
    out1 = engine.generate(["hello", "world!"], GenerationConfig(max_new_tokens=8))
    out2 = engine.generate(["hello", "world!"], GenerationConfig(max_new_tokens=8))
    assert len(out1) == 2
    assert out1 == out2  # greedy is deterministic


def test_cache_decode_matches_full_forward(engine):
    """The block that serves (paged KV, one token a step) and the block the
    train step runs (``tfm.forward``, no cache) choose the same tokens."""
    prompt = engine.tokenizer.encode("abc")
    ids = engine.generate_ids([prompt], GenerationConfig(max_new_tokens=4))[0]
    # replay: dense forward over prompt+gen, greedy argmax at each step
    seq = list(prompt)
    for step in range(4):
        logits = tfm.forward(engine.params, jnp.asarray([seq]), CFG)
        nxt = int(jnp.argmax(logits[0, -1]))
        assert nxt == ids[step], f"divergence at step {step}"
        seq.append(nxt)


def test_sampling_with_temperature(engine):
    """A request's sampling follows its own seed: one seed gives one answer
    wherever in the batch it runs (what lets a stream resume on another
    replica), another seed another answer."""
    prompt = engine.tokenizer.encode("x")

    def sample(seed, copies):
        return engine.generate_ids(
            [prompt] * copies,
            GenerationConfig(
                max_new_tokens=8, temperature=1.5, seed=seed, eos_token=-1
            ),
        )

    same = sample(7, 4)
    assert len({tuple(o) for o in same}) == 1
    others = [sample(seed, 1)[0] for seed in (8, 9, 10)]
    assert len({tuple(o) for o in [same[0], *others]}) > 1


def test_variable_length_batch(engine):
    prompts = [engine.tokenizer.encode(p) for p in ["a", "longer prompt here"]]
    outs = engine.generate_ids(prompts, GenerationConfig(max_new_tokens=4, eos_token=-1))
    assert all(len(o) == 4 for o in outs)


def test_batch_processor_over_dataset():
    import ray_tpu.data as rdata

    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 4, "memory": 1e9})
    try:
        ds = rdata.from_items(
            [{"prompt": f"item {i}"} for i in range(8)],
            override_num_blocks=2,
        )
        proc = LLMProcessor(
            CFG, generation=GenerationConfig(max_new_tokens=4), batch_size=4,
            max_len=64,
        )
        rows = proc.process(ds).take_all()
        assert len(rows) == 8
        assert all("generated_text" in r for r in rows)
    finally:
        ray_tpu.shutdown()


def test_batch_processor_gives_the_engines_own_text(engine):
    """Row for row, the text the engine gives for the same prompts and the
    same (greedy) ``GenerationConfig``."""
    import ray_tpu.data as rdata

    prompts = [f"row {i} " + "x" * i for i in range(6)]
    gen = GenerationConfig(max_new_tokens=5)
    want = engine.generate(prompts, gen)
    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 4, "memory": 1e9})
    try:
        ds = rdata.from_items(
            [{"prompt": p} for p in prompts], override_num_blocks=2
        )
        rows = LLMProcessor(
            CFG, generation=gen, batch_size=4, max_len=64
        ).process(ds).take_all()
    finally:
        ray_tpu.shutdown()
    assert {r["prompt"]: r["generated_text"] for r in rows} == dict(
        zip(prompts, want)
    )


def test_llm_serving():
    import ray_tpu.serve as serve

    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 4, "memory": 1e9})
    try:
        handle = serve.run(build_llm_deployment(CFG))
        out = ray_tpu.get(
            handle.remote({"prompt": "hi", "max_new_tokens": 4}), timeout=120
        )
        assert out["prompt"] == "hi"
        assert isinstance(out["generated_text"], str)
        assert serve.get_router("llm").resumable
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_default_deployment_streams_through_the_router(engine):
    """``build_llm_deployment`` with no engine named serves token by token
    through ``router.stream``, and the pieces are the engine's own tokens."""
    import ray_tpu.serve as serve

    tok = engine.tokenizer
    want = engine.generate_ids(
        [tok.encode("hi")], GenerationConfig(max_new_tokens=6)
    )[0]
    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 4, "memory": 1e9})
    try:
        serve.run(build_llm_deployment(CFG))
        pieces = list(
            serve.get_router("llm").stream(
                {"prompt": "hi", "max_new_tokens": 6}
            )
        )
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    assert pieces == [tok.decode([t]) for t in want]


@pytest.mark.parametrize(
    "call, error",
    [
        (lambda: build_llm_deployment(CFG, engine="dense"), ValueError),
        (lambda: build_llm_deployment(CFG, max_len=64), TypeError),
        (lambda: GenerationConfig(top_k=4), TypeError),
    ],
    ids=["engine_dense", "max_len", "top_k"],
)
def test_what_went_with_the_dense_engine_is_refused(call, error):
    """One engine: no other can be named, and the two parameters that only
    the dense engine read are gone with it (top-k sampling is offered
    nowhere)."""
    with pytest.raises(error):
        call()


def test_llm_deployment_streams_over_http():
    """build_llm_deployment streams decoded token text via
    POST /<name>/stream with zero user code."""
    import json
    import urllib.request

    import pytest as _pytest

    _pytest.importorskip("aiohttp")
    import jax.numpy as jnp

    import ray_tpu.serve as serve

    cfg = tfm.ModelConfig(
        vocab_size=258,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=128,
        dtype=jnp.float32,
    )
    import ray_tpu

    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 4})
    serve.run(
        build_llm_deployment(
            cfg, name="sllm", max_batch=2,
            page_size=8, n_pages=32,
        )
    )
    port = serve.start_http_proxy(port=0)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sllm/stream",
        data=json.dumps({"prompt": "hi", "max_new_tokens": 6}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        body = r.read().decode()
    toks, event = [], "message"
    for line in body.splitlines():
        if line.startswith("event: "):
            event = line[len("event: "):]
        elif line.startswith("data: "):
            if event == "message":
                toks.append(json.loads(line[len("data: "):]))
            event = "message"
    try:
        assert len(toks) == 6 and all(isinstance(t, str) for t in toks)
        assert "event: end" in body
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
