"""LLM serving with continuous batching, paged KV, and token streaming.

A Serve deployment hosts the ContinuousBatchingEngine; the async HTTP
proxy exposes POST /llm (full response) and POST /llm/stream (Server-Sent
Events relayed from a mutable-object Channel the replica writes into).
Run: PYTHONPATH=. python examples/llm_streaming_serve.py
"""
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402

import ray_tpu  # noqa: E402
import ray_tpu.serve as serve  # noqa: E402
from ray_tpu.llm import ContinuousBatchingEngine, GenerationConfig  # noqa: E402
from ray_tpu.models import transformer as tfm  # noqa: E402


def main():
    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 8})

    @serve.deployment(name="llm")
    class LLM:
        def __init__(self):
            cfg = tfm.ModelConfig(
                vocab_size=258 + 0,
                d_model=128,
                n_layers=2,
                n_heads=4,
                n_kv_heads=2,
                d_ff=256,
                max_seq_len=256,
                dtype=jnp.float32,
            )
            self.engine = ContinuousBatchingEngine(
                cfg, max_batch=4, page_size=16, n_pages=64
            )

        def __call__(self, payload):
            gen = GenerationConfig(
                max_new_tokens=int(payload.get("max_new_tokens", 16))
            )
            return {
                "text": self.engine.generate([payload["prompt"]], gen)[0]
            }

        def stream_to(self, writer, payload):
            gen = GenerationConfig(
                max_new_tokens=int(payload.get("max_new_tokens", 16))
            )
            prompt = self.engine.tokenizer.encode(payload["prompt"])
            n = 0
            for tok in self.engine.stream_ids(prompt, gen):
                writer.write(int(tok))
                n += 1
            writer.close_channel()
            return n

    serve.run(LLM.bind())
    port = serve.start_http_proxy(port=0)
    base = f"http://127.0.0.1:{port}"

    req = urllib.request.Request(
        f"{base}/llm",
        data=json.dumps({"prompt": "hello", "max_new_tokens": 8}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        print("full response:", json.loads(r.read())["result"])

    req = urllib.request.Request(
        f"{base}/llm/stream",
        data=json.dumps({"prompt": "hello", "max_new_tokens": 8}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        toks, event = [], "message"
        for line in r.read().decode().splitlines():
            if line.startswith("event: "):
                event = line[len("event: "):]
            elif line.startswith("data: "):
                if event == "error":
                    raise RuntimeError(f"stream failed: {line[6:]}")
                if event == "message":
                    toks.append(json.loads(line[len("data: "):]))
                event = "message"
    print("streamed tokens:", toks)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
