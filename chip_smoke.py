"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the three programs this repo puts on a TPU, each through the entry
point a user would call, at the full width of the widest model the repo
names, and checks every answer against a plain reference:

  sched    the head's scheduling loop over a 1,024-node synthetic fleet with
           the scheduler kernels on the chip, then the README quick start
  serve    serve.run(build_llm_deployment(...)) answering eight requests,
           then a short Pallas-decode run
  train    JaxTrainer taking four make_train_step steps
  cluster  a multi-process Cluster whose head (this process) holds the chip

``--multichip`` runs, alone, the train step under two four-chip meshes and
the one-device loss it is compared with.

One process touches the chip: agents and workers started by the cluster
phase are CPU processes by construction (cluster/agent.py ``_worker_env``).
Every line printed before the last is smoke output — wall seconds that
include compilation, on whatever else the host was doing — never a metric.
Any failed check raises; nothing is caught and carried past. The last line
of stdout is the one the driver reads.

    python chip_smoke.py              # one chip
    python chip_smoke.py --multichip  # one process, four chips
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import re
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

SEED = 0

# A generated token passes when its float32-reference logit lies within
# this much of the reference maximum at its position. Why a margin and not
# token equality, and why this size: CHANGES.md, PR 21.
LOGIT_MARGIN = 0.5
# |train loss - reference loss| bound, both about ln(vocab) ~ 10.4: the two
# differ by bf16 rounding inside attention only (flash kernel vs fused XLA).
LOSS_TOL = 0.05
# multichip: |mesh loss - one-device loss|; the mesh changes the order of
# bf16 reductions (tp all-reduces, pipeline microbatches), nothing else.
MESH_LOSS_TOL = 0.05


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at. FULL is what the chip gets; tests/
    test_chip_smoke.py runs the same phase functions at TOY on the CPU."""

    # sched
    sim_nodes: int
    sim_demands: int
    solve_nodes: int
    solve_shapes: int
    # model (serve + train + multichip)
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    max_seq_len: int
    dtype: str
    # serve
    max_batch: int
    n_pages: int
    prompt_lens: tuple
    new_tokens: int
    # train
    batch: int
    seq: int
    steps: int
    mesh_steps: int
    # cluster
    cluster_tasks: int


FULL = Sizes(
    sim_nodes=1024, sim_demands=100_000, solve_nodes=8192, solve_shapes=1024,
    # the widest configuration of the train step the repo names
    vocab=32_000, d_model=2048, n_layers=12, n_heads=16, n_kv_heads=16,
    d_ff=5504, max_seq_len=1024, dtype="bfloat16",
    max_batch=8, n_pages=2048,
    # five prefill buckets of 16-token pages: 64, 128, 256, 512, 768
    prompt_lens=(64, 120, 128, 250, 256, 500, 512, 768), new_tokens=64,
    batch=8, seq=1024, steps=4, mesh_steps=3,
    cluster_tasks=1000,
)

TOY = Sizes(
    sim_nodes=64, sim_demands=3000, solve_nodes=64, solve_shapes=16,
    vocab=512, d_model=128, n_layers=2, n_heads=4, n_kv_heads=4,
    d_ff=256, max_seq_len=256, dtype="bfloat16",
    max_batch=4, n_pages=64,
    prompt_lens=(16, 30, 32, 60, 64, 100, 112, 128), new_tokens=8,
    batch=4, seq=256, steps=4, mesh_steps=2,
    cluster_tasks=40,
)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"smoke": phase, **fields}, default=str), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


@contextlib.contextmanager
def env(**kv):
    """Set the program's existing environment knobs for a block."""
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update({k: str(v) for k, v in kv.items()})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class CompileClock:
    """Seconds JAX spent in backend compilation (cache retrieval included)
    and persistent-cache hits, read per phase. Every thread counts, the
    scheduler's background prewarm of its bucket grid included."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        out = {
            "compile_s": round(self.seconds, 2),
            "compiles": self.compiles,
            "persistent_cache_hits": self.cache_hits,
        }
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        return out


def memory(dev) -> dict:
    """``peak_bytes_in_use`` is the process's high-water mark, not the
    phase's: a phase that does not raise it used at most that much.
    ``live_array_bytes`` is what Python still references when the phase
    ends (after a collection): what the next phase starts on top of."""
    import gc

    import jax

    gc.collect()
    stats = dev.memory_stats() or {}
    return {
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use": stats.get("bytes_in_use"),
        "live_array_bytes": sum(x.nbytes for x in jax.live_arrays()),
    }


def model_config(sz: Sizes, **over):
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm

    kw = dict(
        vocab_size=sz.vocab, d_model=sz.d_model, n_layers=sz.n_layers,
        n_heads=sz.n_heads, n_kv_heads=sz.n_kv_heads, d_ff=sz.d_ff,
        max_seq_len=sz.max_seq_len, dtype=jnp.dtype(sz.dtype),
    )
    kw.update(over)
    return tfm.ModelConfig(**kw)


@contextlib.contextmanager
def children_print_to_stderr():
    """Processes started inside the block inherit stderr as their stdout:
    agents and workers announce themselves there, and this script's stdout
    carries its own lines only."""
    sys.stdout.flush()
    saved = os.dup(1)
    os.dup2(2, 1)
    try:
        yield
    finally:
        os.dup2(saved, 1)
        os.close(saved)


@contextlib.contextmanager
def reference_attention():
    """Evaluate ``tfm.forward`` / ``tfm.loss_fn`` through
    ``attention_reference`` whatever the backend: the plain reference the
    kernels are checked against. The model has no option for this and
    gets none; the check steers it from outside."""
    from unittest import mock

    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops.layers import attention_reference

    with mock.patch.object(
        tfm,
        "_causal_attention",
        lambda q, k, v, mesh=None: attention_reference(q, k, v, causal=True),
    ):
        yield


# ---------------------------------------------------------------------------
# native libraries
# ---------------------------------------------------------------------------


def native_phase() -> None:
    """Build the five native libraries from the committed sources (a copied
    tree may carry a stale ``_build/``) and load each; a compiler failure
    raises instead of leaving the Python paths behind ``native_*`` to run."""
    import ctypes

    from ray_tpu.native.build import rebuild_all

    t0 = time.perf_counter()
    paths = rebuild_all()
    for path in paths.values():
        ctypes.CDLL(path)
    emit(
        "native",
        built_and_loaded=sorted(paths),
        wall_s=round(time.perf_counter() - t0, 2),
    )


# ---------------------------------------------------------------------------
# sched
# ---------------------------------------------------------------------------


def _replay_sim(sz: Sizes, res: dict) -> dict:
    """The sim's assignments replayed in NumPy, independent of the kernels:
    no node over-committed on any resource, every request delivered exactly
    once, every request left unplaced fits on no node."""
    import numpy as np

    from ray_tpu.scheduler.sim import build_demand_maps

    demands = build_demand_maps(sz.sim_demands, SEED)
    assignments = res["assignments"]
    names = ("CPU", "memory")
    cap = np.array([64.0, 256.0])  # run_sim's per-node defaults
    used = np.zeros((sz.sim_nodes, 2))
    unplaced = []
    for i, d in enumerate(demands):
        nid = assignments.get(f"sim-{i}")
        if nid is None:
            unplaced.append(d)
            continue
        row = int(nid.rsplit("-", 1)[1])
        used[row] += [d.get(n, 0.0) for n in names]
    check(
        res["delivered"] == len(assignments),
        f"{res['delivered']} grants for {len(assignments)} distinct requests",
    )
    check(bool((used <= cap + 1e-4).all()), "a node is over-committed")
    free = cap - used
    for d in unplaced:
        need = np.array([d.get(n, 0.0) for n in names])
        check(
            not bool((free >= need - 1e-6).all(axis=1).any()),
            f"unplaced request {d} still fits on a node",
        )
    return {
        "placed": len(assignments),
        "unplaced": len(unplaced),
        "max_node_cpu_used": float(used[:, 0].max()),
    }


def sched_phase(sz: Sizes, platform: str, clock: CompileClock, dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu
    from ray_tpu.core.scheduling_strategies import (
        PlacementGroupSchedulingStrategy,
    )
    from ray_tpu.scheduler.device import elastic_pack_solve
    from ray_tpu.scheduler.sim import run_sim

    t0 = time.perf_counter()
    # -- the head's loop at cluster size, kernels on `platform` ------------
    with env(RAY_TPU_SCHED_PLATFORM=platform, RAY_TPU_DEVICE_SCHEDULER="1"):
        res = run_sim(
            num_nodes=sz.sim_nodes, num_demands=sz.sim_demands,
            pipeline=True, collect_assignments=True, seed=SEED,
        )
    check(res["completed"], "the sim timed out")
    check(res["delivered"] == sz.sim_demands, "not every request delivered")
    check(
        res["device_platform"] == platform,
        f"rounds ran on {res['device_platform']!r}, not {platform!r}",
    )
    dstats = res["device_stats"]
    check(
        dstats["rounds"] == res["head_sched_rounds"] > 0,
        f"{res['head_sched_rounds']} head rounds but {dstats['rounds']} "
        "device rounds: some ran on the host model",
    )
    replay = _replay_sim(sz, res)
    # -- the same stream on the NumPy golden model -------------------------
    with env(RAY_TPU_DEVICE_SCHEDULER="0"):
        gold = run_sim(
            num_nodes=sz.sim_nodes, num_demands=sz.sim_demands,
            pipeline=True, collect_assignments=True, seed=SEED,
        )
    check(gold["device_platform"] is None, "golden run used a device")
    check(gold["delivered"] == sz.sim_demands, "golden model dropped requests")
    differ = sum(
        1 for k, v in res["assignments"].items()
        if gold["assignments"].get(k) != v
    )
    emit(
        "sched.sim",
        nodes=sz.sim_nodes, demands=sz.sim_demands,
        device_platform=res["device_platform"],
        head_rounds=res["head_sched_rounds"],
        device_rounds=dstats["rounds"], ring_rounds=dstats["ring_rounds"],
        host_model_rounds=res["head_sched_rounds"] - dstats["rounds"],
        full_syncs=dstats["full_syncs"], delta_pushes=dstats["delta_pushes"],
        **replay,
        placements_differing_from_golden_model=differ,
        sim_wall_s=res["elapsed_s"], golden_wall_s=gold["elapsed_s"],
    )

    # -- README quick start on the in-process runtime ----------------------
    with env(RAY_TPU_SCHED_PLATFORM=platform, RAY_TPU_DEVICE_SCHEDULER="1"):
        rt = ray_tpu.init(
            num_nodes=4, resources_per_node={"CPU": 8, "TPU": 4}
        )
        try:
            @ray_tpu.remote
            def square(x):
                return x * x

            @ray_tpu.remote
            class Counter:
                def __init__(self):
                    self.n = 0

                def incr(self):
                    self.n += 1
                    return self.n

            out = ray_tpu.get([square.remote(i) for i in range(8)], timeout=120)
            check(out == [i * i for i in range(8)], f"tasks returned {out}")
            c = Counter.remote()
            check(
                [ray_tpu.get(c.incr.remote(), timeout=60) for _ in range(3)]
                == [1, 2, 3],
                "actor calls out of order",
            )

            @ray_tpu.remote(num_cpus=1)
            def where():
                return ray_tpu.get_runtime_context().get_node_id()

            pgs = {}
            for strategy in ("STRICT_SPREAD", "PACK"):
                pg = ray_tpu.placement_group(
                    [{"CPU": 1}] * 4, strategy=strategy
                )
                check(pg.wait(timeout_seconds=120), f"{strategy} PG not ready")
                nodes = ray_tpu.get(
                    [
                        where.options(
                            scheduling_strategy=PlacementGroupSchedulingStrategy(
                                placement_group=pg,
                                placement_group_bundle_index=i,
                            )
                        ).remote()
                        for i in range(4)
                    ],
                    timeout=120,
                )
                pgs[strategy] = len(set(nodes))
                ray_tpu.remove_placement_group(pg)
            check(pgs["STRICT_SPREAD"] == 4, "STRICT_SPREAD shared a node")
            check(pgs["PACK"] == 1, "PACK did not pack onto one node")
            ds = rt.device_state
            check(ds.device.platform == platform, "runtime scheduler device")
            check(ds.stats["rounds"] > 0, "no device round in the runtime")
            # a jnp array through the object plane stays a device array
            x = jnp.arange(4096, dtype=jnp.float32).reshape(64, 64) * 0.5
            y = ray_tpu.get(ray_tpu.put(x), timeout=60)
            check(isinstance(y, jax.Array), f"get returned {type(y)}")
            check(
                {d.platform for d in y.devices()} == {dev.platform},
                f"array came back on {y.devices()}",
            )
            check(bool(jnp.array_equal(x, y)), "array content changed")
            runtime_rounds = ds.stats["rounds"]
        finally:
            ray_tpu.shutdown()

    # -- one elasticity solve at fleet size --------------------------------
    rng = np.random.default_rng(SEED)
    avail = np.zeros((sz.solve_nodes, 16), dtype=np.float32)
    avail[:, 0] = rng.integers(8, 65, sz.solve_nodes)       # CPU
    avail[:, 1] = rng.integers(32, 257, sz.solve_nodes)     # memory
    shapes = np.zeros((sz.solve_shapes, 16), dtype=np.float32)
    shapes[:, 0] = rng.integers(1, 17, sz.solve_shapes) * 0.25
    shapes[:, 1] = rng.integers(0, 9, sz.solve_shapes)
    # about three times what the fleet holds: the solve has to refuse
    counts = rng.integers(1, 1000, sz.solve_shapes).astype(np.float32)
    placed, per_node = elastic_pack_solve(avail, shapes, counts)
    load = np.einsum("un,ur->nr", per_node.astype(np.float64), shapes)
    check(bool((load <= avail + 1e-3).all()), "solve over-committed a node")
    check(bool((placed <= counts + 1e-3).all()), "solve placed beyond demand")
    check(
        bool(np.allclose(per_node.sum(axis=1), placed, atol=1e-3)),
        "solve's per-node counts do not add up to placed",
    )
    check(0 < float(placed.sum()) < float(counts.sum()), "solve placed "
          f"{placed.sum()} of {counts.sum()} on a fleet a third that size")
    emit(
        "sched.quickstart",
        runtime_device_rounds=runtime_rounds,
        pg_nodes=pgs,
        put_get="jax.Array on " + dev.platform,
        solve=f"{sz.solve_nodes}x{sz.solve_shapes}",
        solve_backend=jax.default_backend(),
        solve_placed=float(placed.sum()),
        solve_demanded=float(counts.sum()),
    )
    emit(
        "sched", ok=True, wall_s=round(time.perf_counter() - t0, 2),
        **clock.take(), **memory(dev),
    )


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


class IdTokenizer:
    """One character per token id, both ways, so a deployment's text
    answers give back the exact ids (the engines' ByteTokenizer can name
    258 of them). No ``eos``: every request runs its full length."""

    BASE = 0x100  # ids land in U+0100..U+7E00: no controls, no surrogates

    def encode(self, text: str) -> List[int]:
        return [ord(ch) - self.BASE for ch in text]

    def decode(self, ids) -> str:
        return "".join(chr(self.BASE + int(i)) for i in ids)


def _check_against_reference(cfg, params, prompts, outs, where: str) -> dict:
    """Teacher-forced float32 dense forward (``tfm.forward``: no cache, no
    paging, ``attention_reference``) over prompt + generated tokens, all
    requests right-padded into one batch (causal, so padding cannot reach
    back). Each generated token's reference logit must lie within
    LOGIT_MARGIN of the reference maximum at its position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import transformer as tfm

    t_max = max(len(p) + len(o) for p, o in zip(prompts, outs))
    tokens = np.zeros((len(prompts), t_max), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        tokens[i, : len(p) + len(o)] = p + o
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with reference_attention():
        logits = jax.jit(lambda p, t: tfm.forward(p, t, cfg32))(
            params32, jnp.asarray(tokens)
        )
    check(bool(jnp.isfinite(logits).all()), f"{where}: reference not finite")
    worst, off_argmax, n = 0.0, 0, 0
    for i, (p, o) in enumerate(zip(prompts, outs)):
        # the logits at position j predict token j + 1
        rows = np.asarray(logits[i, len(p) - 1 : len(p) + len(o) - 1])
        chosen = rows[np.arange(len(o)), o]
        gap = rows.max(axis=1) - chosen
        worst = max(worst, float(gap.max()))
        off_argmax += int((gap > 0).sum())
        n += len(o)
    check(
        worst <= LOGIT_MARGIN,
        f"{where}: a generated token's reference logit is {worst:.3f} below "
        f"the reference maximum (margin {LOGIT_MARGIN})",
    )
    return {
        "tokens_checked": n,
        "tokens_not_reference_argmax": off_argmax,
        "worst_logit_gap": round(worst, 4),
        "logit_margin": LOGIT_MARGIN,
    }


def serve_phase(sz: Sizes, on_chip: bool, clock: CompileClock, dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu.llm import (
        ContinuousBatchingEngine,
        GenerationConfig,
        build_llm_deployment,
    )
    from ray_tpu.models import transformer as tfm

    t0 = time.perf_counter()
    cfg = model_config(sz)
    params = tfm.init_params(cfg, jax.random.PRNGKey(SEED))
    tok = IdTokenizer()
    rng = np.random.default_rng(SEED)
    prompts = [
        [int(t) for t in rng.integers(0, sz.vocab, n)] for n in sz.prompt_lens
    ]
    payloads = [
        {"prompt": tok.decode(p), "max_new_tokens": sz.new_tokens}
        for p in prompts
    ]

    # -- the server: router -> admission -> engine -> pool -> decode -------
    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 8})
    try:
        serve.run(
            build_llm_deployment(
                cfg, params, name="llm",
                max_batch=sz.max_batch, page_size=16, n_pages=sz.n_pages,
                tokenizer=tok,
            )
        )
        router = serve.get_router("llm")
        # the first request alone, consumed token by token
        streamed = [tok.encode(piece) for piece in router.stream(payloads[0])]
        check(
            all(len(s) == 1 for s in streamed),
            "a stream item was not one token",
        )
        outs = [[s[0] for s in streamed]]
        # the rest together, so slots fill and drain under each other
        pending = [router.submit(p) for p in payloads[1:]]
        for p, req in zip(payloads[1:], pending):
            reply = req.result(600.0)
            check(reply["prompt"] == p["prompt"], "reply for another prompt")
            outs.append(tok.encode(reply["generated_text"]))
        stats = router.stats()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    for p, o in zip(prompts, outs):
        check(
            len(o) == sz.new_tokens,
            f"{len(o)} tokens for a {len(p)}-token prompt, "
            f"wanted {sz.new_tokens}",
        )
    served = {
        "requests": len(outs),
        "prompt_lens": list(sz.prompt_lens),
        "new_tokens_each": sz.new_tokens,
        # the engine reads the platform: the Pallas kernel on a TPU
        "attention_path": "pallas kernel" if on_chip else "xla gather",
        "pool": f"{sz.n_pages} pages x 16 tokens",
        "pool_bytes": 2 * cfg.n_layers * cfg.n_kv_heads * sz.n_pages * 16
        * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize,
        "router_codes": stats.get("codes"),
        "serve_wall_s": round(time.perf_counter() - t0, 2),
        **memory(dev),
    }
    ref = _check_against_reference(cfg, params, prompts, outs, "serve")
    emit("serve.requests", **served, **ref)

    # -- the Pallas decode kernel against the XLA formulation, same pool ----
    short = [p[:48] for p in prompts[: sz.max_batch]]
    gen = GenerationConfig(max_new_tokens=min(sz.new_tokens, 16))
    per_path = {}
    # on the chip the engine takes the compiled kernel by itself; the
    # rehearsal on the CPU runs it interpreted, through the tests' hook,
    # which also holds the chip's engine to the gather for the comparison
    for name, kernel in (
        ("gather", None), ("pallas", "compiled" if on_chip else "interpret"),
    ):
        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=sz.max_batch, page_size=16,
            n_pages=sz.n_pages, tokenizer=tok,
        )
        check(
            eng._attn_kernel == ("compiled" if on_chip else None),
            f"the engine chose {eng._attn_kernel!r} for its decode attention",
        )
        eng._attn_kernel = kernel
        per_path[name] = eng.generate_ids(short, gen)
        if kernel and on_chip:
            text = eng._decode_step.lower(
                eng.params, eng.pool.k, eng.pool.v, eng.block_tables,
                eng.positions, eng.cur_tokens, eng.active_mask, eng.temps,
                eng.seeds, eng.pool.state,
            ).compile().as_text()
            check(
                "tpu_custom_call" in text
                and "paged_attention_decode" in text,
                "the Pallas decode step holds no compiled kernel",
            )
        del eng
    pallas_ref = _check_against_reference(
        cfg, params, short, per_path["pallas"], "pallas decode"
    )
    _check_against_reference(
        cfg, params, short, per_path["gather"], "gather decode"
    )
    same = sum(
        int(a == b)
        for pa, ga in zip(per_path["pallas"], per_path["gather"])
        for a, b in zip(pa, ga)
    )
    emit(
        "serve.pallas_decode",
        pool=f"{sz.n_pages} pages x 16 tokens",
        compiled_kernel=on_chip, interpreted=not on_chip,
        tokens_equal_to_gather_path=same, **pallas_ref,
    )
    emit(
        "serve", ok=True, wall_s=round(time.perf_counter() - t0, 2),
        **clock.take(), **memory(dev),
    )


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _flash_kernels_in(text: str) -> Dict[str, int]:
    """Compiled Pallas calls in a train step's text. The forward kernel
    takes (q, k, v); the two backward kernels take six operands."""
    calls = [ln for ln in text.splitlines() if '"tpu_custom_call"' in ln]
    backward = sum("/*index=5*/" in ln for ln in calls)
    return {"forward": len(calls) - backward, "backward": backward}


def _train_batch(sz: Sizes):
    import jax
    import jax.numpy as jnp

    return jax.random.randint(
        jax.random.PRNGKey(SEED + 1), (sz.batch, sz.seq), 0, sz.vocab,
        jnp.int32,
    )


def _optimizer():
    import jax.numpy as jnp
    import optax

    return optax.adam(3e-4, mu_dtype=jnp.bfloat16)


def _train_loop(config: Dict[str, Any]) -> None:
    """``train_loop_per_worker``: ``steps`` train steps on one repeated
    batch, reporting every loss, the reference loss of the initial
    parameters, and what the compiled step holds."""
    import jax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.train import report

    sz, on_chip = config["sizes"], config["on_chip"]
    cfg = model_config(sz, remat=True)
    opt = _optimizer()
    params = tfm.init_params(cfg, jax.random.PRNGKey(SEED))
    opt_state = opt.init(params)
    tokens = _train_batch(sz)
    with reference_attention():
        ref_loss = float(
            jax.jit(lambda p, t: tfm.loss_fn(p, t, cfg))(params, tokens)
        )
    step = jax.jit(tfm.make_train_step(cfg, opt), donate_argnums=(0, 1))
    compiled = step.lower(params, opt_state, tokens).compile()
    kernels = _flash_kernels_in(compiled.as_text()) if on_chip else None
    mem = compiled.memory_analysis()
    for i in range(sz.steps):
        params, opt_state, loss = compiled(params, opt_state, tokens)
        report({"step": i + 1, "loss": float(loss)})
    report(
        {
            "reference_loss": ref_loss,
            "flash_kernels": kernels,
            "step_program_bytes": (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            ) if mem is not None else None,
            "n_params": sum(x.size for x in jax.tree.leaves(params)),
        }
    )


def train_phase(sz: Sizes, on_chip: bool, clock: CompileClock, dev) -> None:
    import math

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    t0 = time.perf_counter()
    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 4})
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_train_") as tmp:
            result = JaxTrainer(
                _train_loop,
                train_loop_config={"sizes": sz, "on_chip": on_chip},
                scaling_config=ScalingConfig(num_workers=1),
                run_config=RunConfig(name="chip-smoke", storage_path=tmp),
            ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    *steps, final = result.metrics_history
    losses = [m["loss"] for m in steps]
    check(len(losses) == sz.steps, f"{len(losses)} steps reported")
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(
        abs(losses[0] - final["reference_loss"]) <= LOSS_TOL,
        f"step-1 loss {losses[0]} vs reference {final['reference_loss']}",
    )
    if on_chip:
        k = final["flash_kernels"]
        check(
            k["forward"] >= 1 and k["backward"] >= 2,
            f"compiled step holds {k} flash kernels",
        )
    emit(
        "train", ok=True,
        shape=f"B={sz.batch} T={sz.seq} L={sz.n_layers} d={sz.d_model}",
        n_params=final["n_params"], remat=True,
        attention_path="pallas flash fwd+bwd (compiled)" if on_chip
        else "as the test steered it",
        flash_kernels=final["flash_kernels"],
        losses=[round(x, 4) for x in losses],
        reference_loss=round(final["reference_loss"], 4),
        loss_tol=LOSS_TOL,
        step_program_bytes=final["step_program_bytes"],
        wall_s=round(time.perf_counter() - t0, 2),
        **clock.take(), **memory(dev),
    )


# ---------------------------------------------------------------------------
# cluster
# ---------------------------------------------------------------------------


def _inc(x):
    return x + 1


def _worker_backend():
    """Runs in a worker process: what JAX would use there."""
    import jax

    return os.environ.get("JAX_PLATFORMS"), jax.default_backend()


def cluster_phase(sz: Sizes, platform: str, clock: CompileClock, dev) -> None:
    """The multi-process tree with the head's scheduler on ``platform``.
    The head lives in this process, which holds the chip; agents and
    workers are children that must never try to open it."""
    import ray_tpu
    from ray_tpu.cluster import Cluster
    from ray_tpu.core.runtime import set_runtime

    t0 = time.perf_counter()
    with env(RAY_TPU_SCHED_PLATFORM=platform, RAY_TPU_DEVICE_SCHEDULER="1"):
        cluster = Cluster()
        try:
            with children_print_to_stderr():
                cluster.add_node({"CPU": 16.0}, num_workers=4)
                cluster.add_node({"CPU": 16.0}, num_workers=4)
            client = cluster.client()
            set_runtime(client)
            try:
                inc = ray_tpu.remote(_inc).options(
                    num_cpus=0.25, max_retries=0
                )
                refs = [inc.remote(i) for i in range(sz.cluster_tasks)]
                out = ray_tpu.get(refs, timeout=600)
                check(
                    out == [i + 1 for i in range(sz.cluster_tasks)],
                    "cluster task results are not exact",
                )
                backends = ray_tpu.get(
                    [
                        ray_tpu.remote(_worker_backend)
                        .options(num_cpus=1)
                        .remote()
                        for _ in range(8)
                    ],
                    timeout=300,
                )
                check(
                    set(backends) == {("cpu", "cpu")},
                    f"a worker is not a CPU process: {set(backends)}",
                )
                # a placement group through the head: the bundle kernels
                # read the scheduler's resident arrays
                pg = ray_tpu.placement_group(
                    [{"CPU": 1}] * 2, strategy="STRICT_SPREAD"
                )
                check(pg.wait(timeout_seconds=120), "PG through the head")
                ray_tpu.remove_placement_group(pg)
                sched = client.query_state("sched")
                ds = cluster.head.device_state
                check(ds.device.platform == platform, "head scheduler device")
                check(
                    ds.stats["rounds"] > 0, "the head ran no device round"
                )
                check(
                    sched["device"]["rounds"] == ds.stats["rounds"],
                    "QueryState('sched') disagrees with the device state",
                )
                emit(
                    "cluster", ok=True,
                    nodes=2, workers_per_node=4, tasks=sz.cluster_tasks,
                    head_device=str(ds.device),
                    head_sched_rounds=sched["sched_rounds"],
                    head_device_rounds=ds.stats["rounds"],
                    worker_backends=sorted(set(backends)),
                    wall_s=round(time.perf_counter() - t0, 2),
                    **clock.take(), **memory(dev),
                )
            finally:
                set_runtime(None)
                client.shutdown()
        finally:
            cluster.shutdown()


# ---------------------------------------------------------------------------
# --multichip
# ---------------------------------------------------------------------------


def _shard_report(params, devices) -> dict:
    """Bytes of the parameter pytree each device holds."""
    import jax

    per_dev = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    return {"param_bytes_total": total, "param_bytes_per_device": per_dev}


def multichip_phase(sz: Sizes, clock: CompileClock, devices) -> None:
    """``make_train_step(cfg, opt, mesh)`` under dp2·tp2 and pp2·tp2 over
    four devices; step-1 loss of each against the one-device loss of the
    same parameters and batch, computed here first."""
    import math

    import jax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel import MeshConfig, build_mesh

    check(len(devices) >= 4, f"--multichip needs 4 devices, got {len(devices)}")
    devices = list(devices[:4])
    cfg = model_config(sz, remat=True)
    opt = _optimizer()
    tokens = _train_batch(sz)
    host_params = jax.device_get(
        tfm.init_params(cfg, jax.random.PRNGKey(SEED))
    )

    t0 = time.perf_counter()
    one = jax.device_put(host_params, devices[0])
    base = float(
        jax.jit(lambda p, t: tfm.loss_fn(p, t, cfg))(
            one, jax.device_put(tokens, devices[0])
        )
    )
    del one
    check(math.isfinite(base), f"one-device loss {base}")
    emit(
        "multichip.one_device", loss=round(base, 4),
        wall_s=round(time.perf_counter() - t0, 2), **clock.take(),
    )

    for name, mc in (
        ("dp2_tp2", MeshConfig(dp=2, tp=2)),
        ("pp2_tp2", MeshConfig(pp=2, tp=2)),
    ):
        t0 = time.perf_counter()
        mesh = build_mesh(mc, devices)
        params = tfm.shard_params(host_params, cfg, mesh)
        shards = _shard_report(params, devices)
        share = max(shards["param_bytes_per_device"].values())
        check(
            share < 0.75 * shards["param_bytes_total"],
            f"{name}: a device holds {share} of "
            f"{shards['param_bytes_total']} parameter bytes",
        )
        opt_state = opt.init(params)
        step = jax.jit(
            tfm.make_train_step(
                cfg, opt, mesh, num_microbatches=2 * mc.pp if mc.pp > 1 else 0
            ),
            donate_argnums=(0, 1),
        )
        compiled = step.lower(params, opt_state, tokens).compile()
        text = compiled.as_text()
        kernels = _flash_kernels_in(text)
        if devices[0].platform == "tpu":
            check(
                kernels["forward"] >= 1 and kernels["backward"] >= 2,
                f"{name}: compiled step holds {kernels} flash kernels",
            )
        losses = []
        for _ in range(sz.mesh_steps):
            params, opt_state, loss = compiled(params, opt_state, tokens)
            losses.append(float(loss))
        check(all(math.isfinite(x) for x in losses), f"{name}: {losses}")
        check(
            abs(losses[0] - base) <= MESH_LOSS_TOL,
            f"{name}: step-1 loss {losses[0]} vs one-device {base}",
        )
        check(losses[-1] < losses[0], f"{name}: loss did not fall {losses}")
        emit(
            f"multichip.{name}", ok=True,
            mesh=dict(mesh.shape), losses=[round(x, 4) for x in losses],
            one_device_loss=round(base, 4), mesh_loss_tol=MESH_LOSS_TOL,
            flash_kernels=kernels,
            collectives={
                op: len(re.findall(rf" {op}(?:-start)?\(", text))
                for op in ("all-reduce", "all-gather", "collective-permute",
                           "all-to-all", "reduce-scatter")
            },
            **shards,
            device_peak_bytes={
                d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devices
            },
            wall_s=round(time.perf_counter() - t0, 2), **clock.take(),
        )
        del params, opt_state, compiled


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--multichip", action="store_true",
        help="run only the four-chip mesh phase and its one-device comparison",
    )
    args = ap.parse_args(argv)

    import jax

    from ray_tpu.util.compile_cache import configure_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: jax.devices()[0] is {dev.platform!r}, not a TPU; "
            "this script proves the chip path and runs nowhere else",
            file=sys.stderr,
        )
        return 2
    configure_compile_cache()
    clock = CompileClock()
    emit(
        "start", device=str(dev), device_kind=dev.device_kind,
        n_devices=len(devices), jax=jax.__version__,
        compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or jax.config.jax_compilation_cache_dir,
        multichip=args.multichip,
    )
    native_phase()
    if args.multichip:
        multichip_phase(FULL, clock, devices)
    else:
        sched_phase(FULL, "tpu", clock, dev)
        serve_phase(FULL, True, clock, dev)
        train_phase(FULL, True, clock, dev)
        cluster_phase(FULL, "tpu", clock, dev)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
