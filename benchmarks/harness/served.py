"""The system under test, brought up through its normal entry points, and
the open-loop load that drives it.

``ray_tpu.init`` -> ``serve.run(build_llm_deployment(engine="continuous"))``
-> the deployment's ingress router -> ``stream_to`` on the replica -> the
engine's ``stream_ids`` -> ``step`` / ``_admit`` -> ``PagedKVPool``,
``_prefill``, ``_decode_step`` -> the token stream back to this process.
Every request is one client thread that reads its stream and stamps each
token on arrival; one dispatcher thread starts them when they are due.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import numpy as np

from . import probes as probes_mod
from . import spec, traffic


class IdTokenizer:
    """One character for each token id, both ways, so that text answers
    give back the exact ids. Surrogates are stepped over. No ``eos``: every
    request runs to its ``max_new_tokens``."""

    BASE = 0x100
    SURROGATES = (0xD800, 0xE000)

    def _char(self, i: int) -> str:
        cp = self.BASE + int(i)
        if cp >= self.SURROGATES[0]:
            cp += self.SURROGATES[1] - self.SURROGATES[0]
        return chr(cp)

    def _id(self, ch: str) -> int:
        cp = ord(ch)
        if cp >= self.SURROGATES[1]:
            cp -= self.SURROGATES[1] - self.SURROGATES[0]
        return cp - self.BASE

    def encode(self, text: str) -> List[int]:
        return [self._id(ch) for ch in text]

    def decode(self, ids) -> str:
        return "".join(self._char(i) for i in ids)


@dataclass
class Client:
    request: traffic.Request
    due_abs: float
    prompt: np.ndarray
    sent: Optional[float] = None
    done: Optional[float] = None
    stamps: List[float] = field(default_factory=list)
    pieces: List[str] = field(default_factory=list)
    error: Optional[str] = None
    cut: bool = False  # ended by the harness at the window's close
    stream: object = None
    thread: Optional[threading.Thread] = None


class Served:
    """Context manager around one deployment of one configuration."""

    def __init__(self, cfg: dict, params, probes: probes_mod.EngineProbes,
                 base: str):
        self.cfg, self.params, self.probes = cfg, params, probes
        self.base = base  # where the cell's own families/ is looked for
        self.tok = IdTokenizer()
        self.router = None
        self.cut_report: dict = {}  # what ended the answers live at the close

    def __enter__(self):
        import ray_tpu
        import ray_tpu.serve as serve
        from ray_tpu.llm import build_llm_deployment
        from ray_tpu.llm.continuous import ContinuousBatchingEngine

        cfg, dep = self.cfg, self.cfg["deployment"]
        model = spec.load_family(cfg, self.base).model_config(cfg)
        self.probes.install(ContinuousBatchingEngine)
        self._ray, self._serve = ray_tpu, serve
        ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 8})
        app = build_llm_deployment(
            model, self.params, name="llm", engine=dep["engine"],
            max_batch=dep["slots"], page_size=dep["page_size"],
            n_pages=dep["pool_pages"], tokenizer=self.tok,
            **dep.get("engine_kwargs", {}),
        )
        # one request thread for each slot of the engine
        app = app.deployment.options(
            ray_actor_options={"max_concurrency": dep["replica_concurrency"]}
        ).bind(*app.init_args, **app.init_kwargs)
        serve.run(app)
        self.router = serve.get_router("llm")
        return self

    def __exit__(self, *exc):
        try:
            self._serve.shutdown()
            self._ray.shutdown()
        finally:
            self.probes.uninstall()
        return False

    # -- one request ------------------------------------------------------
    def _client(self, c: Client, clock) -> None:
        payload = {
            "prompt": self.tok.decode(c.prompt),
            "max_new_tokens": int(c.request.max_new),
            "temperature": 0.0,
        }
        try:
            c.sent = clock()
            with jax.profiler.TraceAnnotation(probes_mod.SPAN_STREAM):
                c.stream = self.router.stream(payload)
            for piece in c.stream:
                c.stamps.append(clock())
                c.pieces.append(piece)
            c.done = clock()
        except Exception as exc:  # noqa: BLE001 - recorded, judged later
            c.error = repr(exc)

    def warm_up(self, schedule: List[traffic.Request], seed: int, page: int):
        """One short request for every prompt shape the schedule holds:
        its padded length (one prefill program each) and its count of full
        pages (one gather of the prefix cache's insert each)."""
        shapes = sorted({
            (-(-r.prompt_len // page), r.prompt_len // page) for r in schedule
        })
        warmed = []
        for i, (padded_pages, full_pages) in enumerate(shapes):
            length = (
                padded_pages * page if full_pages == padded_pages
                else full_pages * page + max(1, page // 2)
            )
            req = traffic.Request(-1 - i, 0.0, length, 2)
            c = Client(req, 0.0, traffic.prompt_ids(
                seed, 1_000_000 + i, length, self.cfg["vocab_size"]))
            self._client(c, time.perf_counter)
            if c.error or len(c.pieces) != 2:
                raise RuntimeError(
                    f"warm-up request of {length} tokens failed: "
                    f"{c.error or c.pieces!r}"
                )
            warmed.append(length)
        # the head's scheduler compiles its kernel's variants on a thread
        # of its own after init: let it end, so that none lands in the window
        for t in threading.enumerate():
            if t.name == "sched-prewarm":
                t.join(120.0)
        return warmed

    # -- the window -------------------------------------------------------
    def drive(self, schedule: List[traffic.Request], seed: int,
              seconds: float, ramp: float, clock=time.perf_counter,
              at_open: Callable[[], None] = lambda: None,
              during: Optional[Callable[[float, float], None]] = None):
        """Offers the schedule, returns (clients, t_open, t_close). The
        window is [t_open, t_close); arrivals stop at its close. What is
        still running then is not waited for and not cancelled either (a
        cancelled stream destroys its shm ring under the replica thread that
        writes it, PERF.md section 7): the engine's own bounded-drain
        eviction (``EngineProbes.end_live_answers``) ends every live answer
        where it stands, the streams close in the normal way, and such a
        request counts as ``cut``, neither finished nor failed.
        ``cut_report`` says how many were cut, how many of those had been
        queued and had no token yet, and how many slots the eviction
        ended: a request that ended short after the close and was not
        evicted is ``unexplained``, and fails the run.
        ``during(t_open, t_close)`` runs in this thread inside the window
        (the traced run takes its capture there)."""
        vocab = self.cfg["vocab_size"]
        t_open = clock() + 0.25 + ramp
        t_close = t_open + seconds
        clients = [
            Client(r, t_open + r.due,
                   traffic.prompt_ids(seed, r.index, r.prompt_len, vocab))
            for r in schedule
        ]
        closing = threading.Event()

        def dispatch():
            for c in clients:
                wait = c.due_abs - clock()
                if wait > 0 and closing.wait(wait):
                    return
                if closing.is_set():
                    return
                c.thread = threading.Thread(
                    target=self._client, args=(c, clock),
                    name=f"client-{c.request.index}", daemon=True,
                )
                c.thread.start()

        dispatcher = threading.Thread(
            target=dispatch, name="dispatcher", daemon=True
        )
        dispatcher.start()
        time.sleep(max(0.0, t_open - clock()))
        at_open()
        if during is not None:
            during(t_open, t_close)
        time.sleep(max(0.0, t_close - clock()))
        closing.set()
        dispatcher.join(10.0)
        t_cut = clock()
        deadline = t_cut + 120.0
        evicted = 0
        while any(c.thread.is_alive() for c in clients if c.thread):
            evicted += self.probes.end_live_answers()
            time.sleep(0.02)
            if clock() > deadline:
                break
        for c in clients:
            c.cut = (
                c.done is not None and c.done >= t_cut
                and len(c.pieces) < c.request.max_new
            )
        cut = [c for c in clients if c.cut]
        self.cut_report = {
            "cut": len(cut),
            "cut_before_first_token": sum(1 for c in cut if not c.pieces),
            "evicted_slots": evicted,
            "unexplained": max(0, len(cut) - evicted),
        }
        alive = [c.request.index for c in clients
                 if c.thread is not None and c.thread.is_alive()]
        if alive:
            raise RuntimeError(f"client threads did not end: {alive}")
        return clients, t_open, t_close
