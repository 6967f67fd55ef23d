"""The benchmark's own spans and counters, wrapped from outside around the
calls into each layer of the serving engine. The program has no spans of
its own yet; when it gets them these wrappers go (PERF.md, section 7).

Spans are ``jax.profiler.TraceAnnotation``s, which land in the profiler's
trace on the host's clock beside the device's operations, and cost a flag
test when no trace is being taken. Counters are appended under the engine's
own lock (every wrapped call runs under it), one small tuple a call.
"""
from __future__ import annotations

import functools
import inspect
import time
from typing import List

import jax
import jax.monitoring as monitoring

SPAN_STEP = "bench.engine.step"
SPAN_ADMIT = "bench.engine.admit"
SPAN_PREFIX_INSERT = "bench.engine.prefix_insert"
SPAN_PREFILL = "bench.engine.prefill"
SPAN_DECODE = "bench.engine.decode"
SPAN_STREAM = "bench.router.stream"


class CompileCounter:
    """JAX compile events in this process, persistent-cache hits apart."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class EngineProbes:
    """Wraps ``ContinuousBatchingEngine`` (class methods, and the jitted
    programs each instance builds). ``decode_log`` holds one
    ``(host time, (context length of each live slot, ...))`` for each
    decode step, ``prefill_log`` one ``(host time, padded prompt tokens)``
    for each prefill program run.

    Every private name of the program that the benchmark touches is named
    in this file and looked for when the probes go in: a program that has
    renamed one fails the run there, by that name, and nowhere later."""

    # methods wrapped in a span, by the span's name
    SPANNED = {"step": SPAN_STEP, "_admit": SPAN_ADMIT,
               "_prefix_insert": SPAN_PREFIX_INSERT}
    # other names of the class, and of an engine once it has built its programs
    CLASS_NAMES = ("_build_fns", "_force_evict_active")
    ENGINE_NAMES = ("_decode_step", "_prefill", "slots", "pool", "_lock")

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.decode_log: List[tuple] = []
        self.prefill_log: List[tuple] = []
        self.engines: List[object] = []
        self._installed = None

    @staticmethod
    def _need(owner, names, what: str) -> None:
        missing = [n for n in names if not hasattr(owner, n)]
        if missing:
            raise RuntimeError(
                f"the benchmark's probes wrap {what} by name and the program "
                f"no longer has {missing}: mend benchmarks/harness/probes.py "
                "in a benchmark PR"
            )

    def install(self, engine_cls) -> None:
        if self._installed is not None:
            raise RuntimeError("probes are already installed")
        self._need(engine_cls, (*self.SPANNED, *self.CLASS_NAMES), "the engine")
        probes = self
        saved = {
            name: getattr(engine_cls, name)
            for name in (*self.SPANNED, "_build_fns")
        }
        self._installed = (engine_cls, saved)

        def spanned(name, fn):
            @functools.wraps(fn)
            def wrapper(self, *a, **kw):
                with jax.profiler.TraceAnnotation(name):
                    return fn(self, *a, **kw)

            return wrapper

        for name, span in self.SPANNED.items():
            setattr(engine_cls, name, spanned(span, saved[name]))

        def build_fns(engine):
            saved["_build_fns"](engine)
            probes._need(engine, probes.ENGINE_NAMES, "a built engine")
            probes.engines.append(engine)
            decode, prefill = engine._decode_step, engine._prefill
            prefill_sig = inspect.signature(prefill)
            if "t_pad" not in prefill_sig.parameters:
                raise RuntimeError(
                    "the prefill program no longer takes its padded length "
                    "as `t_pad`: mend benchmarks/harness/probes.py"
                )

            def decode_step(*a, **kw):
                live = tuple(s.pos + 1 for s in engine.slots if s.active)
                probes.decode_log.append((probes.clock(), live))
                with jax.profiler.TraceAnnotation(SPAN_DECODE):
                    return decode(*a, **kw)

            def prefill_fn(*a, **kw):
                t_pad = prefill_sig.bind(*a, **kw).arguments["t_pad"]
                probes.prefill_log.append((probes.clock(), int(t_pad)))
                with jax.profiler.TraceAnnotation(SPAN_PREFILL):
                    return prefill(*a, **kw)

            engine._decode_step = decode_step
            engine._prefill = prefill_fn

        engine_cls._build_fns = build_fns

    def uninstall(self) -> None:
        if self._installed is None:
            return
        engine_cls, saved = self._installed
        for name, fn in saved.items():
            setattr(engine_cls, name, fn)
        self._installed = None

    def end_live_answers(self) -> int:
        """Ends every answer that is live in a slot where it stands, through
        the engine's own bounded-drain eviction (the program has no public
        drain or cancel that a client can call; PERF.md section 7). Returns
        how many slots that ended."""
        ended = 0
        for e in self.engines:
            with e._lock:
                ended += sum(1 for s in e.slots if s.active)
                e._force_evict_active()
        return ended

    def release_engines(self) -> None:
        """Drop what the engines hold on the device, whatever it is called:
        every attribute of an engine or of its pool that holds a device
        array (pool, slot state, weights) or a compiled program."""
        def on_device(value) -> bool:
            program = callable(value) and not isinstance(value, type)
            return program or any(
                isinstance(leaf, jax.Array)
                for leaf in jax.tree_util.tree_leaves(value)
            )

        for e in self.engines:
            for owner in (e.pool, e):
                for name, value in list(vars(owner).items()):
                    if on_device(value):
                        setattr(owner, name, None)
        self.engines.clear()
