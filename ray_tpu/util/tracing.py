"""Distributed trace-context propagation.

Capability analog of the reference's OpenTelemetry task tracing
(/root/reference/python/ray/util/tracing/tracing_helper.py: the ambient
span context is serialized into every task spec at submission and
re-installed around execution on the worker, so spans from every hop of
a task tree share one trace id).

Here the context is a small dict ``{"trace_id", "span_id"}`` carried in
``TaskSpec.trace`` / ``LeaseRequest.trace`` / direct-call items:

- the driver's first submission in a tree mints a trace id;
- the worker installs the received context (contextvar) around user-code
  execution, so NESTED submissions inherit the same trace id with the
  executing task as their parent span;
- every lifecycle event recorded against the task (head + local runtime
  timelines) carries ``trace_id``/``parent_id``, and the Chrome-trace
  export exposes them in ``args`` — one trace is filterable across every
  node it touched.
"""
from __future__ import annotations

import contextvars
import itertools
import sys
import threading
import time as _time
from collections import deque
from contextlib import contextmanager
from typing import List, Optional

from ray_tpu._ids import rand_hex
from ray_tpu.config import cfg

_ctx: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "ray_tpu_trace", default=None
)


def current() -> Optional[dict]:
    return _ctx.get()


def child_context(task_id: str, autostart: Optional[bool] = None) -> Optional[dict]:
    """Trace context for a task being SUBMITTED now: inherits the ambient
    trace (nested call) or — when root minting is enabled
    (``cfg.trace_tasks``, default on) — mints a fresh trace id (tree
    root). The new task's span id is its task id. With ``trace_tasks``
    off, only explicitly-started traces (``start_trace`` or a context
    installed by an executing traced task) propagate; untraced
    submissions carry ``None`` and pay zero minting cost."""
    amb = _ctx.get()
    if amb is not None:
        return {
            "trace_id": amb["trace_id"],
            "span_id": task_id,
            "parent_id": amb["span_id"],
        }
    # ``autostart`` lets hot callers pass a cached copy of the flag: the
    # cfg read consults os.environ live, measurable per-call at thousands
    # of submissions per second
    if not (cfg.trace_tasks if autostart is None else autostart):
        return None
    return {
        "trace_id": rand_hex(8),
        "span_id": task_id,
        "parent_id": None,
    }


def start_trace() -> "object":
    """Explicitly open a trace at the caller (driver code): submissions
    made while the returned token is installed share one trace id even
    when ``cfg.trace_tasks`` is off. Returns a token for ``uninstall``."""
    return _ctx.set(
        {"trace_id": rand_hex(8), "span_id": "driver", "parent_id": None}
    )


def install(trace: Optional[dict]):
    """Install the received context around task execution; returns a
    token for ``uninstall``."""
    return _ctx.set(trace)


def uninstall(token) -> None:
    _ctx.reset(token)


def event_args(trace: Optional[dict]) -> dict:
    """kwargs for TaskEventBuffer.record."""
    if not trace:
        return {}
    out = {"trace_id": trace["trace_id"]}
    if trace.get("parent_id"):
        out["parent_id"] = trace["parent_id"]
    return out


# ---------------------------------------------------------------------------
# process-local span recorder (ISSUE 15, repaired in ISSUE 26): named
# duration spans beyond the per-task lifecycle — scheduler rounds, serve
# request lifecycle, the serving engine's step, socket-plane stripes,
# elastic reshape phases. Spans land in a bounded ring and merge into
# every Chrome-trace export (core/events.TaskEventBuffer.dump_timeline)
# and crash bundle.
# ---------------------------------------------------------------------------

#: epoch seconds at ``time.perf_counter() == 0``, taken once at import.
#: ``span()`` takes both ends of an interval from ``perf_counter`` and the
#: ring keeps Chrome's epoch ``ts`` through this one anchor, so a reader
#: that holds perf_counter stamps maps them onto ring spans exactly:
#: ``ts_us = (PERF_EPOCH_S + t_perf) * 1e6``.
PERF_EPOCH_S = _time.time() - _time.perf_counter()

_span_ids = itertools.count(1)
_open = threading.local()  # .span: innermost span open on this thread


class Span:
    """One interval on the ``perf_counter`` clock with two sinks: the
    process ring (``SPANS``) and, while a profiler captures, the
    profiler's own trace, where it lies on the device operations' clock.

    ``with tracing.span(name, cat, **args) as sp`` is the thread-scoped
    form: it nests (``parent`` is the span open on the same thread),
    carries the ``trace_id`` of ``current()`` and is annotated for the
    profiler. ``tracing.span(...).begin()`` ... ``.end()`` is the
    detached form for an interval that ends elsewhere than it began (a
    request's life): same ids, ring only. ``set()`` adds args; they must
    be JSON-serializable host values. The ring's record shares the span's
    args, so a ``set()`` after the end still lands there: a count the
    device hands over after the interval closed (``engine.decode``'s
    expert counts, known at the readback) is set then."""

    __slots__ = ("name", "cat", "pid", "args", "t0", "_ring", "_outer", "_ann")

    def __init__(self, ring, name: str, cat: str, pid: str, args: dict):
        self._ring = ring
        self.name, self.cat, self.pid, self.args = name, cat, pid, args
        self.t0 = 0.0
        self._outer = self._ann = None

    def set(self, **args) -> None:
        self.args.update(args)

    def begin(self) -> "Span":
        self._outer = outer = getattr(_open, "span", None)
        trace = _ctx.get()
        self.args["id"] = next(_span_ids)
        if outer is not None:
            self.args["parent"] = outer.args["id"]
        if trace is not None:
            self.args["trace_id"] = trace["trace_id"]
        self.t0 = _time.perf_counter()
        return self

    def end(self, **args) -> None:
        t1 = _time.perf_counter()
        if args:
            self.args.update(args)
        self._ring.append(
            {
                "name": self.name,
                "cat": self.cat,
                "ph": "X",
                "ts": (PERF_EPOCH_S + self.t0) * 1e6,
                "dur": (t1 - self.t0) * 1e6,
                "pid": self.pid or "process",
                "tid": threading.get_ident(),
                "args": self.args,
            }
        )

    def __enter__(self) -> "Span":
        # a profiler can only capture in a process that has loaded JAX,
        # and this module must not load it (node agents never do)
        jax = sys.modules.get("jax")
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        _open.span = self.begin()
        return self

    def __exit__(self, *exc) -> None:
        self.end()
        _open.span = self._outer
        if self._ann is not None:
            self._ann.__exit__(*exc)


class _NoSpan:
    """What ``span()`` hands out while ``cfg.trace_spans`` is off."""

    __slots__ = ()

    def set(self, **args) -> None:
        pass

    def begin(self) -> "_NoSpan":
        return self

    def end(self, **args) -> None:
        pass

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def __bool__(self) -> bool:
        return False


_NO_SPAN = _NoSpan()


class SpanBuffer:
    """Bounded ring of completed spans in Chrome-trace 'X' form.
    ``dropped`` counts the spans the ring has pushed out since it was made
    or cleared: a reader of a window that finds it above 0 reads part of
    that window."""

    def __init__(self, max_spans: int = 50_000):
        self._spans: deque = deque(maxlen=max_spans)
        self._lock = threading.Lock()
        self.dropped = 0

    def append(self, span: dict) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)

    def span(self, name: str, cat: str = "runtime", pid: str = "", **args):
        """The one way to time an interval inside the program: see
        ``Span``. The single switch is ``cfg.trace_spans``; off, nothing
        is recorded or annotated and the returned object is falsy, so a
        caller can skip work that only feeds ``set()``."""
        if not cfg.trace_spans:
            return _NO_SPAN
        return Span(self, name, cat, pid, args)

    def record(
        self,
        name: str,
        cat: str,
        start_ts: float,
        dur_s: float,
        pid: str = "",
        tid=0,
        **args,
    ) -> None:
        """One completed span timed by its caller: ``start_ts`` is epoch
        seconds (time.time()), ``dur_s`` its wall duration. ``args`` must
        be JSON-serializable (they land in trace exports verbatim)."""
        if not cfg.trace_spans:
            return
        span = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": start_ts * 1e6,
            "dur": max(0.0, dur_s) * 1e6,
            "pid": pid or "process",
            "tid": tid,
        }
        if args:
            span["args"] = args
        self.append(span)

    def slices(
        self, since_s: Optional[float] = None, cat: Optional[str] = None
    ) -> List[dict]:
        """Snapshot (optionally only spans STARTING within the last
        ``since_s`` seconds, the crash-bundle window)."""
        with self._lock:
            spans = list(self._spans)
        if since_s is not None:
            cutoff = (_time.time() - since_s) * 1e6
            spans = [s for s in spans if s["ts"] >= cutoff]
        if cat is not None:
            spans = [s for s in spans if s["cat"] == cat]
        return spans

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0


#: the process's span ring (one per process, like the metrics registry)
SPANS = SpanBuffer()
#: ``tracing.span(name, cat, **args)``: a span into the process's ring
span = SPANS.span


@contextmanager
def installed(trace: Optional[dict]):
    """``install(trace)`` for the length of a block: what is submitted
    inside it belongs to that trace."""
    token = _ctx.set(trace)
    try:
        yield trace
    finally:
        _ctx.reset(token)
