"""One request's way from the router to its slot and back, as the program's
own spans tell it (cats ``serve`` and ``engine``, ``ray_tpu/util/tracing``):
``serve.admit`` -> ``serve.stream`` -> the first ``replica.stream`` ->
``engine.request``, joined by ``trace_id`` and ``parent``, on the window
arithmetic of ``engine_spans`` (one clock, ``tracing.PERF_EPOCH_S``).

The moments of a request, in the ring's microseconds:

- ``dispatched``: ``serve.stream.ts`` + ``dispatch_ms``: the router has made
  the channel and handed the call to the replica's mailbox;
- ``taken_up``: ``replica.stream.ts``: a replica thread runs the call;
- ``submitted``: ``engine.request.ts``: ``submit()`` has had its turn at the
  engine's lock and the request is registered;
- ``admitted``: ``engine.request.ts`` + ``queue_wait_ms``, where the span
  has a ``slot``: its admission begins, the slot is taken;
- ``released``: the end of ``engine.request``: the slot falls free.

From ``dispatched`` to ``admitted`` a request is **backlog**: work the router
has let in and no slot serves. A ring without ``serve.stream`` spans that
carry an ``id`` (the parent of the PR that made them spans) gives
``load(run) -> None``, and every reader then reads ``None``. The readers
take the ring for one engine's: a process that serves two replicas would
mix their slots.
"""
from __future__ import annotations

import json
import sys
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from . import engine_spans

CATS = ("serve", "engine")
_logged = None  # the run whose hand-over was last logged: ten readers load


def end_of(span: dict) -> float:
    return span["ts"] + span["dur"]


class Count:
    """How many of a set of intervals ``[a, b)`` are open at a time."""

    def __init__(self, intervals: Iterable[Tuple[float, float]]):
        events = sorted(
            e for a, b in intervals if b > a for e in ((a, 1), (b, -1))
        )
        self.times: List[float] = []
        self.counts: List[int] = []  # after every event at that time
        n = 0
        for t, d in events:
            n += d
            if self.times and self.times[-1] == t:
                self.counts[-1] = n
            else:
                self.times.append(t)
                self.counts.append(n)

    def at(self, t: float) -> int:
        i = bisect_right(self.times, t)
        return self.counts[i - 1] if i else 0


def pieces(lo: float, hi: float, *counts: Count) -> Iterator[tuple]:
    """``(length, count of each)`` over the stretches of ``[lo, hi)`` in
    which none of the counts changes."""
    cuts = sorted(
        {lo, hi, *(t for c in counts for t in c.times if lo < t < hi)}
    )
    for a, b in zip(cuts, cuts[1:]):
        yield (b - a, *(c.at(a) for c in counts))


class Request:
    """The four spans of one routed request (any but ``stream`` may be
    ``None``) and its moments."""

    def __init__(self, stream, admit, replica, request):
        self.stream, self.admit = stream, admit
        self.replica, self.request = replica, request
        ms = stream["args"].get("dispatch_ms")
        self.dispatched = None if ms is None else stream["ts"] + ms * 1e3
        self.taken_up = None if replica is None else replica["ts"]
        self.submitted = None if request is None else request["ts"]
        self.admitted = None if request is None else admitted(request)

    @property
    def backlog(self) -> Optional[Tuple[float, float]]:
        """From the dispatch to the admission or, for a request that never
        had a slot, to the end of the last span it has."""
        if self.dispatched is None:
            return None
        last = self.request or self.replica or self.stream
        until = self.admitted if self.admitted is not None else end_of(last)
        return self.dispatched, until


def admitted(request: dict) -> Optional[float]:
    """When the admission of an ``engine.request`` span began, if it was
    given a slot."""
    a = request["args"]
    if "slot" not in a or "queue_wait_ms" not in a:
        return None
    return request["ts"] + a["queue_wait_ms"] * 1e3


def _first(spans: List[dict], name: str, parent=None) -> Optional[dict]:
    """The first span of that name, under that parent if one is given."""
    return next(
        (s for s in spans if s["name"] == name
         and (parent is None or s["args"].get("parent") == parent)),
        None,
    )


class RequestPath:
    def __init__(self, spans: List[dict], lo_us: float, hi_us: float):
        self.es = engine_spans.EngineSpans(spans, lo_us, hi_us)
        self.lo, self.hi = lo_us, hi_us
        by_trace: Dict[str, List[dict]] = defaultdict(list)
        for s in spans:
            if "trace_id" in s["args"]:
                by_trace[s["args"]["trace_id"]].append(s)
        self.requests: List[Request] = []
        for stream in (s for s in spans if s["name"] == "serve.stream"):
            mine = sorted(
                by_trace.get(stream["args"].get("trace_id"), ()),
                key=lambda s: s["ts"],
            )
            replica = _first(mine, "replica.stream")
            request = replica and _first(
                mine, "engine.request", parent=replica["args"]["id"]
            )
            self.requests.append(
                Request(stream, _first(mine, "serve.admit"), replica, request)
            )
        self.backlog = Count(
            r.backlog for r in self.requests if r.backlog is not None
        )
        # when each slot was taken: every engine.request that was given
        # one, routed or not: (slot, its admission, its end)
        self.tenancies = [
            (s["args"]["slot"], admitted(s), end_of(s)) for s in spans
            if s["name"] == "engine.request" and admitted(s) is not None
        ]
        self.taken = Count((a, b) for _, a, b in self.tenancies)
        # the engine's slots, as its engine.decode spans state them
        self.slots: Optional[int] = max(
            (s["args"]["slots"] for s in spans
             if s["name"] == "engine.decode" and "slots" in s["args"]),
            default=None,
        )

    @property
    def window_s(self) -> float:
        return self.es.window_s

    def inside(self, t: Optional[float]) -> bool:
        return t is not None and self.lo <= t < self.hi

    def idle_with_backlog(self) -> Optional[float]:
        """Slot-microseconds of the window in which a slot stood empty
        while a dispatched request waited for one: the integral of
        min(empty slots, backlog)."""
        if not self.slots:
            return None
        return sum(
            length * min(self.slots - taken, backlog)
            for length, taken, backlog in pieces(
                self.lo, self.hi, self.taken, self.backlog)
        )

    def last_token_ms(self) -> List[float]:
        """End of a finished ``engine.request`` inside the window to the
        end of the ``replica.stream`` above it."""
        return [
            (end_of(r.replica) - end_of(r.request)) * 1e-3
            for r in self.requests
            if r.request is not None
            and r.request["args"].get("end") == "finished"
            and self.inside(end_of(r.request))
        ]

    def next_call_ms(self) -> List[float]:
        """On one replica thread, the end of a ``replica.stream`` inside the
        window to the start of the next, where that next request had been
        dispatched by then: it waited for a thread, not the thread for it."""
        by_tid: Dict[int, List[Request]] = defaultdict(list)
        for r in self.requests:
            if r.replica is not None:
                by_tid[r.replica["tid"]].append(r)
        out = []
        for calls in by_tid.values():
            calls.sort(key=lambda r: r.taken_up)
            for prev, nxt in zip(calls, calls[1:]):
                ended = end_of(prev.replica)
                if (self.inside(ended) and nxt.dispatched is not None
                        and nxt.dispatched <= ended):
                    out.append((nxt.taken_up - ended) * 1e-3)
        return out

    def handover_ms(self) -> Optional[List[float]]:
        """Slot by slot, from the end of a tenant to the admission of the
        slot's next one: the gaps at whose start a request waited that no
        other empty slot could take (backlog at least the empty slots,
        this one among them), each for the part of it that lies inside
        the window. The tenants are the whole ring's, so a gap that began
        in the ramp-in is paired; one that the close cuts, or that no
        tenant ended, counts up to the close."""
        if not self.slots:
            return None
        by_slot: Dict[int, List[Tuple[float, float]]] = defaultdict(list)
        for slot, a, b in self.tenancies:
            by_slot[slot].append((a, b))
        out = []
        for held in by_slot.values():
            held.sort()
            taken_again = [a for a, _ in held[1:]] + [float("inf")]
            for (_, freed), again in zip(held, taken_again):
                lo, hi = max(freed, self.lo), min(again, self.hi)
                if hi > lo and self.backlog.at(freed) >= (
                        self.slots - self.taken.at(freed)):
                    out.append((hi - lo) * 1e-3)
        return out

    def legs(self) -> dict:
        """A freed slot's hand-over and the four legs that should add up
        to it, each a mean in ms over the window (``None`` where there was
        none). ``slot_handover`` is the mean of ``handover_ms``, of which
        ``gaps`` are counted. ``idle_over_admissions`` is the same wait by
        Little's law: the slot-time that stood empty beside a waiting
        request (``idle_with_backlog``) over the admissions inside the
        window, whichever slot the engine gave each. The two differ by
        the gaps an edge of the window cuts, which ``handover_ms`` counts
        for their part inside: the engine fills the lowest free slot, so
        where the batch does not fill the high slots stand empty for whole
        windows. The legs follow a replica thread from the end of one
        request to the admission of its next."""
        mean = engine_spans.mean
        idle = self.idle_with_backlog()
        admissions = sum(1 for _, a, _ in self.tenancies if self.inside(a))
        gaps = self.handover_ms()
        legs = {
            "last_token": mean(self.last_token_ms()),
            "next_call": mean(self.next_call_ms()),
            "submit": mean([
                (r.submitted - r.taken_up) * 1e-3 for r in self.requests
                if r.submitted is not None and self.inside(r.taken_up)
            ]),
            "queue_wait": mean([
                r.request["args"]["queue_wait_ms"] for r in self.requests
                if self.inside(r.admitted)
            ]),
        }
        return {
            "slot_handover": mean(gaps) if gaps else None,
            "gaps": None if gaps is None else len(gaps),
            "idle_over_admissions": (
                idle * 1e-3 / admissions
                if admissions and idle is not None else None
            ),
            "admissions": admissions,
            "sum_of_legs": (
                None if None in legs.values() else sum(legs.values())
            ),
            **legs,
        }


def load(run) -> Optional[RequestPath]:
    """The run's requests; the first time a run is loaded its hand-over
    and the legs are logged (``"phase": "handover_legs"``), so that a run
    shows whether the legs add up."""
    global _logged
    rp = _build(run)
    if rp is not None and _logged is not run:
        _logged = run
        print(json.dumps({"phase": "handover_legs", **rp.legs()}),
              file=sys.stderr, flush=True)
    return rp


def _build(run) -> Optional[RequestPath]:
    from ray_tpu.util import tracing

    anchor = getattr(tracing, "PERF_EPOCH_S", None)
    if anchor is None:
        return None
    spans = [
        s for cat in CATS for s in tracing.SPANS.slices(cat=cat)
        if "id" in s.get("args", ())
    ]
    if not any(s["name"] == "serve.stream" for s in spans):
        return None
    return RequestPath(
        spans, (anchor + run.t_open) * 1e6, (anchor + run.t_close) * 1e6
    )
