"""The program's own spans (``engine.*``, ``ray_tpu/util/tracing.py``) as
the per-layer readers see them: mapped onto the run's clock, cut to the
window, with the tree their ``parent`` ids make.

The program times a span with ``time.perf_counter()``, the clock ``run.py``
hands its harness, and keeps it in the process's ring with an epoch ``ts``
through one public anchor (``tracing.PERF_EPOCH_S``), so ``run.t_open`` and
``run.t_close`` map onto ring spans exactly. A program without the anchor
or without an engine span in its ring (the parent of the PR that added
them) gives ``load(run) -> None``, and every reader then reads ``None``.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Optional

CAT = "engine"


class EngineSpans:
    """The ring's engine spans and the window ``[lo, hi)``, in the ring's
    microseconds."""

    def __init__(self, spans: List[dict], lo_us: float, hi_us: float):
        self.spans, self.lo, self.hi = spans, lo_us, hi_us
        self._children: Optional[Dict[int, List[dict]]] = None

    def named(self, name: str, overlap: bool = False) -> List[dict]:
        """Spans of that name that start inside the window or, with
        ``overlap``, that share any part of it."""
        if overlap:
            return [
                s for s in self.spans
                if s["name"] == name
                and s["ts"] < self.hi and s["ts"] + s["dur"] > self.lo
            ]
        return [
            s for s in self.spans
            if s["name"] == name and self.lo <= s["ts"] < self.hi
        ]

    def under(self, span: dict, names: Iterable[str]) -> List[dict]:
        """The spans of those names that ``span`` caused, at any depth."""
        if self._children is None:
            self._children = defaultdict(list)
            for s in self.spans:
                parent = s["args"].get("parent")
                if parent is not None:
                    self._children[parent].append(s)
        names, out, todo = set(names), [], [span]
        while todo:
            for child in self._children.get(todo.pop()["args"]["id"], ()):
                todo.append(child)
                if child["name"] in names:
                    out.append(child)
        return out

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-6


def load(run) -> Optional[EngineSpans]:
    from ray_tpu.util import tracing

    anchor = getattr(tracing, "PERF_EPOCH_S", None)
    if anchor is None:
        return None
    spans = [
        s for s in tracing.SPANS.slices(cat=CAT) if "id" in s.get("args", ())
    ]
    if not spans:
        return None
    return EngineSpans(
        spans, (anchor + run.t_open) * 1e6, (anchor + run.t_close) * 1e6
    )


def mean(values: List[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None
