"""Reduction of a profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read. Needs nothing but JAX's ``ProfileData``.

What is read, and from where:

- device planes are those named ``/device:TPU:<n>``. Their ``XLA Ops``
  line holds one event for each operation that ran, ``XLA Modules`` one
  for each compiled program that ran (``jit_decode_step(...)``).
- ``busy_s``: the union of the operations' intervals, averaged over the
  device planes. ``window_s``: from the first to the last event of any
  plane, host threads included, so idle time at either end counts.
- ``device_ops``: time by operation name, children taken out of the
  operation that contains them (a ``while`` holds its body's operations).
- ``programs``: for each program name, its device seconds and how often
  it ran.
- ``idle_gaps``: each stretch in which no operation ran on the first
  device, given to the benchmark's host span (``bench.*``) that covered
  it: the innermost span of the engine, else ``bench.router.stream``, else
  ``none``. Host spans and device operations are on one clock in the file.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
LOW_PRIORITY_SPAN = "bench.router.stream"


@dataclass
class TraceSummary:
    window_s: float = 0.0
    busy_s: float = 0.0
    n_devices: int = 0
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    # program name (without its fingerprint) -> (seconds, runs)
    programs: Dict[str, Tuple[float, int]] = field(default_factory=dict)
    host_spans: Dict[str, Tuple[float, int]] = field(default_factory=dict)

    def program(self, needle: str) -> Tuple[float, int]:
        """Device seconds and runs of the programs whose name holds
        ``needle`` (``decode_step`` finds ``jit_decode_step``)."""
        secs, runs = 0.0, 0
        for name, (s, n) in self.programs.items():
            if needle in name:
                secs, runs = secs + s, runs + n
        return secs, runs


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def short_op_name(name: str) -> str:
    """``%copy.79 = bf16[8,32]{1,0:T(8,128)} copy(...)`` -> ``copy.79
    bf16[8,32]``: the operation and the shape it produces."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = rhs.split("{", 1)[0].strip()
    return f"{lhs.lstrip('%')} {shape}"[:120]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Time by name with nested events taken out of their parents."""
    total: Dict[str, float] = defaultdict(float)
    stack: List[List] = []  # [end, name, self_ns]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm, self_ns = stack.pop()
            total[nm] += self_ns
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        end, nm, self_ns = stack.pop()
        total[nm] += self_ns
    return total


def _flatten_spans(spans: List[Tuple[float, float, str]]):
    """Non-overlapping (start, end, name) segments: at every instant the
    covering engine span that started last, else the low-priority span."""
    points = sorted({p for s, e, _ in spans for p in (s, e)})
    if not points:
        return []
    by_start = sorted(spans)
    segs: List[Tuple[float, float, str]] = []
    active: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(by_start) and by_start[i][0] <= a:
            active.append(by_start[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        if not active:
            continue
        strong = [sp for sp in active if sp[2] != LOW_PRIORITY_SPAN]
        pick = max(strong or active, key=lambda sp: sp[0])
        if segs and segs[-1][2] == pick[2] and segs[-1][1] == a:
            segs[-1] = (segs[-1][0], b, pick[2])
        else:
            segs.append((a, b, pick[2]))
    return segs


def _attribute(gaps, segs) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        covered, k = 0.0, j
        while k < len(segs) and segs[k][0] < ge:
            o = min(ge, segs[k][1]) - max(gs, segs[k][0])
            if o > 0:
                out[segs[k][2]] += o
                covered += o
            k += 1
        out["none"] += (ge - gs) - covered
    return out


def reduce_xplane(path: str, top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lo, hi = float("inf"), float("-inf")
    per_device = []
    spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        is_host = plane.name.startswith("/host:")
        if not (is_device or is_host):
            continue
        ops, modules = [], []
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                lo, hi = min(lo, s), max(hi, e)
                if is_device and line.name == OPS_LINE:
                    ops.append((s, e, ev.name))
                elif is_device and line.name == MODULES_LINE:
                    modules.append((s, e, ev.name))
                elif is_host and ev.name.startswith(SPAN_PREFIX):
                    spans.append((s, e, ev.name))
        if is_device and (ops or modules):
            per_device.append((ops or modules, modules))
    out = TraceSummary()
    if hi <= lo:
        return out
    out.window_s = (hi - lo) * 1e-9
    for s, e, name in spans:
        secs, n = out.host_spans.get(name, (0.0, 0))
        out.host_spans[name] = (secs + (e - s) * 1e-9, n + 1)
    out.n_devices = len(per_device)
    if not per_device:
        return out
    busy_total = 0.0
    for ops, _ in per_device:
        busy_total += sum(e - s for s, e in _union([(s, e) for s, e, _ in ops]))
    out.busy_s = busy_total * 1e-9 / len(per_device)
    ops0, modules0 = per_device[0]
    selfs = _self_times([(s, e, short_op_name(n)) for s, e, n in ops0])
    out.device_ops = [
        (name, ns * 1e-9)
        for name, ns in sorted(selfs.items(), key=lambda kv: -kv[1])[:top]
    ]
    for s, e, name in modules0:
        key = name.split("(")[0]
        secs, n = out.programs.get(key, (0.0, 0))
        out.programs[key] = (secs + (e - s) * 1e-9, n + 1)
    busy = _union([(s, e) for s, e, _ in ops0])
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    by_span = _attribute(gaps, _flatten_spans(spans))
    out.idle_gaps = [
        (name, ns * 1e-9)
        for name, ns in sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        if ns > 0
    ]
    return out
