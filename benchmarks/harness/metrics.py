"""End-to-end metric arithmetic, from the clients' stamps alone, and the
``Run`` object the per-layer readers are handed."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from . import stats


@dataclass
class Run:
    """Everything one run observed. Times are seconds on one host clock."""
    cfg: dict
    mix: dict
    base: str                           # where the cell's own files are looked for
    peaks: dict
    t_open: float
    t_close: float
    setup_s: float
    clients: list                       # served.Client
    decode_log: List[tuple]             # (time, (live context lengths))
    prefill_log: List[tuple]            # (time, padded prompt tokens)
    window_compiles: int
    memory_peak_bytes: Optional[int]
    capture: Optional[Tuple[float, float]] = None   # traced stretch
    trace: object = None                             # trace.TraceSummary

    def streams(self) -> List[List[float]]:
        return [c.stamps for c in self.clients if c.stamps]


def end_to_end(run: Run) -> Dict[str, Optional[float]]:
    s, t0, t1 = run.streams(), run.t_open, run.t_close
    gaps = stats.window_gaps(s, t0, t1)
    mean = stats.gap_mean(s, t0, t1)
    p99 = stats.percentile(gaps, 99.0)
    return {
        "tokens_per_s": stats.tokens_in_window(s, t0, t1) / (t1 - t0),
        "token_gap_mean_ms": None if mean is None else mean * 1e3,
        "token_gap_p99_ms": None if p99 is None else p99 * 1e3,
        "setup_s": run.setup_s,
    }
