"""One general open-loop traffic generator, driven by a mix's data file.

A mix (``benchmarks/traffic/<mix>.json``) gives distributions and rates;
this module turns it into a schedule. The set of prompt lengths, answer
lengths and inter-arrival gaps is a fixed quantile grid of those
distributions, and which request gets which length and gap is a shuffle
drawn from the mix's own ``order_seed``: the schedule depends on the mix and
on the window's length, never on ``--seed``. The seed draws the token ids
(and the weights). With some hundred requests of heavy-tailed length, the
order decides which long answers straddle the window's two ends, and a
shuffle by the seed moved ``tokens_per_s`` by 7 % between seeds against
0.2 % between two runs of one seed (PERF.md, section 6); so every seed
offers the same work at the same times, and two seeds differ no more than
two runs of one seed.

Keys of a mix file:

- ``arrivals``: ``rate_per_s`` (mean over ramp-in and window),
  ``ramp_in_s`` (arrivals before the window opens, so that it opens on a
  loaded engine), ``interarrival_cv`` (1 = Poisson-like; the gaps are
  Weibull with that coefficient of variation), ``initial_burst`` (requests
  sent at once when the ramp-in starts, 1 ms apart), ``order_seed`` (which
  shuffle of the lengths and gaps this mix is; default 0).
- ``prompt_tokens`` / ``output_tokens``: ``dist`` is ``lognormal``
  (``median``, ``sigma``), ``loguniform``, ``uniform`` or ``fixed``
  (``value``), clipped to ``min``..``max``; ``at_max_share`` puts that share
  of the requests at ``max`` (inputs truncated to the context limit). A
  prompt length may be moved to the nearest entry of ``round_to`` and then
  shortened by a fixed pattern of 0..``jitter_below``-1 tokens: prompts
  then fall just under a limited set of page multiples, as real prompts do
  under a limited set of padded lengths, and the engine compiles one
  prefill program for each.
- ``temperature``: 0 is greedy, the only kind the output check can judge.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int
    due: float  # seconds from the window's opening; negative in the ramp-in
    prompt_len: int
    max_new: int


def _quantile(spec: dict, u: float) -> float:
    dist = spec["dist"]
    if dist == "fixed":
        return float(spec["value"])
    if dist == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    if dist == "loguniform":
        return math.exp(
            math.log(spec["min"])
            + u * (math.log(spec["max"]) - math.log(spec["min"]))
        )
    if dist == "lognormal":
        z = NormalDist().inv_cdf(u)
        return spec["median"] * math.exp(spec["sigma"] * z)
    raise ValueError(f"unknown distribution {dist!r}")


def length_set(spec: dict, n: int) -> List[int]:
    """The n lengths every seed shares: quantiles at (i + 1/2) / n."""
    at_max = int(round(float(spec.get("at_max_share", 0.0)) * n))
    if at_max:
        body = length_set(dict(spec, at_max_share=0.0), n - at_max)
        # through the same rounding and jitter as every other length
        top = length_set(
            dict(spec, dist="fixed", value=spec["max"], at_max_share=0.0),
            at_max,
        )
        return sorted(body + top)
    out = []
    buckets = sorted(spec.get("round_to") or [])
    jitter = int(spec.get("jitter_below", 0))
    lo = int(spec.get("min", 1))
    hi = int(spec.get("max", 1 << 30))
    for i in range(n):
        x = int(round(_quantile(spec, (i + 0.5) / n)))
        x = min(max(x, lo), hi)
        if buckets:
            top = min(buckets, key=lambda b: (abs(b - x), b))
            x = top - ((7 * i + 3) % jitter if jitter else 0)
            x = min(max(x, lo), hi)
        out.append(x)
    return sorted(out)


def _weibull_shape(cv: float) -> float:
    """Shape k with the wanted coefficient of variation (bisection)."""
    def cv_of(k):
        g1 = math.gamma(1 + 1 / k)
        g2 = math.gamma(1 + 2 / k)
        return math.sqrt(max(g2 / (g1 * g1) - 1, 0.0))

    lo, hi = 0.2, 20.0  # cv falls as k rises
    for _ in range(80):
        mid = (lo + hi) / 2
        if cv_of(mid) > cv:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def gap_set(n: int, span: float, cv: float) -> List[float]:
    """n inter-arrival gaps that sum to ``span``: Weibull quantiles."""
    if n <= 0:
        return []
    if cv <= 0:
        return [span / n] * n
    k = _weibull_shape(cv)
    raw = [(-math.log(1 - (i + 0.5) / n)) ** (1 / k) for i in range(n)]
    scale = span / sum(raw)
    return [g * scale for g in raw]


def schedule(mix: dict, seconds: float) -> List[Request]:
    arr = mix["arrivals"]
    ramp = float(arr["ramp_in_s"])
    n = max(1, int(round(arr["rate_per_s"] * (ramp + seconds))))
    burst = min(int(arr.get("initial_burst", 0)), n)
    prompts = length_set(mix["prompt_tokens"], n)
    outputs = length_set(mix["output_tokens"], n)
    # the last arrival falls half a mean gap before the close
    spaced = n - burst
    span = (ramp + seconds) * (spaced / (spaced + 0.5)) if spaced else 0.0
    gaps = gap_set(spaced, span, float(arr.get("interarrival_cv", 1.0)))
    rng = np.random.default_rng([int(arr.get("order_seed", 0)), 0x7AFF1C])
    prompts = [prompts[i] for i in rng.permutation(n)]
    outputs = [outputs[i] for i in rng.permutation(n)]
    gaps = [gaps[i] for i in rng.permutation(spaced)]
    dues = [-ramp + 0.001 * i for i in range(burst)]
    t = -ramp
    for g in gaps:
        t += g
        dues.append(t)
    return [
        Request(i, dues[i], prompts[i], outputs[i]) for i in range(n)
    ]


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """The token ids of request ``index``: uniform over the vocabulary."""
    rng = np.random.default_rng([int(seed), 0x70C3, int(index)])
    return rng.integers(0, vocab, size=length, dtype=np.int64).astype(np.int32)
