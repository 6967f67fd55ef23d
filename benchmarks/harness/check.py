"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished (drawn from the seed, the longest always
in it) is run through the configuration's plain reference, prompt and
served tokens together, once each. For every served token the reference's
logit of that token is set against the reference's best logit at that
position: the token's gap. Greedy decoding in bfloat16 picks near-ties
differently, so most gaps are 0 and the rest are small; how many are not
small is what separates the stated precision from the one below it.

Numbers compared (each printed beside its limit; the limits are the cell's
own, ``workloads/<cell>.json`` ``check``; PERF.md section 2 gives the
readings each was set from):

- ``mean_gap``: the mean gap over the sample; it grows as the square of
  the noise in the logits.
- ``tail_share``: the share of the compared tokens whose gap is over the
  cell's ``tail_over``. A steady stand-in for the widest gap, which swings
  by its nature; it grows faster than any power of the noise.
- ``gross_gaps``: tokens whose gap is over ``GROSS_OVER``, which no
  rounding gives and one altered token does. Exact: limit 0.
- ``wrong_length``: finished requests whose answer is not ``max_new``
  tokens long. ``split_pieces``: stream items that are not exactly one
  token. ``bad_ids``: served ids outside the vocabulary. ``errors``:
  requests that ended in an error. All exact: limit 0.
- ``compared_tokens``: how many served tokens the sample held (at least 1).
- ``p99_gap``, ``max_gap`` and ``off_argmax`` (served tokens that are not
  the reference's first) are read beside them and held to nothing.

With ``control=True`` the same positions are also read from the control:
the reference computed in int8, the precision below the stated bfloat16
(``references/dense_gqa.py``). At each position the token that int8 puts
first is taken as if it had been served, its gap under the float32
reference read, and the same numbers put through the same limits:
``control_correct`` has to come out false.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

GROSS_OVER = 1.0          # a gap no rounding gives; an altered token reads ~4
CONTROL_QUANT = "int8"    # the precision below the configurations' bfloat16


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pick_sample(finished: List[dict], seed: int, min_tokens: int,
                max_requests: int) -> List[dict]:
    """The longest finished request, then others in an order drawn from
    the seed until the sample holds ``min_tokens`` served tokens."""
    if not finished:
        return []
    order = sorted(
        finished, key=lambda r: (-(r["prompt_len"] + len(r["ids"])), r["index"])
    )
    sample = [order[0]]
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    for i in rng.permutation(len(rest)):
        if (
            sum(len(r["ids"]) for r in sample) >= min_tokens
            or len(sample) >= max_requests
        ):
            break
        sample.append(rest[int(i)])
    return sample


def output_gaps(cfg: dict, params, sample: List[dict], ref, control: bool,
                log: Optional[Callable] = None):
    """Per-token gaps of the served tokens, and of the control's tokens.
    ``ref`` is the configuration's reference (``spec.load_reference``)."""
    import jax.numpy as jnp

    gaps: List[float] = []
    control_gaps: List[float] = []
    for r in sample:
        prompt, ids = list(r["prompt"]), list(r["ids"])
        p, n = len(prompt), len(ids)
        t_pad = _round_up(p + n, 512)
        n_rows = _round_up(n, 256)
        tokens = np.zeros(t_pad, np.int32)
        tokens[: p + n] = prompt + ids
        rows = np.zeros(n_rows, np.int32)
        rows[:n] = np.arange(p - 1, p + n - 1)  # row j predicts token j + 1
        served = np.zeros(n_rows, np.int32)
        served[:n] = ids
        logits = ref.reference_logits(
            params, cfg, jnp.asarray(tokens), jnp.asarray(rows)
        )
        best = logits.max(axis=-1)
        chosen = jnp.take_along_axis(
            logits, jnp.asarray(served)[:, None], axis=1
        )[:, 0]
        g = np.asarray(best - chosen)[:n]
        if not np.isfinite(g).all():
            raise FloatingPointError("the reference's logits are not finite")
        gaps.extend(float(x) for x in g)
        cg = None
        if control:
            low = ref.reference_logits(
                params, cfg, jnp.asarray(tokens), jnp.asarray(rows),
                quant=CONTROL_QUANT,
            )
            first = jnp.argmax(low, axis=-1)
            cg = np.asarray(
                best - jnp.take_along_axis(logits, first[:, None], axis=1)[:, 0]
            )[:n]
            control_gaps.extend(float(x) for x in cg)
            del low
        del logits
        if log is not None:  # every gap that is not 0, by its place
            def nonzero(a):
                return [[int(i), round(float(a[i]), 5)] for i in np.flatnonzero(a)]

            log(phase="gaps", index=r["index"], prompt_len=p, tokens=n,
                gaps=nonzero(g), **({} if cg is None else {"control": nonzero(cg)}))
    return gaps, control_gaps


def gap_numbers(gaps: List[float], tail_over: float) -> Dict[str, object]:
    """The numbers read from one list of per-token gaps."""
    if not gaps:
        return {"mean_gap": None, "tail_share": None, "gross_gaps": None,
                "max_gap": None, "p99_gap": None, "off_argmax": None}
    a = np.asarray(gaps, np.float64)
    return {
        "mean_gap": float(a.mean()),
        "tail_share": float((a > tail_over).mean()),
        "gross_gaps": int((a > GROSS_OVER).sum()),
        "max_gap": float(a.max()),
        "p99_gap": float(np.percentile(a, 99)),
        "off_argmax": int((a > 0).sum()),
    }


def compare(cfg: dict, params, records: List[dict], seed: int,
            limits: dict, ref, control: bool = False,
            log: Optional[Callable] = None) -> Dict[str, dict]:
    """``records``: one dict for each request sent, with ``index``,
    ``prompt`` (ids), ``prompt_len``, ``max_new``, ``ids`` (served ids),
    ``pieces_bad`` (stream items that were not one token), ``finished``,
    ``error``. ``limits``: the cell's ``check``. ``ref``: the
    configuration's reference. Returns
    name -> {"value", "limit" | "at_least"}."""
    vocab = cfg["vocab_size"]
    finished = [r for r in records if r["finished"]]
    out = {
        "errors": {
            "value": sum(1 for r in records if r["error"]), "limit": 0},
        "wrong_length": {
            "value": sum(1 for r in finished if len(r["ids"]) != r["max_new"]),
            "limit": 0},
        "split_pieces": {
            "value": sum(r["pieces_bad"] for r in records), "limit": 0},
        "bad_ids": {
            "value": sum(
                1 for r in records for i in r["ids"] if not 0 <= i < vocab),
            "limit": 0},
    }
    usable = [
        r for r in finished
        if r["ids"] and all(0 <= i < vocab for i in r["ids"])
    ]
    sample = pick_sample(
        usable, seed, int(limits["sample_min_tokens"]),
        int(limits["sample_max_requests"]),
    )
    gaps, control_gaps = output_gaps(cfg, params, sample, ref, control, log)
    out["compared_tokens"] = {"value": len(gaps), "at_least": 1}
    tail_over = float(limits["tail_over"])
    held = {
        "mean_gap": {"limit": limits["mean_gap"]},
        "tail_share": {"limit": limits["tail_share"], "over": tail_over},
        "gross_gaps": {"limit": 0, "over": GROSS_OVER},
    }
    for name, value in gap_numbers(gaps, tail_over).items():
        out[name] = {"value": value, **held.get(name, {})}
    if control:
        as_program = dict(out)
        for name, value in gap_numbers(control_gaps, tail_over).items():
            out["control_" + name] = {"value": value}
            if name in held:
                as_program[name] = {"value": value, **held[name]}
        out["control_correct"] = {"value": verdict(as_program)}
    return out


def verdict(compared: Dict[str, dict]) -> bool:
    for entry in compared.values():
        v = entry["value"]
        if "limit" in entry and (v is None or not v <= entry["limit"]):
            return False
        if "at_least" in entry and (v is None or v < entry["at_least"]):
            return False
    return True
