"""Share of the roofline a run of the history-plus-suffix program reaches:
the least time the chip could take for the ALGORITHMIC work of one chunk
(the configuration's family's ``prefill_chunk_work(cfg, tokens, history)``:
the blocks over the chunk's tokens, their attention over the history,
every weight and the history's K and V read once; the larger of bytes over
peak bandwidth and FLOPs over peak rate) at the mean history of the chunks
the traced stretch ran, over the program's measured device time a run. The
chunks are the window's ``engine.prefill`` spans': a prompt of ``t_pad``
padded tokens whose first program took ``head`` ran ``chunks - 1`` chunks
of equal length after it. ``None`` where the spans carry no ``head`` (the
parent), the family has no such count or no chunk ran."""
from harness import engine_spans, peaks, spec


def read(run):
    if run.trace is None or run.capture is None:
        return None
    secs, runs = run.trace.program("prefill_suffix")
    es = engine_spans.load(run)
    family = spec.load_family(run.cfg, run.base)
    work = getattr(family, "prefill_chunk_work", None)
    if not runs or es is None or work is None:
        return None
    lo = es.lo + (run.capture[0] - run.t_open) * 1e6
    hi = es.lo + (run.capture[1] - run.t_open) * 1e6
    tokens, histories = [], []
    for s in es.named("engine.prefill", overlap=True):
        a = s["args"]
        n = a.get("chunks", 1) - 1
        if "head" not in a or n < 1 or not lo <= s["ts"] < hi:
            continue
        each = (a["t_pad"] - a["head"]) // n
        tokens += [each] * n
        histories += [a["head"] + i * each for i in range(n)]
    if not tokens:
        return None
    least = peaks.least_seconds(
        *work(
            run.cfg, round(sum(tokens) / len(tokens)),
            round(sum(histories) / len(histories)),
        ),
        run.peaks,
    )
    return 100.0 * least / (secs / runs)
