"""Whole-window model FLOP/s utilization: the FLOPs the algorithm needs
for every token delivered inside the window (2 x matrix parameters and the
attention over its context) and for the prefill of every request whose
first token fell inside it, over window x peak bf16 FLOP/s. Bounds every
kernel's roofline share from above: a kernel taken off the path leaves
its own metric silent, not this one."""
from bisect import bisect_left

from harness import counts


def read(run):
    flops = 0
    matrix_flops = counts.decode_token_flops(run.cfg, 0)  # a token, no context
    for c in run.clients:
        s = c.stamps
        lo, hi = bisect_left(s, run.t_open), bisect_left(s, run.t_close)
        if hi <= lo:
            continue
        p = c.request.prompt_len
        if lo == 0:
            # the first token comes out of the prefill
            flops += counts.prefill_flops(run.cfg, p)
            lo = 1
        n = hi - lo
        if n > 0:
            # token k (0-based) of the answer attends over p + k tokens
            ctx = n * p + (lo + hi - 1) * n // 2
            flops += n * matrix_flops + counts.attention_flops(run.cfg, ctx)
    if not flops:
        return None
    return 100.0 * flops / (
        (run.t_close - run.t_open) * run.peaks["bf16_flops_per_s"]
    )
