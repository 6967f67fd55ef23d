"""Whole-window model FLOP/s utilization: the FLOPs the algorithm needs
for every token delivered inside the window (the configuration's family's
``decode_token_flops`` over that token's own context) and for the prefill
of every request whose first token fell inside it (``prefill_flops``),
over window x peak bf16 FLOP/s. Bounds every kernel's roofline share from
above: a kernel taken off the path leaves its own metric silent, not this
one."""
from bisect import bisect_left

from harness import spec


def read(run):
    family = spec.load_family(run.cfg, run.base)
    flops = 0
    for c in run.clients:
        s = c.stamps
        lo, hi = bisect_left(s, run.t_open), bisect_left(s, run.t_close)
        if hi <= lo:
            continue
        p = c.request.prompt_len
        if lo == 0:
            # the first token comes out of the prefill
            flops += family.prefill_flops(run.cfg, p)
            lo = 1
        # token k (0-based) of the answer attends over p + k tokens
        flops += sum(
            family.decode_token_flops(run.cfg, p + k) for k in range(lo, hi)
        )
    if not flops:
        return None
    return 100.0 * flops / (
        (run.t_close - run.t_open) * run.peaks["bf16_flops_per_s"]
    )
