"""99th percentile of the gaps between consecutive tokens of a stream at
the client, all streams pooled over the window: what ``token_gap_p99_ms``
is end to end in ``reasoning``. In a cell whose prompts run to thousands
of tokens it is the stall of every live stream behind one admission."""
from harness import metrics


def read(run):
    return metrics.end_to_end(run)["token_gap_p99_ms"]
