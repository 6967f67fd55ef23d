"""Mean gap between consecutive tokens of a stream at the client, over the
window: what ``token_gap_mean_ms`` is end to end in the cells below their
knee. Above the knee the queue grows all through the run and a long
prompt's prefill stalls every live stream, so the gap is read per layer
here and judges nothing."""
from harness import metrics


def read(run):
    return metrics.end_to_end(run)["token_gap_mean_ms"]
