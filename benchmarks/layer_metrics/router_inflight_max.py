"""The most requests the router had in flight: the largest ``inflight`` of
the ``serve.admit`` spans that began inside the window (in flight after the
grant, or at the shed). The admission layer sheds past
``serve_admission_max_inflight`` = 256, and a shed request is a failed
operation: an open-loop cell above its knee runs under that ceiling."""
from harness import request_path


def read(run):
    rp = request_path.load(run)
    if rp is None:
        return None
    inflight = [
        s["args"]["inflight"] for s in rp.es.named("serve.admit")
        if "inflight" in s["args"]
    ]
    return max(inflight) if inflight else None
