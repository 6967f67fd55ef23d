"""Process peak of device memory after the window, before the reference
runs: weights, KV pool and the step's temporaries."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2**30
