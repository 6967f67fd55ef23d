"""Live slots in a decode step, mean over the window's steps, counted in
the benchmark's wrapper of the decode program."""


def read(run):
    live = [
        len(ctxs) for t, ctxs in run.decode_log
        if run.t_open <= t < run.t_close
    ]
    return sum(live) / len(live) if live else None
