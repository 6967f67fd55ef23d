"""Tokens per delivery at the client: a delivery starts after every gap
over 1 ms. 1 means every token arrives when it is made; 32 means a stream
sees its tokens once in 32 steps of the engine."""
from harness import stats


def read(run):
    return stats.clump_tokens(run.streams(), run.t_open, run.t_close)
