"""JAX compile events between the window's opening and its close. Must
read 0: nothing compiles inside the measured window."""


def read(run):
    return float(run.window_compiles)
