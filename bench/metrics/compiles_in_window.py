"""Backend compiles inside the window (JAX monitoring events); every shape
is warmed up in set-up, so this should read 0."""


def read(run):
    return float(run.compiles_window)
