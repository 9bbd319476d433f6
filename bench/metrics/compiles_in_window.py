"""Scheduler: XLA compiles (JAX's monitoring events) while the window was
open.  Every executable is built in set-up, so this should read 0."""


def read(run):
    return run.compiles_in_window
