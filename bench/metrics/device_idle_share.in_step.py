"""Scheduler: the share of the benchmark's ``serving`` span in which no
operation ran on the device while the host was inside the program's
``serve.step`` (``bench/spans.py``); the rest of ``device_idle_share`` is
idle while the host was outside the program.  None where the trace holds
no ``serve.step`` span."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    share = spans.idle_in_step(run.trace, "serving")
    return None if share is None else 100.0 * share
