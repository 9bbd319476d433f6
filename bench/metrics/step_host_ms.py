"""Scheduler: the host's work per server step, from the program's own
spans: the median ``serve.step`` less the time it waited for the device
(``serve.prefill_wait``, ``serve.decode_wait``), in ms (``bench/spans.py``).
None where the trace holds no ``serve.step`` span."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.step_host_ms(run.trace)
