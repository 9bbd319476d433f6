"""Device: the share of the time the server had work (the benchmark's
``serving`` span: a live slot or a due request) during which no operation
ran on the device, from the trace."""
from bench import tracereduce


def read(run):
    if run.trace is None:
        return None
    try:
        return 100.0 * tracereduce.idle_share(run.trace, "serving")
    except ValueError:
        return None
