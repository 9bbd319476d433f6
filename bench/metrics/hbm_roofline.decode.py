"""Device: bytes the window's decode steps need (all weights once a step,
plus the keys and values of live tokens only, not the cache's
``max_len``; the family's ``decode_step_bytes``,
``references/<family>.py``) over the decode executable's device time in
the trace, as a share of the chip's HBM bandwidth."""
from bench import tracereduce


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = tracereduce.module_seconds(run.trace, "decode_and_pick")
    steps = run.window_decode_steps()
    if t <= 0 or not steps:
        return None
    need = sum(run.family.decode_step_bytes(run.model, keys)
               for keys in steps.values())
    return 100.0 * need / t / run.peaks["hbm_bytes_per_s"]
