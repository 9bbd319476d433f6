"""Model step: operations the window's true (unpadded) prompt tokens need
(the family's ``prefill_flops``, ``references/<family>.py``) over the
device time of the prefill executables in the trace, as a share of the
chip's peak (``bench/peaks.json``)."""
from bench import tracereduce


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = tracereduce.module_seconds(run.trace, "packed_prefill")
    prompts = run.window_prefills()
    if t <= 0 or not prompts:
        return None
    need = sum(run.family.prefill_flops(run.model, n) for n in prompts)
    return 100.0 * need / t / run.peaks["bf16_flops_per_s"]
