"""Model step: operations the window's decoded tokens need, each at its
own context length (the family's ``decode_flops``,
``references/<family>.py``), over the device time of the decode
executable in the trace, as a share of the chip's peak."""
from bench import tracereduce


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = tracereduce.module_seconds(run.trace, "decode_and_pick")
    steps = run.window_decode_steps()
    if t <= 0 or not steps:
        return None
    need = sum(run.family.decode_flops(run.model, k)
               for keys in steps.values() for k in keys)
    return 100.0 * need / t / run.peaks["bf16_flops_per_s"]
