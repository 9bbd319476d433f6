"""A family that is not dense, added as new files only (``tiny.add_moe``),
runs through the whole harness on the CPU: its weights from its own
table, the comparison with its own reference, and the roofline readers
on its own counts."""
import dataclasses
import os
import time

import numpy as np
import pytest

from bench import harness, tracereduce, weights
from bench.tests import tiny, tiny_moe

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "tiny_v5e.xplane.pb")
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
# counts that the family module's own are replaced with
STUBS = '''

def prefill_flops(m, prompt_len):
    return 1000 * prompt_len + 1


def decode_flops(m, keys):
    return 10 * keys


def decode_step_bytes(m, keys):
    return 100 * len(keys) + sum(keys)
'''


def test_moe_reference_matches_the_program_in_float32():
    import jax
    import jax.numpy as jnp
    from repro.models import get_model
    m = dict(tiny.TINY_MOE_MODEL, param_dtype="float32")
    params = weights.make(tiny_moe.shapes(m), m, seed=5)
    model = get_model(harness.build_model_config(m, "qwen2-moe-a2.7b"))
    weights.check_layout(params, model.abstract_params())
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        for n in (1, 17, 40):
            want, _ = jax.jit(model.prefill)(params,
                                             jnp.asarray(toks[None, :n]))
            got = tiny_moe.logits(params, m, toks[:n].astype(np.int32),
                                  n - 1)
            np.testing.assert_allclose(
                got[0], np.asarray(want[0, -1, :m["vocab_size"]]),
                rtol=1e-4, atol=1e-4)


def test_moe_cell_runs_correct(tmp_path):
    bench = tiny.add_moe(tiny.make(str(tmp_path)))
    out = harness.run_cell(bench, tiny.MOE_WORKLOAD, seed=2**33 + 5,
                           seconds=1.5, trace=False,
                           t_process=time.perf_counter(), log=lambda s: None)
    assert out["correct"] is True
    assert out["attempted"] == 12 and out["failed"] == 0
    assert out["check"]["widest_gap"]["value"] is not None


def test_roofline_readers_take_the_familys_counts(tmp_path, monkeypatch):
    """With the family's counts stubbed in its file, and the readers given
    a trace recorded on a chip and fixed peaks, the three roofline shares
    are the stubs' sums over the window's prompts and decode steps."""
    bench = tiny.add_moe(tiny.make(str(tmp_path)))
    with open(os.path.join(bench.dir, "references", "tiny_moe.py"),
              "a") as f:
        f.write(STUBS)
    chip = tracereduce.load(TRACE)
    seen = []
    real = bench.metric_reader

    def reader(name):
        read = real(name)

        def on_chip_trace(rec):
            rec = dataclasses.replace(rec, peaks=PEAKS, trace=chip)
            seen.append(rec)
            return read(rec)
        return on_chip_trace

    monkeypatch.setattr(bench, "metric_reader", reader)
    out = harness.run_cell(bench, tiny.MOE_WORKLOAD, seed=7, seconds=1.5,
                           trace=True, t_process=time.perf_counter(),
                           log=lambda s: None)
    assert out["correct"] is True
    rec = seen[0]
    assert rec.family.__file__ == os.path.join(bench.dir, "references",
                                               "tiny_moe.py")
    prompts = rec.window_prefills()
    steps = rec.window_decode_steps()
    assert prompts and steps
    prefill_s = tracereduce.module_seconds(chip, "packed_prefill")
    decode_s = tracereduce.module_seconds(chip, "decode_and_pick")
    flops_s, bytes_s = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]
    want = {
        "mfu.prefill": sum(1000 * n + 1 for n in prompts) / prefill_s
        / flops_s,
        "mfu.decode": sum(10 * k for keys in steps.values() for k in keys)
        / decode_s / flops_s,
        "hbm_roofline.decode": sum(100 * len(keys) + sum(keys)
                                   for keys in steps.values())
        / decode_s / bytes_s,
    }
    for name, share in want.items():
        assert out["metrics"][name]["value"] == pytest.approx(100 * share,
                                                              rel=1e-12)
