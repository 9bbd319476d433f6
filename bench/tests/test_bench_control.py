"""The control that the limits on ``correct`` are set against: the plain
reference computed in int8, judged in the server's place on the same
prompts and served tokens, reads wider gaps than the served tokens do.

On a TPU v5e, at the cell's own size, the control's smallest readings
are 7 times the program's largest widest gap and 24 times its largest
mean gap (PERF.md), and fail the cell's limits.  At the width this test
can hold on a CPU (two layers of width 256) the two lie closer together,
so it checks their order, summed over three seeds, not the limits."""
import time

import pytest

from bench import harness
from bench.tests import tiny

SMALL = dict(tiny.TINY_MODEL, d_model=256, d_ff=768, head_dim=64,
             vocab_size=4096)
CELL = dict(tiny.TINY_CELL, check=dict(tiny.TINY_CELL["check"],
                                        min_tokens=120))


@pytest.fixture(scope="module")
def bench(tmp_path_factory, monkeypatch_module):
    monkeypatch_module.setitem(tiny.TINY_CONFIG, "model", SMALL)
    return tiny.make(str(tmp_path_factory.mktemp("small")), cell=CELL)


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


def test_control_reads_wider_gaps_than_the_program(bench):
    cell = bench.cell(tiny.WORKLOAD)
    assert cell.config["model"]["d_model"] == 256
    compiles = harness.CompileLog()
    totals = {False: [0.0, 0.0], True: [0.0, 0.0]}
    try:
        for seed in (1, 2, 4):
            params, server, _ = harness.start_server(cell, seed, compiles)
            run = harness.serve_window(server, cell, seed=seed, seconds=2.0,
                                       rate=cell.settings["rate_rps"],
                                       trace=False, compiles=compiles)
            for control in (False, True):
                chk = harness.reference_check(cell, params,
                                              run.window.served, seed,
                                              control=control)
                r = chk["readings"]
                assert r["tokens_compared"] >= 100
                totals[control][0] += r["widest_gap"]
                totals[control][1] += r["mean_gap"]
    finally:
        compiles.close()
    (pw, pm), (cw, cm) = totals[False], totals[True]
    assert cw > 2 * pw and cm > 2 * pm, totals


def test_control_run_puts_the_int8_reference_in_the_servers_place(
        tmp_path):
    """``--control`` judges the int8 reference's choices: with limits of
    0 it is not correct, as it departs from the float32 reference on
    some token of the sample."""
    strict = dict(CELL, check=dict(CELL["check"], limits={
        "widest_gap": 0.0, "mean_gap": 0.0}))
    with pytest.MonkeyPatch().context() as mp:
        mp.setitem(tiny.TINY_CONFIG, "model", SMALL)
        b = tiny.make(str(tmp_path), cell=strict)
    out = harness.run_cell(b, tiny.WORKLOAD, seed=2, seconds=2.0,
                           trace=False, t_process=time.perf_counter(),
                           control=True, log=lambda s: None)
    assert out["check"]["widest_gap"]["value"] > 0
    assert out["correct"] is False
