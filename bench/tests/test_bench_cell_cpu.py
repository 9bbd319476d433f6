"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: the window, the drain, the comparison and the metrics."""
import time

import pytest

from bench import harness
from bench.tests import tiny

DEVICE_METRICS = {"mfu.prefill", "mfu.decode", "device_idle_share",
                  "hbm_roofline.decode"}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


def test_untraced_run_reports_the_end_to_end_metrics(bench):
    lines = []
    out = harness.run_cell(bench, tiny.WORKLOAD, seed=2**33 + 1,
                           seconds=1.5, trace=False,
                           t_process=time.perf_counter(), log=lines.append)
    assert out["correct"] is True
    assert out["attempted"] == 12 and out["failed"] == 0
    cell = bench.cell(tiny.WORKLOAD)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "setup_s" in out["metrics"]
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-2:] == ["_check_lines", "check"] or \
        list(out)[-1] == "_check_lines"
    assert out["check"]["unfinished"] == {"value": 0, "limit": 0}
    assert out["check"]["widest_gap"]["value"] is not None
    assert [ln.split(":")[0] for ln in lines] == ["setup", "generator",
                                                  "readings"]
    assert "executables=" in lines[0] and "lag_p99_ms=" in lines[1]
    # a CPU run names its device and gives no device number
    assert out["device"]["platform"] == "cpu"
    assert "busy_s" not in out["device"]


def test_traced_run_reports_host_metrics_and_no_device_numbers(bench):
    out = harness.run_cell(bench, tiny.WORKLOAD, seed=3, seconds=1.5,
                           trace=True, t_process=time.perf_counter(),
                           log=lambda s: None)
    assert out["correct"] is True
    host = {m["name"] for m in bench.cell(tiny.WORKLOAD).per_layer} - \
        DEVICE_METRICS
    assert set(out["metrics"]) == host
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert "breakdown" not in out and "busy_s" not in out["device"]
