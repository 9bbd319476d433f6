"""The reduction from a profiler trace to device time, busy and idle
shares and the breakdown, on a small trace recorded on a TPU v5 lite (the
tiny cell of ``tiny.py``, one chip, a 0.5 s window, seed 105)."""
import os

import pytest

from bench import tracereduce as tr

# the reduction's readings of this file: busy, prefill, decode seconds, idle
PINNED = (0.000354029, 4.8577e-05, 0.00031738, 0.9935191583797099)
TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(TRACE)


def test_planes_executables_and_spans(trace):
    assert trace.devices == ["/device:TPU:0"]
    mods = {tr.module_name(n) for n, _, _ in trace.modules["/device:TPU:0"]}
    assert mods == {"packed_prefill", "decode_and_pick"}
    assert {n for n, _, _ in trace.spans} == {"step", "generator",
                                              "await_arrival", "serving"}


def test_busy_idle_and_executable_time(trace):
    busy = tr.busy_s(trace)
    prefill = tr.module_seconds(trace, "packed_prefill")
    decode = tr.module_seconds(trace, "decode_and_pick")
    serving = tr.length(tr.span_intervals(trace, "serving")) / 1e9
    assert 0 < busy <= prefill + decode
    assert busy < serving < 0.5
    idle = tr.idle_share(trace)
    assert 0.0 < idle < 1.0
    assert idle == pytest.approx(1.0 - busy / serving, abs=0.02)
    assert (busy, prefill, decode, idle) == pytest.approx(PINNED, rel=1e-9)


def test_breakdown(trace):
    ops = tr.top_ops(trace)
    assert len(ops) == 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert all(n.split("/")[0] in ("packed_prefill", "decode_and_pick")
               for n, _ in ops)
    assert not any(n.split("/")[1].startswith("while") for n, _ in ops)
    gaps = tr.idle_gaps(trace)
    assert len(gaps) == 10
    assert {n for n, _ in gaps} <= {"step", "generator", "await_arrival",
                                    "none"}
    assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                          reverse=True)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.overlap([(0, 3), (5, 8)], [(2, 6)]) == 2
    assert tr.length([(0, 3), (5, 8)]) == 6
    assert tr.op_name("%fusion.12 = bf16[2]{0} fusion(x)") == "fusion.12"
    assert tr.module_name("jit_decode_and_pick(1613)") == "decode_and_pick"
