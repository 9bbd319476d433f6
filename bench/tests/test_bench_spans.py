"""The reduction of the program's ``serve.*`` spans (``bench/spans.py``) and
its two readers: on a hand-built trace whose every number is worked out
below, and on two small traces recorded on a TPU v5 lite, one without
program spans, whose gaps it must read as ``tracereduce`` does, and one
with them."""
import os

import pytest

from bench import spans, tracereduce
from bench.spec import Bench
from bench.tracereduce import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000                          # ns

# two steps of a server inside one ``serving`` span, times in ms
HOST = [("serving", 0, 100), ("step", 10, 50), ("serve.step", 11, 49),
        ("serve.prefill", 12, 15), ("serve.prefill_wait", 15, 20),
        ("serve.decode", 20.5, 30), ("serve.decode_wait", 30, 40),
        ("serve.bookkeeping", 41, 48), ("generator", 51, 54),
        ("serve.submit", 52, 53), ("step", 55, 95), ("serve.step", 56, 94),
        ("serve.decode", 57, 62), ("serve.decode_wait", 62, 80),
        ("serve.bookkeeping", 86, 93)]
OPS = [(16, 19), (22, 24), (27, 39), (75, 84)]
DEV = "/device:TPU:0"


def _ns(events):
    return [(n, s * MS, e * MS) for n, s, e in events]


@pytest.fixture
def trace():
    return Trace(modules={DEV: _ns([("jit_packed_prefill(1)", 16, 19),
                                    ("jit_decode_and_pick(2)", 22, 39),
                                    ("jit_decode_and_pick(2)", 75, 84)])},
                 ops={DEV: _ns([(f"%op.{i} = f()", s, e)
                                for i, (s, e) in enumerate(OPS)])},
                 spans=sorted(_ns(HOST), key=lambda e: e[1]))


def _run(trace):
    return type("Run", (), {"trace": trace})()


def test_gaps_are_named_by_the_innermost_span(trace):
    # (39, 75) runs from the first step's serve.decode_wait through its
    # bookkeeping and the generator into the second step's serve.decode
    # and serve.decode_wait, which hold 14 ms of it, more than any other;
    # (19, 22) ends in serve.decode, which holds 1.5 ms; (24, 27) lies
    # inside serve.decode.
    assert spans.idle_gaps(trace) == [["serve.decode_wait", 0.036],
                                      ["serve.decode", 0.003],
                                      ["serve.decode", 0.003]]
    # the benchmark's own labels see the same gaps, all as ``step``
    assert tracereduce.idle_gaps(trace) == [["step", 0.036],
                                            ["step", 0.003],
                                            ["step", 0.003]]


def test_step_host_time_leaves_out_the_waits(trace):
    # steps of 38 ms waiting 5 + 10 and 18 ms: 23 and 20, median 21.5
    assert spans.step_host_ms(trace) == pytest.approx(21.5)
    bench = Bench()
    assert bench.metric_reader("step_host_ms")(_run(trace)) == \
        pytest.approx(21.5)


def test_idle_inside_the_step_is_part_of_the_idle_share(trace):
    # serve.step covers 76 ms of the 100 ms serving span, 26 of them busy
    assert spans.idle_in_step(trace) == pytest.approx(0.50)
    assert tracereduce.idle_share(trace) == pytest.approx(0.74)
    read = Bench().metric_reader("device_idle_share.in_step")
    assert read(_run(trace)) == pytest.approx(50.0)
    assert read(_run(trace)) <= \
        Bench().metric_reader("device_idle_share")(_run(trace))


def test_idle_split_charges_the_innermost_open_span(trace):
    split = spans.idle_by_span(trace)
    want = {"none": 17, "serve.decode_wait": 14, "serve.bookkeeping": 14,
            "serve.decode": 9.5, "serve.step": 7.5, "step": 4,
            "serve.prefill": 3, "serve.prefill_wait": 2,
            "generator": 2, "serve.submit": 1}     # submit: inside generator
    assert split == pytest.approx({k: v / 1e3 for k, v in want.items()})
    assert sum(split.values()) == pytest.approx(0.074)
    assert sum(v for k, v in split.items() if k.startswith("serve.")
               and k != "serve.submit") == \
        pytest.approx(spans.idle_in_step(trace) * 0.1)


def test_innermost_pieces_cover_nested_spans():
    pieces = spans.innermost([("a", 0, 10), ("b", 2, 5), ("c", 3, 4),
                              ("d", 6, 10)])
    assert pieces == [("a", 0, 2), ("b", 2, 3), ("c", 3, 4), ("b", 4, 5),
                      ("a", 5, 6), ("d", 6, 10)]


def test_intersect_of_merged_lists():
    a, b = [(0, 4), (6, 10), (12, 13)], [(2, 7), (9, 12)]
    assert spans.intersect(a, b) == [(2, 4), (6, 7), (9, 10)]
    assert tracereduce.length(spans.intersect(a, b)) == \
        tracereduce.overlap(a, b)
    assert spans.intersect(a, []) == []


@pytest.mark.parametrize("name", ["step_host_ms",
                                  "device_idle_share.in_step"])
def test_a_trace_without_program_spans(name):
    trace = spans.load(os.path.join(DATA, "tiny_v5e.xplane.pb"))
    assert spans.idle_gaps(trace) == tracereduce.idle_gaps(trace)
    assert spans.step_host_ms(trace) is None
    assert spans.idle_in_step(trace) is None
    read = Bench().metric_reader(name)
    assert read(_run(trace)) is None
    assert read(_run(None)) is None


# a trace with the program's spans, recorded on a TPU v5 lite: the tiny
# cell of ``tiny.py``, one chip, a 0.5 s window, seed 2200000105
SPANS_TRACE = os.path.join(DATA, "tiny_v5e_spans.xplane.pb")
PINNED_GAPS = [["await_arrival", 0.270497557],
               ["await_arrival", 0.059103373],
               ["serve.prefill", 0.002545081],
               ["serve.decode_wait", 0.002300767],
               ["serve.decode", 0.002283805], ["serve.decode", 0.002221689],
               ["serve.decode_wait", 0.002220362],
               ["serve.decode", 0.002186657], ["serve.prefill", 0.002184014],
               ["serve.decode", 0.002174081]]
# step_host_ms, idle share inside serve.step, idle share
PINNED = (1.104665, 0.9887520442669482, 0.9939727255616863)


@pytest.fixture(scope="module")
def recorded():
    return spans.load(SPANS_TRACE)


def test_recorded_spans_nest_in_the_benchmarks_steps(recorded):
    names = [n for n, _, _ in recorded.spans]
    assert set(spans.SERVE_SPANS) - set(names) == {"serve.rebuild"}
    steps = tracereduce.span_intervals(recorded, "step")
    assert names.count("serve.step") == names.count("step") == 22
    for n, s, e in recorded.spans:
        if n.startswith("serve.") and n != "serve.submit":
            assert any(a <= s and e <= b for a, b in steps), n


def test_recorded_gaps_and_readings(recorded):
    assert spans.idle_gaps(recorded) == PINNED_GAPS
    assert [s for _, s in tracereduce.idle_gaps(recorded)] == \
        [s for _, s in PINNED_GAPS]
    readings = (spans.step_host_ms(recorded), spans.idle_in_step(recorded),
                tracereduce.idle_share(recorded))
    assert readings == pytest.approx(PINNED, rel=1e-9)
    assert readings[1] <= readings[2]
    bench = Bench()
    assert bench.metric_reader("device_idle_share.in_step")(
        _run(recorded)) == pytest.approx(100 * PINNED[1], rel=1e-9)
    serving = tracereduce.length(
        tracereduce.span_intervals(recorded, "serving")) / 1e9
    split = spans.idle_by_span(recorded)
    assert sum(split.values()) == pytest.approx(serving * PINNED[2])
    assert sum(v for k, v in split.items() if k.startswith("serve.")
               and k != "serve.submit") == \
        pytest.approx(serving * PINNED[1])
