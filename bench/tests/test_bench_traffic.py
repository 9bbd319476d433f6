"""The general traffic generator: deterministic per seed, the same work
for every seed, and the stated distributions."""
import json
import os
import statistics

import numpy as np
import pytest

from bench import traffic
from bench.spec import BENCH_DIR
from bench.tests import tiny


def _mix(name):
    if name == "tiny_chat":
        return tiny.TINY_TRAFFIC
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "tiny_chat"])
def test_schedule_is_deterministic_per_seed(name):
    a = traffic.schedule(_mix(name), rate=3.0, seconds=40, seed=2**33 + 7,
                         vocab=1000)
    b = traffic.schedule(_mix(name), rate=3.0, seconds=40, seed=2**33 + 7,
                         vocab=1000)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.schedule(_mix(name), rate=3.0, seconds=40, seed=8,
                         vocab=1000)
    assert [x.due_s for x in a] != [x.due_s for x in c]


@pytest.mark.parametrize("name", ["chat", "tiny_chat"])
def test_every_seed_gets_the_same_work_in_another_order(name):
    runs = [traffic.schedule(_mix(name), rate=2.5, seconds=40, seed=s,
                             vocab=1000) for s in (1, 2, 3)]
    for run in runs:
        assert len(run) == 100
        assert all(0.0 <= x.due_s < 40.0 for x in run)
        assert [x.due_s for x in run] == sorted(x.due_s for x in run)
    for size in (lambda x: len(x.prompt), lambda x: x.max_new):
        sets = [sorted(map(size, run)) for run in runs]
        assert sets[0] == sets[1] == sets[2]
    gaps = [sorted(np.diff([x.due_s for x in run]).round(9)) for run in runs]
    assert len(set(map(tuple, gaps))) > 1      # the order differs ...
    assert abs(sum(gaps[0]) - sum(gaps[1])) < 1.0   # ... the span hardly


def test_chat_lengths_follow_the_stated_distribution():
    mix = _mix("chat")
    n = 400
    prompt = traffic.length_set(mix["prompt_len"], n)
    assert prompt.min() >= 16 and prompt.max() <= 256
    assert statistics.median(prompt.tolist()) == pytest.approx(128, abs=1)
    out = traffic.length_set(mix["max_new"], n)
    assert out.min() >= 8 and out.max() <= 256
    # lognormal with sigma 0.6: the 16th percentile is median * e^-0.6
    assert np.percentile(prompt, 15.87) == pytest.approx(
        128 * np.exp(-0.6), rel=0.03)


def test_uniform_lengths_cover_both_ends_evenly():
    out = traffic.length_set({"dist": "uniform", "min": 16, "max": 64}, 400)
    assert out.min() == 16 and out.max() == 64
    counts = np.bincount(out - 16)
    assert counts.max() - counts.min() <= 1


def test_poisson_gaps():
    g = traffic.gap_set({"kind": "poisson"}, 2000, 100.0)
    assert g.sum() == pytest.approx(100.0)
    assert np.std(g) / np.mean(g) == pytest.approx(1.0, abs=0.05)
    with pytest.raises(ValueError):
        traffic.gap_set({"kind": "bursty"}, 10, 1.0)
