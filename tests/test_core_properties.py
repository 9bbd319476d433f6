"""Hypothesis property tests on the MEP invariants (eq. 2–4) and the
PatternStore invariants (§3.2 Performance Pattern Inheritance).

Kept separate from test_core_mep.py so environments without the optional
``hypothesis`` dev dependency (see requirements-dev.txt) skip these
instead of failing collection.
"""
import json
import math
import random

import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="optional dev dependency; pip install -r "
                           "requirements-dev.txt")
from hypothesis import given, settings, strategies as st

from repro.core import PatternStore, fe_check, get_case, trimmed_mean
from repro.core.datagen import generate
from repro.core.kernelcase import ArraySpec
from repro.core.patterns import Pattern


# -------------------------------------------------------- eq.3 trimmed ----
@given(st.lists(st.floats(min_value=1e-6, max_value=1e3,
                          allow_nan=False), min_size=7, max_size=50),
       st.integers(min_value=0, max_value=3))
@settings(max_examples=100, deadline=None)
def test_trimmed_mean_properties(times, k):
    if len(times) <= 2 * k:
        with pytest.raises(ValueError):
            trimmed_mean(times, k)
        return
    tm = trimmed_mean(times, k)
    s = sorted(times)
    # bounded by the kept extremes
    assert s[k] - 1e-9 <= tm <= s[len(s) - k - 1] + 1e-9
    # permutation invariant
    assert math.isclose(tm, trimmed_mean(list(reversed(times)), k),
                        rel_tol=1e-9)
    # outlier robustness: inflating the max by 1000× can't change k>0 trim
    if k > 0:
        inflated = s[:-1] + [s[-1] * 1000]
        assert math.isclose(tm, trimmed_mean(inflated, k), rel_tol=1e-9)


# ------------------------------------------------------ datagen / eq.2 ----
@given(st.integers(min_value=1, max_value=64),
       st.integers(min_value=1, max_value=64),
       st.sampled_from(["normal", "uniform", "positive", "sorted",
                        "symmetric", "spd"]))
@settings(max_examples=50, deadline=None)
def test_datagen_properties(n, m, kind):
    spec = ArraySpec((n, m) if kind not in ("symmetric", "spd") else (n, n),
                     "float32", kind)
    a, = generate([spec], seed=7)
    b, = generate([spec], seed=7)
    np.testing.assert_array_equal(a, b)          # deterministic
    assert a.nbytes == spec.nbytes
    if kind == "sorted":
        assert np.all(np.diff(a, axis=-1) >= 0)
    if kind == "symmetric":
        np.testing.assert_allclose(a, a.T, rtol=1e-6)
    if kind == "spd":
        ev = np.linalg.eigvalsh(a.astype(np.float64))
        assert ev.min() > 0
    if kind == "positive":
        assert a.min() > 0


# ----------------------------------------------- variant-space property ---
@given(st.data())
@settings(max_examples=15, deadline=None)
def test_random_variants_preserve_fe(data):
    """Any point in a case's variant space is functionally equivalent
    (the optimizer can never trade correctness for speed)."""
    name = data.draw(st.sampled_from(["atax", "gesummv", "reduction",
                                      "vectoradd", "dwthaar1d",
                                      "fastwalshtransform"]))
    case = get_case(name)
    variant = {k: data.draw(st.sampled_from(vs))
               for k, vs in case.variant_space.items()}
    rtol = 200.0 if variant.get("compute_dtype") == "bf16" else 1.0
    r = fe_check(case, variant, min(case.scales), n_input_sets=1,
                 rtol_scale=rtol)
    assert r.ok, f"{name} {variant}: {r.detail}"


# ---------------------------------------- PatternStore invariants (§3.2) --
class _Case:
    """record/suggest only touch .name and .family."""
    def __init__(self, name, family):
        self.name, self.family = name, family


_gains = st.floats(min_value=1.03, max_value=100.0, allow_nan=False)
_names = st.sampled_from(["k0", "k1", "k2", "k3"])
_families = st.sampled_from(["matmul", "scan", "stencil"])
_platforms = st.sampled_from(["cpu", "tpu-v5e-model"])
_deltas = st.dictionaries(
    st.sampled_from(["block_m", "block_n", "block_k", "unroll", "dtype"]),
    st.sampled_from([32, 64, 128, 256, "bf16", True]),
    min_size=1, max_size=3)


@given(name=_names, family=_families, platform=_platforms,
       delta=_deltas, gain=_gains)
@settings(max_examples=50, deadline=None)
def test_pattern_record_suggest_roundtrip(name, family, platform,
                                          delta, gain):
    """Any recorded win (gain above the noise floor, non-empty delta) is
    suggested back for a sibling kernel of the same family/platform."""
    store = PatternStore()
    store.record(_Case(name, family), platform, {}, dict(delta), gain)
    hints = store.suggest(_Case("sibling", family), platform)
    assert dict(delta) in hints


@given(gains=st.lists(_gains, min_size=1, max_size=10),
       delta=_deltas)
@settings(max_examples=50, deadline=None)
def test_pattern_merge_keeps_max_gain(gains, delta):
    store = PatternStore()
    for g in gains:
        store.record(_Case("k", "matmul"), "cpu", {}, dict(delta), g)
    assert len(store) == 1
    assert store.patterns[0].gain == pytest.approx(max(gains))


@given(own_gain=_gains, other_gain=_gains, platform=_platforms)
@settings(max_examples=50, deadline=None)
def test_suggest_never_echoes_own_delta_first(own_gain, other_gain,
                                              platform):
    """A kernel's own winning delta is already its baseline: whenever
    any other kernel has contributed a pattern, the own-sourced delta
    must not lead the hints — regardless of relative gains."""
    store = PatternStore()
    store.record(_Case("me", "matmul"), platform, {},
                 {"block_m": 128}, own_gain)
    store.record(_Case("other", "matmul"), "cpu", {},
                 {"block_n": 64}, other_gain)
    first = store.suggest_patterns(_Case("me", "matmul"), platform)[0]
    assert first.source_kernel != "me"


@given(data=st.lists(
    st.tuples(_names, _families, _platforms, _deltas, _gains),
    min_size=1, max_size=20), seed=st.integers(0, 2 ** 16))
@settings(max_examples=30, deadline=None)
def test_journal_replay_is_order_insensitive(tmp_path_factory, data, seed):
    """Shuffling the journal lines cannot change the merged view: same
    (family, platform, delta) keys, same max gains."""
    import os
    tmp = tmp_path_factory.mktemp("pat")
    lines = [json.dumps(Pattern(f, p, dict(d), g, n).to_dict())
             for n, f, p, d, g in data]
    shuffled = list(lines)
    random.Random(seed).shuffle(shuffled)

    def merged_view(journal_lines, tag):
        path = os.path.join(str(tmp), f"{tag}.jsonl")
        with open(path, "w") as f:
            f.write("\n".join(journal_lines) + "\n")
        store = PatternStore(path)
        return {k: v.gain for k, v in
                ((p.merge_key(), p) for p in store.patterns)}

    assert merged_view(lines, "a") == merged_view(shuffled, "b")


# ------------------------------------------- adaptive measurement engine --
from repro.core.measure import (MeasureConfig, effective_k,  # noqa: E402
                                measure_callable, trimmed_stats)


def _noise_samples(mean, noise, seed, n):
    rng = random.Random(seed)
    return [mean * (1.0 + rng.uniform(-noise, noise)) for _ in range(n)]


@given(st.lists(st.floats(min_value=1e-4, max_value=1e2,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=40),
       st.integers(min_value=0, max_value=4))
@settings(max_examples=100, deadline=None)
def test_trimmed_stats_matches_eq3_on_partial_samples(times, k):
    """trimmed_stats degrades k to what the sample affords and must
    agree with the eq. 3 trimmed mean at that effective k."""
    mean, hw, ke = trimmed_stats(times, k, 1.96)
    assert ke == effective_k(len(times), k)
    assert len(times) > 2 * ke                   # eq. 3 precondition holds
    assert mean == pytest.approx(trimmed_mean(times, ke), rel=1e-9)
    assert hw >= 0.0
    # permutation invariant, like the trimmed mean itself
    m2, h2, k2 = trimmed_stats(list(reversed(times)), k, 1.96)
    assert (m2, h2, k2) == (pytest.approx(mean), pytest.approx(hw), ke)


@given(st.integers(min_value=2, max_value=4),      # candidate count
       st.floats(min_value=0.0, max_value=0.02),   # relative noise
       st.integers(min_value=0, max_value=2**31))  # stream seed
@settings(max_examples=60, deadline=None)
def test_adaptive_stopping_preserves_fixed_r_winner(n_cands, noise, seed):
    """On synthetic noise distributions whose means are separated by
    more than the noise + CI widths, CI-based early stopping and
    incumbent racing must pick the same argmin a full fixed-R=30 sweep
    picks — and the raced-out losers must be losers under fixed-R too."""
    r_cap, k = 30, 3
    means = [1.0 * (1.3 ** i) for i in range(n_cands)]   # ≥30% separation
    random.Random(seed).shuffle(means)
    streams = [_noise_samples(m, noise, f"{seed}:{i}", r_cap)
               for i, m in enumerate(means)]

    fixed = [trimmed_mean(s, k) for s in streams]
    fixed_winner = fixed.index(min(fixed))

    # sequential search-loop semantics: the incumbent is the best
    # adaptive mean seen so far; raced-out candidates are losses
    incumbent = None
    adaptive = []
    for s in streams:
        res = measure_callable(iter(s).__next__, r=r_cap, k=k,
                               incumbent_s=incumbent)
        adaptive.append(res)
        if not res.raced_out and (incumbent is None
                                  or res.trimmed_mean_s < incumbent):
            incumbent = res.trimmed_mean_s
    feasible = [i for i, res in enumerate(adaptive) if not res.raced_out]
    winner = min(feasible, key=lambda i: adaptive[i].trimmed_mean_s)

    assert winner == fixed_winner
    assert not adaptive[fixed_winner].raced_out
    for i, res in enumerate(adaptive):
        assert res.r <= r_cap                      # eq. 3 cap respected
        if res.raced_out:                          # raced ⇒ fixed-R loser
            assert fixed[i] > fixed[fixed_winner]


@given(st.floats(min_value=1e-3, max_value=10.0),
       st.floats(min_value=0.0, max_value=0.05),
       st.integers(min_value=0, max_value=2**31))
@settings(max_examples=60, deadline=None)
def test_adaptive_mean_is_ci_close_to_fixed_r_mean(mean, noise, seed):
    """Early stopping may not bias the estimate: the adaptively-stopped
    trimmed mean lies within its own reported CI (plus the noise span)
    of the full fixed-R trimmed mean over the same stream."""
    r_cap, k = 30, 3
    samples = _noise_samples(mean, noise, seed, r_cap)
    res = measure_callable(iter(samples).__next__, r=r_cap, k=k)
    full = trimmed_mean(samples, k)
    tol = res.ci_half_width_s + noise * mean + 1e-12
    assert abs(res.trimmed_mean_s - full) <= tol
