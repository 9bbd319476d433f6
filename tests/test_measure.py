"""Adaptive measurement engine: CI-based early stopping, incumbent
racing, the cross-process timing lease, and the MEP probe memo.

Run standalone (the CI ``test-measure`` job):

    PYTHONPATH=src python -m pytest -q tests/test_measure.py
"""
import json
import os
import random
import subprocess
import sys
import threading
import zlib

import pytest

from repro.core import (Campaign, CaseJob, CPUPlatform, EvalCache,
                        HeuristicProposer, InProcessExecutor, MeasureConfig,
                        MEPConstraints, OptConfig, Platform,
                        TPUModelPlatform, build_mep, get_case, wallclock)
from repro.core import measure as measure_mod
from repro.core.measure import (TimingLease, effective_k, measure_callable,
                                resolve_lease, trimmed_stats)
from repro.core.workers import run_case_job

FAST = MEPConstraints(t_max_s=2.0, r=5, k=1)


def _stream(values):
    it = iter(values)
    return lambda: next(it)


# ---------------------------------------------------------------- engine --
def test_adaptive_stops_early_on_low_noise():
    res = measure_callable(_stream([1.0, 1.0005, 0.9995] + [1.0] * 100),
                           r=30, k=3)
    assert res.r < 30 and res.r_cap == 30
    assert res.trimmed_mean_s == pytest.approx(1.0, rel=1e-3)
    assert res.ci_half_width_s <= 0.05 * res.trimmed_mean_s
    assert not res.raced_out and not res.deterministic


def test_noisy_measurement_runs_to_the_cap():
    rng = random.Random(0)
    res = measure_callable(lambda: rng.uniform(0.5, 1.5), r=30, k=3)
    assert res.r == 30                      # eq. 3 cap respected, not passed
    assert res.k == 3                       # full trim once n > 2k
    assert len(res.times_s) == 30


def test_cap_is_never_exceeded():
    rng = random.Random(1)
    for cap in (1, 2, 5, 17):
        res = measure_callable(lambda: rng.uniform(0.1, 10.0), r=cap, k=3)
        assert res.r <= cap


def test_partial_sample_trims_with_effective_k():
    # 5 reps against k=3: eq. 3 needs R > 2k, so the trim shrinks to
    # what the collected sample affords
    assert effective_k(5, 3) == 2
    assert effective_k(7, 3) == 3
    assert effective_k(1, 3) == 0
    mean, hw, ke = trimmed_stats([1.0, 2.0, 3.0, 4.0, 100.0], 3, 1.96)
    assert ke == 2 and mean == 3.0          # outliers dropped both sides
    assert hw == 0.0                        # single kept sample → no spread


def test_incumbent_racing_aborts_losers():
    # ci_rel tight enough that the CI never converges under the cap, so
    # the race decision is what stops the timing (CI convergence is
    # checked first: a converged loser is kept as a full record)
    rng = random.Random(2)
    res = measure_callable(lambda: rng.uniform(1.5, 2.5), r=30, k=3,
                           cfg=MeasureConfig(ci_rel=0.001),
                           incumbent_s=1.0)
    assert res.raced_out
    assert res.r < 30                       # did not pay the full cap
    assert res.lower_bound_s > 1.0          # provably cannot beat incumbent
    assert res.trimmed_mean_s > 1.0


def test_converged_loser_is_a_full_record_not_raced():
    """CI convergence is checked before racing: a loser whose timing
    already converged is cached full-fidelity (reusable against any
    future incumbent) instead of being stamped raced_out."""
    res = measure_callable(_stream([2.0, 2.001, 1.999] + [2.0] * 50),
                           r=30, k=3, incumbent_s=1.0)
    assert not res.raced_out
    assert res.r < 30                       # still stopped early (CI)
    assert res.trimmed_mean_s == pytest.approx(2.0, rel=1e-3)


def test_racing_never_aborts_a_winner():
    # candidate clearly faster than the incumbent: must run to CI
    # convergence (or cap), never raced out
    res = measure_callable(_stream([0.5] * 100), r=30, k=3, incumbent_s=1.0)
    assert not res.raced_out
    assert res.trimmed_mean_s == pytest.approx(0.5)


def test_race_disabled_pays_full_measurement():
    rng = random.Random(3)
    res = measure_callable(lambda: rng.uniform(1.9, 2.1), r=30, k=3,
                           cfg=MeasureConfig(race=False, ci_rel=1e-9),
                           incumbent_s=1.0)
    assert not res.raced_out and res.r == 30


def test_fixed_mode_matches_legacy_eq3():
    vals = [1.0, 5.0, 2.0, 0.1, 3.0, 2.5, 1.5, 2.2, 1.8, 2.1]
    res = measure_callable(_stream(vals), r=10, k=2,
                           cfg=MeasureConfig(adaptive=False))
    from repro.core import trimmed_mean
    assert res.r == 10 and res.k == 2
    assert res.trimmed_mean_s == pytest.approx(trimmed_mean(vals, 2))


def test_deterministic_short_circuits_to_one_rep():
    res = measure_callable(lambda: 0.25, r=30, k=3, deterministic=True)
    assert res.r == 1 and res.k == 0 and res.deterministic
    assert res.ci_half_width_s == 0.0
    assert res.trimmed_mean_s == 0.25


def test_measure_config_wire_roundtrip_via_optconfig():
    cfg = OptConfig(r=30, k=3,
                    measure=MeasureConfig(ci_rel=0.1, race=False))
    back = OptConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert back == cfg
    assert back.measure.ci_rel == 0.1 and back.measure.race is False
    # None stays None
    assert OptConfig.from_dict(OptConfig().to_dict()).measure is None


def test_resolve_lease_precedence():
    assert resolve_lease(None, "/tmp/x.lock").lease_path == "/tmp/x.lock"
    explicit = MeasureConfig(lease_path="/tmp/mine.lock")
    assert resolve_lease(explicit, "/tmp/x.lock").lease_path \
        == "/tmp/mine.lock"
    assert resolve_lease(None, None).lease_path is None


# ------------------------------------------------------------ satellites --
def test_wallclock_warmup_zero_no_nameerror():
    """Regression: warmup=0 used to crash on the unbound ``out`` before
    jax.block_until_ready."""
    calls = []

    def fn(x):
        calls.append(1)
        return x

    res = wallclock(fn, (1.0,), r=3, k=0, warmup=0)
    assert len(calls) == 3                  # no warmup call happened
    assert res.r == 3

    calls.clear()
    wallclock(fn, (1.0,), r=3, k=0, warmup=2)
    assert len(calls) == 5                  # each warmup call executed


def test_tpu_model_platform_single_rep():
    """The analytic platform is deterministic: one rep, no synthetic
    [t]*R padding (the old path silently padded when r <= 2k)."""
    case = get_case("gemm")
    res = TPUModelPlatform().time_variant(case, case.baseline_variant,
                                          256, None, r=5, k=3)
    assert res.r == 1 and res.k == 0
    assert res.deterministic
    assert len(res.times_s) == 1
    assert res.ci_half_width_s == 0.0
    assert res.trimmed_mean_s == res.times_s[0] > 0


class _CountingCPU(CPUPlatform):
    def __init__(self):
        super().__init__()
        self.timings = 0

    def time_variant(self, *a, **kw):
        self.timings += 1
        return super().time_variant(*a, **kw)


def test_build_mep_probe_memo_dedups_across_calls():
    measure_mod.clear_probe_memo()
    plat = _CountingCPU()
    case = get_case("gemm")
    mep1 = build_mep(case, plat, constraints=FAST, seed=0)
    first = plat.timings
    assert first >= 1
    mep2 = build_mep(case, plat, constraints=FAST, seed=0)
    assert mep2.scale == mep1.scale
    assert plat.timings == first            # every probe memo-served
    assert measure_mod.probe_hits >= 1


def test_build_mep_fallback_never_retimes_probed_scale():
    """All scales time-rejected → the fallback must reuse the smallest
    scale's existing probe, not pay a second timing for it."""
    measure_mod.clear_probe_memo()
    plat = _CountingCPU()
    case = get_case("gemm")
    tight = MEPConstraints(t_max_s=1e-9, r=5, k=1)    # rejects everything
    mep = build_mep(case, plat, constraints=tight, seed=0)
    assert any("fallback" in line for line in mep.log)
    # one probe per admissible scale, none repeated for the fallback
    admissible = sum(1 for line in mep.log if "rejected, projected" in line)
    assert plat.timings == admissible


# ------------------------------------------------------------- the lease --
def test_timing_lease_serializes_threads(tmp_path):
    lease = TimingLease(str(tmp_path / "lease.lock"))
    active, overlaps = [0], [0]

    def worker():
        for _ in range(25):
            with lease.slice_():
                active[0] += 1
                if active[0] > 1:
                    overlaps[0] += 1
                active[0] -= 1

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert overlaps[0] == 0
    assert lease.acquisitions == 100


def test_engine_uses_lease_for_wallclock_slices(tmp_path):
    path = str(tmp_path / "lease.lock")
    cfg = MeasureConfig(lease_path=path, lease_slice=2, adaptive=False)
    measure_callable(_stream([1.0] * 10), r=10, k=1, cfg=cfg)
    assert os.path.exists(path)
    assert measure_mod.get_lease(path).acquisitions >= 5   # 10 reps / 2


# ------------------------------------------ raced-out is always a loss ----
class _ScriptedPlatform(Platform):
    """Measured-style platform whose per-variant 'wall clock' is a
    deterministic pseudo-noise stream around a variant-dependent mean:
    the baseline is slow, one candidate is fast, the rest are far
    slower — racing must retire the losers, never the winner."""
    name = "scripted"
    concurrency_safe = False

    def _mean(self, variant) -> float:
        if variant.get("block_m") == 256:
            return 0.5                       # the true winner
        if variant == {"block_m": 128, "block_n": 128, "block_k": 128}:
            return 1.0                       # baseline
        # stable digest, NOT the salted builtin hash(): the loser means
        # must sit at 2.0-2.6 under every PYTHONHASHSEED so racing
        # deterministically triggers
        digest = zlib.crc32(repr(sorted(variant.items())).encode())
        return 2.0 + (digest % 7) / 10.0

    def time_variant(self, case, variant, scale, inputs, *, r, k,
                     budget=None, incumbent_s=None):
        # ±10% noise: wide enough that losers race out before their CI
        # converges, narrow enough that the winner ordering is stable
        mean = self._mean(variant)
        rng = random.Random(repr(sorted(variant.items())))
        return measure_callable(
            lambda: mean * rng.uniform(0.9, 1.1), r=r, k=k,
            cfg=budget, incumbent_s=incumbent_s)


def test_raced_out_candidates_never_win():
    # ci_rel tight enough that losers hit the race decision before CI
    # convergence (otherwise they'd stop as full-fidelity records)
    case = get_case("gemm")
    job = CaseJob(case, HeuristicProposer(0),
                  cfg=OptConfig(d_rounds=3, n_candidates=4, r=30, k=3,
                                measure=MeasureConfig(ci_rel=0.001)),
                  constraints=MEPConstraints(r=30, k=3))
    res = run_case_job(job, _ScriptedPlatform())
    assert res.raced_out >= 1                # racing actually triggered
    raced_variants = [c.variant for rl in res.rounds for c in rl.candidates
                      if c.raced_out]
    assert res.best_variant not in raced_variants
    # the per-round winners were all full (non-raced) measurements
    for rl in res.rounds:
        for c in rl.candidates:
            if c.status == "ok" and not c.raced_out:
                assert c.time_s >= rl.best_time_s or not rl.improved \
                    or c.time_s == pytest.approx(rl.best_time_s)
    # economy: racing + CI stop paid fewer reps than fixed-R would
    assert 0 < res.timing_reps < res.timing_reps_fixed


def test_raced_out_cache_replay_revalidates_against_new_incumbent(tmp_path):
    """A cached raced-out record is only a hit while it still provably
    loses; against a *worse* incumbent the candidate might win, so the
    evaluator must re-measure instead of replaying the partial timing."""
    from repro.core.evalcache import EvalRecord

    cache = EvalCache(str(tmp_path / "ec.jsonl"))
    spec = {"kind": "eval", "case": "x", "variant": {}, "scale": 1,
            "platform": "scripted"}
    calls = [0]

    def compute():
        calls[0] += 1
        return EvalRecord(status="ok", time_s=2.0, raced_out=True,
                          lower_bound_s=1.9)

    def accept_for(incumbent):
        def accept(rec):
            if not rec.raced_out:
                return True
            return incumbent is not None and rec.lower_bound_s > incumbent
        return accept

    cache.get_or_compute(spec, compute, accept=accept_for(1.0))
    assert calls[0] == 1
    # same incumbent → still a provable loss → replay
    _, hit = cache.get_or_compute(spec, compute, accept=accept_for(1.0))
    assert hit and calls[0] == 1
    # incumbent got worse (slower) → record no longer proves a loss
    _, hit = cache.get_or_compute(spec, compute, accept=accept_for(2.5))
    assert not hit and calls[0] == 2


# ------------------------------------------------- measured fan-out e2e ---
@pytest.mark.slow
def test_measured_campaign_fans_out_across_processes(tmp_path):
    """End-to-end: a CPU (measured) campaign on SubprocessExecutor with
    2 workers — the configuration the old pinning made impossible —
    completes with full per-candidate timings."""
    from repro.core import SubprocessExecutor, OptResult

    cache = EvalCache(str(tmp_path / "ec.jsonl"))
    ex = SubprocessExecutor(2)
    try:
        camp = Campaign(CPUPlatform(), executor=ex, cache=cache,
                        measure=MeasureConfig(ci_rel=0.2))
        jobs = [CaseJob(get_case(n), HeuristicProposer(0),
                        cfg=OptConfig(d_rounds=1, n_candidates=2, r=5, k=1),
                        constraints=FAST, seed=0)
                for n in ("atax", "bicg")]
        results = camp.run(jobs)
    finally:
        slots = {s for _, s in ex.dispatch_log}
        ex.close()
    assert len(slots) == 2                   # both workers actually used
    assert camp.lease_path == cache.path + ".timelease"
    for res in results:
        assert isinstance(res, OptResult)
        assert res.timing_reps > 0
        assert res.best_time_s > 0
