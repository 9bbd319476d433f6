"""Diagnosis classifier, learned pattern ranking, and LLM-reply
validation (PR: diagnosis-driven proposals)."""
import json
import os

import pytest

from repro.core.diagnosis import (BALANCED_MARGIN, BOTTLENECKS,
                                  Diagnosis, classify, diagnose_feedback,
                                  ridge_flop_per_byte)
from repro.core.kernelcase import get_case
from repro.core.patterns import PatternStore
from repro.core.profiler import TPUModelPlatform
from repro.core.proposer import (HeuristicProposer, LLMProposer,
                                 ProposalError, RoundState, _json_span,
                                 _validated)


# ---------------------------------------------------------------------------
# bottleneck classifier: one synthetic fixture per class
# ---------------------------------------------------------------------------
class TestClassify:
    def test_memory_bound(self):
        d = classify(1e-6, 5e-6, arithmetic_intensity=10.0)
        assert d.bottleneck == "memory"
        assert d.memory_fraction > d.compute_fraction
        assert d.arithmetic_intensity < d.ridge_flop_per_byte

    def test_compute_bound(self):
        d = classify(5e-6, 1e-6, mxu_utilization=0.95)
        assert d.bottleneck == "compute"

    def test_latency_bound(self):
        d = classify(1e-6, 1e-6, latency_s=8e-6)
        assert d.bottleneck == "latency"
        assert d.latency_fraction > 0.5

    def test_collective_bound(self):
        d = classify(1e-6, 1e-6, collective_s=8e-6)
        assert d.bottleneck == "collective"

    def test_occupancy_from_underfilled_mxu(self):
        # compute dominates but the MXU is badly under-filled:
        # alignment, not flops, is the lever
        d = classify(5e-6, 1e-6, mxu_utilization=0.3)
        assert d.bottleneck == "occupancy"

    def test_occupancy_from_vmem_overflow_trumps_everything(self):
        d = classify(1e-6, 9e-6, vmem_fraction=0.95)
        assert d.bottleneck == "occupancy"

    def test_balanced_within_margin(self):
        d = classify(1.0, 1.0 + BALANCED_MARGIN / 4)
        assert d.bottleneck == "balanced"

    def test_zero_terms_is_low_confidence_balanced(self):
        d = classify(0.0, 0.0)
        assert d.bottleneck == "balanced"
        assert d.confidence == pytest.approx(0.05)

    def test_noisy_timing_discounts_confidence(self):
        clean = classify(1e-6, 9e-6)
        noisy = classify(1e-6, 9e-6, ci_rel=0.5)
        assert noisy.bottleneck == clean.bottleneck == "memory"
        assert noisy.confidence < clean.confidence
        floor = classify(1e-6, 9e-6, ci_rel=10.0)
        assert floor.confidence == pytest.approx(0.05)

    def test_all_verdicts_in_registry(self):
        for d in (classify(5e-6, 1e-6), classify(1e-6, 5e-6),
                  classify(0, 0, latency_s=1e-6),
                  classify(0, 0, collective_s=1e-6),
                  classify(1e-6, 0, mxu_utilization=0.1),
                  classify(1.0, 1.0)):
            assert d.bottleneck in BOTTLENECKS

    def test_wire_roundtrip_and_summary(self):
        d = classify(1e-6, 5e-6, mxu_utilization=0.8,
                     arithmetic_intensity=12.5, ci_rel=0.02)
        d2 = Diagnosis.from_dict(json.loads(json.dumps(d.to_dict())))
        assert d2 == d
        assert "memory" in d.summary()
        assert f"{ridge_flop_per_byte():.0f}" in d.summary()


class TestDiagnoseFeedback:
    def test_gemm_on_tpu_model_is_memory_bound_at_baseline(self):
        plat = TPUModelPlatform()
        case = get_case("gemm")
        fb = plat.profile_feedback(case, case.baseline_variant, 256)
        d = diagnose_feedback(fb)
        assert d.bottleneck == "memory"
        assert 0.0 < d.arithmetic_intensity < d.ridge_flop_per_byte

    def test_minimal_cpu_feedback_works(self):
        # only the minimal counter set: missing keys default neutral
        d = diagnose_feedback({"flops": 1e9, "traffic_bytes": 1e9,
                               "arithmetic_intensity": 1.0})
        assert d.bottleneck == "memory"
        assert d.mxu_utilization == 1.0

    def test_roofline_to_dict_carries_diagnosis(self):
        from repro.launch.roofline import Roofline
        rl = Roofline(flops_per_chip=1e12, bytes_per_chip=1e11,
                      collective_bytes_per_chip=0.0, n_chips=1,
                      model_flops_total=1e12)
        d = rl.to_dict()["diagnosis"]
        assert d["bottleneck"] in BOTTLENECKS
        assert rl.diagnose().bottleneck == d["bottleneck"]


# ---------------------------------------------------------------------------
# learned pattern ranking: suggested-but-never-winning patterns demote
# ---------------------------------------------------------------------------
def _seed_two_equal_patterns(store):
    """Two equal-gain matmul patterns with different deltas."""
    gemm, syrk = get_case("gemm"), get_case("syrk")
    base = dict(gemm.baseline_variant)
    store.record(gemm, "tpu-model", base,
                 dict(base, compute_dtype="bf16"), 2.0)
    base_s = dict(syrk.baseline_variant)
    store.record(syrk, "tpu-model", base_s,
                 dict(base_s, fuse_epilogue=True), 2.0)
    loser = next(p for p in store.patterns
                 if p.delta == {"compute_dtype": "bf16"})
    fresh = next(p for p in store.patterns
                 if p.delta == {"fuse_epilogue": True})
    return loser, fresh


class TestAcceptanceRanking:
    def test_repeated_loser_sorts_below_fresh_equal_gain(self):
        store = PatternStore()
        loser, fresh = _seed_two_equal_patterns(store)
        target = get_case("2mm")
        for _ in range(6):
            store.record_hint_outcome(target, "tpu-model", loser,
                                      won=False, bottleneck="memory")
        ranked = store.suggest_patterns(target, "tpu-model",
                                        bottleneck="memory")
        deltas = [p.delta for p in ranked]
        assert deltas.index({"fuse_epilogue": True}) \
            < deltas.index({"compute_dtype": "bf16"})
        n, w = store.acceptance({"compute_dtype": "bf16"}, "matmul",
                                "memory")
        assert (n, w) == (6, 0)

    def test_winning_pattern_recovers_rank(self):
        store = PatternStore()
        loser, fresh = _seed_two_equal_patterns(store)
        target = get_case("2mm")
        # the "loser" keeps landing in round winners, the other never does
        for _ in range(6):
            store.record_hint_outcome(target, "tpu-model", loser, won=True)
            store.record_hint_outcome(target, "tpu-model", fresh, won=False)
        ranked = store.suggest_patterns(target, "tpu-model")
        assert ranked[0].delta == {"compute_dtype": "bf16"}

    def test_acceptance_ledger_replays_from_journal(self, tmp_path):
        path = str(tmp_path / "pat.jsonl")
        store = PatternStore(path)
        loser, _ = _seed_two_equal_patterns(store)
        target = get_case("2mm")
        for won in (False, False, True):
            store.record_hint_outcome(target, "tpu-model", loser,
                                      won=won, bottleneck="memory")
        reopened = PatternStore(path)
        assert reopened.acceptance({"compute_dtype": "bf16"}, "matmul",
                                   "memory") == (3, 1)

    def test_acceptance_survives_compaction(self, tmp_path):
        path = str(tmp_path / "pat.jsonl")
        store = PatternStore(path)
        loser, _ = _seed_two_equal_patterns(store)
        target = get_case("2mm")
        # re-record the same two patterns repeatedly: the journal's
        # live/merged ratio crosses the compaction threshold
        for i in range(60):
            store.record_hint_outcome(target, "tpu-model", loser,
                                      won=i % 3 == 0, bottleneck="memory")
            _seed_two_equal_patterns(store)
        n, w = store.acceptance({"compute_dtype": "bf16"}, "matmul",
                                "memory")
        assert (n, w) == (60, 20)
        assert PatternStore(path).acceptance(
            {"compute_dtype": "bf16"}, "matmul", "memory") == (60, 20)

    def test_bottleneck_tag_on_recorded_patterns(self):
        store = PatternStore()
        gemm = get_case("gemm")
        base = dict(gemm.baseline_variant)
        store.record(gemm, "tpu-model", base,
                     dict(base, compute_dtype="bf16"), 2.0,
                     bottleneck="memory")
        assert store.patterns[0].bottleneck == "memory"
        d = store.patterns[0].to_dict()
        from repro.core.patterns import Pattern
        assert Pattern.from_dict(d).bottleneck == "memory"


# ---------------------------------------------------------------------------
# diagnosis-routed proposer vs the legacy threshold branches
# ---------------------------------------------------------------------------
class TestDiagnosisRouting:
    def _state(self, case, plat, diag):
        fb = plat.profile_feedback(case, case.baseline_variant, 256)
        return RoundState(round=1, baseline_variant=case.baseline_variant,
                          baseline_time_s=1e-3, feedback=fb,
                          diagnosis=diag)

    def test_memory_route_leads_with_combined_recipe(self):
        plat = TPUModelPlatform()
        case = get_case("gemm")
        fb = plat.profile_feedback(case, case.baseline_variant, 256)
        state = self._state(case, plat, diagnose_feedback(fb))
        cands = HeuristicProposer(0, platform="tpu-model").propose(
            case, state, 4)
        first = cands[0]
        assert first["compute_dtype"] == "bf16"
        assert first["fuse_epilogue"] is True
        assert first["block_m"] % 128 == 0

    def test_diagnose_false_reproduces_legacy_branches(self):
        plat = TPUModelPlatform()
        case = get_case("gemm")
        fb = plat.profile_feedback(case, case.baseline_variant, 256)
        legacy_state = self._state(case, plat, None)
        undiag = HeuristicProposer(0, platform="tpu-model",
                                   diagnose=False)
        diag_off = undiag.propose(
            case, self._state(case, plat, diagnose_feedback(fb)), 4)
        no_diag = HeuristicProposer(0, platform="tpu-model").propose(
            case, legacy_state, 4)
        # diagnose=False ignores the verdict; no diagnosis falls back —
        # both must emit the legacy move set
        assert diag_off == no_diag

    def test_spec_roundtrip_carries_diagnose_flag(self):
        from repro.core.proposer import proposer_from_spec
        p = HeuristicProposer(3, platform="tpu-model", diagnose=False)
        q = proposer_from_spec(p.to_spec())
        assert isinstance(q, HeuristicProposer) and q.diagnose is False


# ---------------------------------------------------------------------------
# LLM-reply validation: refusal / malformed / out-of-space → ProposalError
# ---------------------------------------------------------------------------
class TestLLMReplyValidation:
    def _proposer(self, monkeypatch, reply):
        p = LLMProposer(platform="tpu-model")
        monkeypatch.setattr(p, "_round_text", lambda prompt: reply)
        monkeypatch.setattr(p, "_chat", lambda prompt: reply)
        return p

    def _state(self, case):
        return RoundState(round=0, baseline_variant=case.baseline_variant,
                          baseline_time_s=1e-3, feedback={}, hints=[])

    def test_refusal_shaped_reply_raises(self, monkeypatch):
        case = get_case("gemm")
        p = self._proposer(monkeypatch, "I can't help with that.")
        with pytest.raises(ProposalError, match="refusal"):
            p.propose(case, self._state(case), 2)

    def test_malformed_json_raises(self, monkeypatch):
        case = get_case("gemm")
        p = self._proposer(monkeypatch, '[{"block_m": 64,]')
        with pytest.raises(ProposalError, match="malformed"):
            p.propose(case, self._state(case), 2)

    def test_out_of_space_value_raises(self, monkeypatch):
        case = get_case("gemm")
        p = self._proposer(monkeypatch, '[{"block_m": 999}]')
        with pytest.raises(ProposalError, match="outside"):
            p.propose(case, self._state(case), 2)

    def test_valid_reply_merges_onto_baseline(self, monkeypatch):
        case = get_case("gemm")
        p = self._proposer(
            monkeypatch,
            'Sure: [{"block_m": 128, "compute_dtype": "bf16"}]')
        (v,) = p.propose(case, self._state(case), 1)
        assert v["block_m"] == 128 and v["compute_dtype"] == "bf16"
        assert v["block_n"] == case.baseline_variant["block_n"]

    def test_repair_defers_to_aer_on_garbage(self, monkeypatch):
        case = get_case("gemm")
        p = self._proposer(monkeypatch, "cannot fix, sorry")
        assert p.repair(case, dict(case.baseline_variant),
                        "RuntimeError: boom") is None

    def test_repair_applies_valid_fix(self, monkeypatch):
        case = get_case("gemm")
        p = self._proposer(monkeypatch, 'try {"block_k": 64} instead')
        v = p.repair(case, dict(case.baseline_variant),
                     "RuntimeError: boom")
        assert v["block_k"] == 64

    def test_json_span_and_validated_helpers(self):
        assert _json_span('x [1, 2] y', "[", "]", what="list") == [1, 2]
        with pytest.raises(ProposalError):
            _json_span("no json here", "{", "}", what="dict")
        case = get_case("gemm")
        out = _validated(case, {"block_m": 64, "unknown_knob": 7})
        assert out == {"block_m": 64}      # unknown keys still dropped


# ---------------------------------------------------------------------------
# end-to-end: diagnosis + hint evidence through the search loop journals
# ---------------------------------------------------------------------------
class TestJournaledEvidence:
    def test_round_records_carry_diagnosis_and_hint_outcomes(self, tmp_path):
        from repro.core.evalcache import ResultsDB
        from repro.core.mep import MEPConstraints
        from repro.core.optimizer import OptConfig, OptResult
        from repro.core.workers import CaseJob, run_case_job

        store = PatternStore(str(tmp_path / "pat.jsonl"))
        db = ResultsDB(str(tmp_path / "db.jsonl"))
        plat = TPUModelPlatform()
        cfg = OptConfig(d_rounds=3, n_candidates=2, r=3, k=1)
        cons = MEPConstraints(r=3, k=1, t_max_s=2.0)
        for name in ("gemm", "2mm"):
            run_case_job(
                CaseJob(get_case(name),
                        HeuristicProposer(0, platform="tpu-model"),
                        cfg=cfg, constraints=cons),
                plat, campaign_id="t", patterns=store, db=db)

        rounds = list(db.records("round"))
        assert rounds and all(r["diagnosis"]["bottleneck"] in BOTTLENECKS
                              for r in rounds)
        hints = [h for r in rounds for h in r.get("ppi_hints", [])]
        assert hints, "second case must inherit hints from the first"
        for h in hints:
            assert {"delta", "bottleneck", "accepted", "pid",
                    "ns"} <= set(h)
        assert any(h["accepted"] for h in hints)

        # the same evidence must survive the OptResult wire form
        res = run_case_job(
            CaseJob(get_case("atax"),
                    HeuristicProposer(0, platform="tpu-model"),
                    cfg=cfg, constraints=cons),
            plat, patterns=store)
        rt = OptResult.from_dict(
            json.loads(json.dumps(res.to_dict(full=True))))
        assert rt.hints_suggested == res.hints_suggested
        assert rt.rounds[0].diagnosis is not None
