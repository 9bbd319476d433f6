"""Campaign engine: evalcache semantics, concurrency safety, results DB,
campaign-vs-serial equivalence, and the uniform early-stop rule."""
import json
import threading

import pytest

from repro.core import (Campaign, CaseJob, CPUPlatform, EvalCache,
                        EvalRecord, HeuristicProposer, MEPConstraints,
                        OptConfig, PatternStore, ResultsDB,
                        TPUModelPlatform, canonical_spec, get_case, optimize)
from repro.core.kernelcase import ArraySpec, KernelCase
from repro.core.proposer import Proposer

FAST = MEPConstraints(t_max_s=2.0, r=5, k=1)
FAST_CFG = OptConfig(d_rounds=2, n_candidates=2, r=5, k=1)


# ------------------------------------------------------------ evalcache ---
def test_evalcache_hit_miss_and_persistence(tmp_path):
    path = str(tmp_path / "ec.jsonl")
    cache = EvalCache(path)
    spec = canonical_spec("gemm", {"block_m": 128}, 256, "tpu-v5e-model",
                          r=5, k=1)
    calls = []

    def compute():
        calls.append(1)
        return EvalRecord(status="ok", time_s=1.5,
                          final_variant={"block_m": 128})

    rec, hit = cache.get_or_compute(spec, compute)
    assert not hit and rec.time_s == 1.5 and len(calls) == 1
    rec2, hit2 = cache.get_or_compute(spec, compute)
    assert hit2 and rec2.time_s == 1.5 and len(calls) == 1
    assert cache.stats() == {"hits": 1, "misses": 1, "waits": 0,
                             "stale": 0, "entries": 1}
    # key order in the variant dict must not matter
    spec_perm = canonical_spec("gemm", {"block_m": 128}, 256,
                               "tpu-v5e-model", k=1, r=5)
    _, hit3 = cache.get_or_compute(spec_perm, compute)
    assert hit3 and len(calls) == 1
    # any spec component change is a different entry
    for other in (canonical_spec("gemm", {"block_m": 128}, 512,
                                 "tpu-v5e-model", r=5, k=1),
                  canonical_spec("gemm", {"block_m": 64}, 256,
                                 "tpu-v5e-model", r=5, k=1),
                  canonical_spec("syrk", {"block_m": 128}, 256,
                                 "tpu-v5e-model", r=5, k=1),
                  canonical_spec("gemm", {"block_m": 128}, 256, "cpu",
                                 r=5, k=1)):
        assert cache.lookup(other) is None
    # persistence: a fresh cache over the same file answers from disk
    cache2 = EvalCache(path)
    rec4, hit4 = cache2.get_or_compute(spec, compute)
    assert hit4 and rec4.time_s == 1.5 and len(calls) == 1


def test_evalcache_inflight_dedup():
    """Two workers racing on the same key compute it exactly once."""
    cache = EvalCache()
    spec = canonical_spec("gemm", {"block_m": 64}, 256, "tpu-v5e-model")
    gate = threading.Event()
    calls = []

    def compute():
        calls.append(1)
        gate.wait(timeout=5)
        return EvalRecord(status="ok", time_s=2.0)

    out = []
    threads = [threading.Thread(
        target=lambda: out.append(cache.get_or_compute(spec, compute)))
        for _ in range(4)]
    for t in threads:
        t.start()
    gate.set()
    for t in threads:
        t.join()
    assert len(calls) == 1
    assert all(rec.time_s == 2.0 for rec, _ in out)


def test_results_db_roundtrip(tmp_path):
    db = ResultsDB(str(tmp_path / "campaign.jsonl"))
    camp = Campaign(TPUModelPlatform(), cache=EvalCache(), db=db)
    camp.run([CaseJob(get_case("gemm"), HeuristicProposer(0),
                      cfg=FAST_CFG, constraints=FAST)])
    kinds = [r["kind"] for r in db.records()]
    assert kinds[0] == "campaign_start" and kinds[-1] == "campaign_end"
    assert "round" in kinds and "case_result" in kinds
    case_res = next(db.records("case_result"))
    assert case_res["case"] == "gemm" and case_res["speedup"] >= 1.0


# ------------------------------------------------------------- campaign ---
def test_results_db_concurrent_writers(tmp_path):
    """Two threads journaling interleaved campaigns: every line stays
    valid JSON and no record is lost (extends the torn-line skip test —
    torn lines must come only from crashes, never from interleaving)."""
    db = ResultsDB(str(tmp_path / "campaign.jsonl"))
    n = 200

    def journal(writer):
        for i in range(n):
            db.append("round", writer=writer, i=i,
                      candidates=[{"variant": {"block_m": 64}, "time_s": 1.0}])

    threads = [threading.Thread(target=journal, args=(w,)) for w in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with open(db.path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    assert len(records) == 2 * n
    for w in range(2):
        assert sorted(r["i"] for r in records if r["writer"] == w) \
            == list(range(n))


# ------------------------------------------------- cache invalidation ----
def _toy_case(build):
    return KernelCase(
        name="digest_toy", suite="hpc", family="elementwise",
        ref=lambda x: x * 2.0, build=build,
        input_specs=lambda s: [ArraySpec((s,), "float32")],
        variant_space={"mul": [2.0]}, baseline_variant={"mul": 2.0},
        flops=lambda s: float(s), scales=(64,))


def _build_v1(variant, impl="jnp"):
    m = variant["mul"]
    return lambda x: x * m


def _build_v2(variant, impl="jnp"):      # the "edited" kernel source:
    m = variant["mul"]                   # same semantics, different code
    return lambda x: x * m + 0.0


def test_evalcache_source_digest_invalidation():
    """Editing a case's build source must invalidate its cached timings:
    the spec key carries a per-case source digest, so the mutated case
    misses instead of replaying the old kernel's numbers."""
    case_v1, case_v2 = _toy_case(_build_v1), _toy_case(_build_v2)
    assert case_v1.source_digest() != case_v2.source_digest()
    # a case derived via dataclasses.replace re-derives its digest rather
    # than inheriting the stale cached one
    import dataclasses
    derived = dataclasses.replace(case_v1, build=_build_v2)
    assert derived.source_digest() == case_v2.source_digest()
    cache = EvalCache()
    plat = TPUModelPlatform()
    r1 = optimize(case_v1, plat, HeuristicProposer(0), cfg=FAST_CFG,
                  constraints=FAST, cache=cache)
    assert r1.cache_misses >= 1 and r1.cache_hits == 0
    # unchanged source: everything replays from cache
    r1b = optimize(case_v1, plat, HeuristicProposer(0), cfg=FAST_CFG,
                   constraints=FAST, cache=cache)
    assert r1b.cache_misses == 0 and r1b.cache_hits >= 1
    # mutated source, same case name/variant/scale: cache miss
    r2 = optimize(case_v2, plat, HeuristicProposer(0), cfg=FAST_CFG,
                  constraints=FAST, cache=cache)
    assert r2.cache_misses >= 1 and r2.cache_hits == 0


# --------------------------------------------------------- stop event ----
def test_campaign_stop_event_interrupts_at_round_boundary():
    stop = threading.Event()
    stop.set()
    camp = Campaign(TPUModelPlatform(), cache=EvalCache())
    res = camp.run([CaseJob(get_case("gemm"), HeuristicProposer(0),
                            cfg=FAST_CFG, constraints=FAST)], stop=stop)[0]
    assert res.stop_reason == "stop requested"
    assert res.rounds == []
    assert res.best_variant == dict(get_case("gemm").baseline_variant)
    assert res.speedup == pytest.approx(1.0)


def test_campaign_equals_serial_fixed_seed():
    """Same best variant and time as the serial optimize() path, for a
    fixed seed, on a deterministic (analytic) platform."""
    plat = TPUModelPlatform()
    kernels = [get_case("gemm"), get_case("syrk")]
    serial = [optimize(c, plat, HeuristicProposer(0), cfg=FAST_CFG,
                       constraints=FAST) for c in kernels]
    camp = Campaign(TPUModelPlatform(), cache=EvalCache(), max_workers=2)
    conc = camp.run([CaseJob(c, HeuristicProposer(0), cfg=FAST_CFG,
                             constraints=FAST) for c in kernels])
    for s, c in zip(serial, conc):
        assert s.best_variant == c.best_variant
        assert s.best_time_s == pytest.approx(c.best_time_s, rel=1e-12)
        assert s.baseline_time_s == pytest.approx(c.baseline_time_s,
                                                  rel=1e-12)


def test_campaign_cache_survives_restart(tmp_path):
    path = str(tmp_path / "ec.jsonl")

    def run_once():
        cache = EvalCache(path)
        camp = Campaign(TPUModelPlatform(), cache=cache)
        res = camp.run([CaseJob(get_case("gemm"), HeuristicProposer(0),
                                cfg=FAST_CFG, constraints=FAST)])[0]
        return res, cache

    r1, c1 = run_once()
    assert c1.stats()["hits"] == 0
    r2, c2 = run_once()        # fresh cache object, same file: all hits
    assert r2.best_variant == r1.best_variant
    assert r2.best_time_s == r1.best_time_s
    assert c2.stats()["misses"] == 0
    assert c2.stats()["hits"] >= 1 and r2.cache_hits >= 1


def test_campaign_dedups_mep_and_shares_cache_across_jobs():
    """Two jobs on the same case (heuristic + direct) share the MEP and
    at least the baseline measurement comes from cache."""
    from repro.core import DirectProposer
    cache = EvalCache()
    camp = Campaign(TPUModelPlatform(), cache=cache, max_workers=1)
    case = get_case("gemm")
    res_h, res_d = camp.run([
        CaseJob(case, HeuristicProposer(0), cfg=FAST_CFG, constraints=FAST),
        CaseJob(case, DirectProposer(),
                cfg=OptConfig(d_rounds=1, n_candidates=1, r=5, k=1),
                constraints=FAST, label="gemm#direct"),
    ])
    assert len(camp.executor._meps) == 1   # one MEP built for both jobs
    assert res_d.cache_hits >= 1         # baseline re-measure was a hit
    assert res_d.baseline_time_s == res_h.baseline_time_s


# ------------------------------------------------- concurrency safety ----
def test_concurrent_pattern_store_record(tmp_path):
    store = PatternStore(str(tmp_path / "pat.jsonl"))
    case = get_case("gemm")
    base = dict(case.baseline_variant)

    def work(i):
        # identical delta from every thread → must merge, not duplicate
        store.record(case, "cpu", base, dict(base, block_m=128),
                     gain=2.0 + (i % 3) * 0.1)
        # distinct per-thread delta → one entry each
        store.record(case, "cpu", base, dict(base, block_n=64 + i), gain=1.5)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    same = [p for p in store.patterns if p.delta == {"block_m": 128}]
    assert len(same) == 1
    assert same[0].gain == pytest.approx(2.2)     # best observed gain kept
    distinct = [p for p in store.patterns if "block_n" in p.delta]
    assert len(distinct) == 8
    with open(store.path) as f:       # every journal line stayed valid JSON
        lines = [json.loads(line) for line in f if line.strip()]
    assert lines                      # replaying the journal merges back
    assert len(PatternStore(store.path)) == len(store.patterns)


def test_cpu_platform_compiled_cache_is_bounded():
    plat = CPUPlatform(max_cache=2)
    case = get_case("vectoradd")
    variants = [dict(case.baseline_variant, block=b)
                for b in case.variant_space["block"]]
    assert len(variants) >= 3
    for v in variants:
        plat._compiled(case, v)
    assert len(plat._cache) == 2
    # most-recently-used stays, oldest was evicted
    key_last = (case.name, tuple(sorted(variants[-1].items())))
    key_first = (case.name, tuple(sorted(variants[0].items())))
    assert key_last in plat._cache and key_first not in plat._cache


def test_measured_platform_fans_out_under_timing_lease(tmp_path):
    """The one-worker clamp for measured platforms is gone: wall-clock
    slices serialize on the campaign's timing lease instead.  The lease
    lives next to the eval cache when there is one, else in a
    campaign-scoped temp file; analytic platforms need none."""
    assert Campaign(CPUPlatform()).max_workers > 1
    assert Campaign(TPUModelPlatform()).max_workers > 1
    assert Campaign(CPUPlatform(), max_workers=3).max_workers == 3
    cache = EvalCache(str(tmp_path / "ec.jsonl"))
    assert Campaign(CPUPlatform(), cache=cache).lease_path \
        == cache.path + ".timelease"
    assert Campaign(CPUPlatform()).lease_path            # tempdir fallback
    assert Campaign(TPUModelPlatform()).lease_path is None


# ------------------------------------------------------- early stopping ---
class _NullProposer(Proposer):
    name = "null"

    def propose(self, case, state, n):
        return []


def test_early_stop_round_zero_no_feasible():
    """A round with zero feasible candidates stops the loop immediately —
    even at round 0 — with the reason logged."""
    res = optimize(get_case("gemm"), TPUModelPlatform(), _NullProposer(),
                   cfg=OptConfig(d_rounds=4, n_candidates=2, r=5, k=1),
                   constraints=FAST)
    assert len(res.rounds) == 1
    assert "no feasible" in res.stop_reason
    assert res.rounds[0].stop_reason == res.stop_reason
    assert any("stopped" in line for line in res.mep_log)
    assert res.best_variant == dict(get_case("gemm").baseline_variant)
    assert res.speedup == pytest.approx(1.0)


class _BoomProposer(Proposer):
    name = "boom"

    def propose(self, case, state, n):
        raise RuntimeError("proposer exploded")


def test_failed_job_does_not_discard_others(tmp_path):
    """One failing job still lets every other job finish, the journal
    gets campaign_end (with the error recorded), and only then does
    run() raise."""
    db = ResultsDB(str(tmp_path / "c.jsonl"))
    camp = Campaign(TPUModelPlatform(), cache=EvalCache(), db=db,
                    max_workers=2)
    jobs = [CaseJob(get_case("gemm"), HeuristicProposer(0), cfg=FAST_CFG,
                    constraints=FAST),
            CaseJob(get_case("syrk"), _BoomProposer(), cfg=FAST_CFG,
                    constraints=FAST)]
    with pytest.raises(RuntimeError, match="campaign job 'syrk' failed"):
        camp.run(jobs)
    end = next(db.records("campaign_end"))
    assert [r["case"] for r in end["results"]] == ["gemm"]
    assert end["errors"][0]["job"] == "syrk"
    assert "proposer exploded" in end["errors"][0]["error"]


def test_journal_is_strict_json_with_inf_times(tmp_path):
    """Failed candidates carry time_s=inf; the JSONL journal and cache
    files must still be strict (RFC-8259) JSON on every line — plain
    json.dumps would emit the non-standard token ``Infinity``."""
    db = ResultsDB(str(tmp_path / "c.jsonl"))
    db.append("round", best_time_s=float("inf"),
              candidates=[{"time_s": float("inf"), "status": "fe_fail"}])
    cache = EvalCache(str(tmp_path / "ec.jsonl"))
    spec = canonical_spec("gemm", {"block_m": 7}, 256, "tpu-v5e-model")
    cache.get_or_compute(spec, lambda: EvalRecord(status="build_error"))
    for path in (db.path, cache.path):
        with open(path) as f:
            for line in f:
                json.loads(line, parse_constant=lambda c: pytest.fail(
                    f"non-standard JSON constant {c!r} in {path}"))
    # the failed record's inf time round-trips via None-on-disk
    rec = EvalCache(cache.path).lookup(spec)
    assert rec.status == "build_error" and rec.time_s == float("inf")


class _EchoProposer(Proposer):
    """Re-proposes the baseline's twin: never any improvement."""
    name = "echo"

    def propose(self, case, state, n):
        return [dict(state.baseline_variant)]


def test_early_stop_round_zero_tie():
    """A round whose winner merely ties the baseline stops at round 0
    (the seed looped on, re-evaluating hopeless rounds)."""
    res = optimize(get_case("gemm"), TPUModelPlatform(), _EchoProposer(),
                   cfg=OptConfig(d_rounds=5, n_candidates=1, r=5, k=1),
                   constraints=FAST)
    assert len(res.rounds) == 1
    assert "did not beat" in res.stop_reason
