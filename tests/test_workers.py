"""Worker fabric: wire round-trips, subprocess-executor equivalence,
fault paths (crash / timeout / replacement), cross-process cache dedup,
measured-cache namespace+TTL staleness, multi-process journal appends,
and LLM round-prompt coalescing.

Run standalone (the CI ``test-workers`` job):

    REPRO_CAMPAIGN_WORKERS=2 PYTHONPATH=src python -m pytest -q tests/test_workers.py
"""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from repro.core import (Campaign, CaseJob, CPUPlatform, EvalCache,
                        EvalRecord, HeuristicProposer, InProcessExecutor,
                        LLMBatcher, LLMProposer, LocalClusterExecutor,
                        MEPConstraints, OptConfig, OptResult, ResultsDB,
                        SubprocessExecutor, TPUModelPlatform, WorkerContext,
                        WorkerFault, canonical_spec, get_case, optimize,
                        platform_from_name)
from repro.core.kernelcase import KernelCase
from repro.core.proposer import Proposer
from repro.core.workers import job_from_spec, job_to_spec

FAST = MEPConstraints(t_max_s=2.0, r=5, k=1)
FAST_CFG = OptConfig(d_rounds=2, n_candidates=2, r=5, k=1)


def _ctx(platform=None, **kw):
    return WorkerContext(platform=platform or TPUModelPlatform(), **kw)


def _job(case="gemm", seed=0, label=""):
    return CaseJob(get_case(case), HeuristicProposer(seed), cfg=FAST_CFG,
                   constraints=FAST, seed=seed, label=label)


# ------------------------------------------------------------- wire form --
def test_spawned_workers_run_on_the_cpu(monkeypatch):
    """A chip belongs to one process: workers never reach for it, even
    when the parent that spawns them runs on the TPU."""
    from repro.core.workers import _worker_env
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = _worker_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PYTHONPATH"].split(os.pathsep)[0].endswith("src")


def test_platform_registry_roundtrip():
    assert platform_from_name("tpu-v5e-model").name == "tpu-v5e-model"
    assert platform_from_name("cpu").name == "cpu"
    with pytest.raises(KeyError, match="unknown platform"):
        platform_from_name("dcu-z100")


def test_kernelcase_wire_roundtrip_checks_digest():
    case = get_case("gemm")
    d = case.to_dict()
    assert KernelCase.from_dict(d) is case
    d["digest"] = "deadbeefdead"
    with pytest.raises(ValueError, match="digest mismatch"):
        KernelCase.from_dict(d)


def test_job_spec_roundtrip(tmp_path):
    cache = EvalCache(str(tmp_path / "ec.jsonl"), namespace="nsA",
                      ttl_s=123.0)
    db = ResultsDB(str(tmp_path / "db.jsonl"))
    ctx = _ctx(cache=cache, db=db)
    job = _job(seed=7, label="gemm#x")
    spec = job_to_spec(job, ctx, "c0")
    # the spec is pure JSON — it must survive the pipe byte-for-byte
    spec = json.loads(json.dumps(spec))
    back, scale = job_from_spec(spec)
    assert back.case is job.case
    assert back.proposer.seed == 7 and back.proposer.name == "heuristic"
    assert back.cfg == job.cfg and back.constraints == job.constraints
    assert back.seed == 7 and back.label == "gemm#x" and scale is None
    assert spec["cache"] == {"path": cache.path, "ns": "nsA",
                             "ttl_s": 123.0}
    assert spec["db"] == db.path


def test_optresult_wire_roundtrip():
    res = optimize(get_case("gemm"), TPUModelPlatform(),
                   HeuristicProposer(0), cfg=FAST_CFG, constraints=FAST)
    d = json.loads(json.dumps(res.to_dict(full=True), default=str))
    back = OptResult.from_dict(d)
    assert back.best_variant == res.best_variant
    assert back.best_time_s == res.best_time_s
    assert back.stop_reason == res.stop_reason
    assert len(back.rounds) == len(res.rounds)
    assert [c.variant for c in back.rounds[0].candidates] \
        == [c.variant for c in res.rounds[0].candidates]


class _CustomProposer(Proposer):
    name = "custom"

    def propose(self, case, state, n):
        return []


def test_non_wire_safe_job_fails_before_spawn():
    job = CaseJob(get_case("gemm"), _CustomProposer(), cfg=FAST_CFG,
                  constraints=FAST)
    with pytest.raises(TypeError, match="not wire-safe"):
        SubprocessExecutor(2).run([job], _ctx(), campaign_id="c0")


def test_in_memory_cache_rejected_for_subprocess():
    with pytest.raises(ValueError, match="file-backed"):
        SubprocessExecutor(2).run([_job()], _ctx(cache=EvalCache()),
                                  campaign_id="c0")


# ----------------------------------------------------------- equivalence --
def test_subprocess_matches_inprocess(tmp_path):
    plat = TPUModelPlatform()
    jobs = [_job("gemm"), _job("syrk")]
    ref = Campaign(plat, cache=EvalCache(str(tmp_path / "a.jsonl")),
                   executor=InProcessExecutor(2)).run(
        [_job("gemm"), _job("syrk")])
    sub = Campaign(plat, cache=EvalCache(str(tmp_path / "b.jsonl")),
                   executor=SubprocessExecutor(2)).run(jobs)
    for r, s in zip(ref, sub):
        assert s.best_variant == r.best_variant
        assert s.best_time_s == pytest.approx(r.best_time_s, rel=1e-12)
        assert s.stop_reason == r.stop_reason
        assert len(s.rounds) == len(r.rounds)


def test_subprocess_stop_event_pre_set(tmp_path):
    stop = threading.Event()
    stop.set()
    camp = Campaign(TPUModelPlatform(),
                    cache=EvalCache(str(tmp_path / "ec.jsonl")),
                    executor=SubprocessExecutor(1))
    res = camp.run([_job()], stop=stop)[0]
    assert res.stop_reason == "stop requested"
    assert res.rounds == []


# ----------------------------------------------------------- fault paths --
def test_worker_crash_mid_eval_replaced_and_retried(tmp_path):
    """First attempt crashes the worker process; the executor journals
    the fault, replaces the worker, and the retry on the fresh process
    succeeds."""
    db = ResultsDB(str(tmp_path / "db.jsonl"))
    job = _job()
    job.inject = {"crash_once_flag": str(tmp_path / "crashed.flag")}
    ex = SubprocessExecutor(1, retries=1)
    out = ex.run([job], _ctx(cache=EvalCache(str(tmp_path / "ec.jsonl")),
                             db=db), campaign_id="c0")
    assert isinstance(out[0], OptResult) and out[0].speedup >= 1.0
    assert os.path.exists(str(tmp_path / "crashed.flag"))
    faults = list(db.records("worker_fault"))
    assert len(faults) == 1
    assert faults[0]["fault"] == "crash" and faults[0]["job"] == "gemm"
    assert [j for j, _ in ex.dispatch_log] == ["gemm", "gemm"]


def test_worker_crash_exhausts_retries_raises_workerfault(tmp_path):
    db = ResultsDB(str(tmp_path / "db.jsonl"))
    job = _job()
    job.inject = {"crash": True, "exit_code": 43}
    camp = Campaign(TPUModelPlatform(), db=db,
                    cache=EvalCache(str(tmp_path / "ec.jsonl")),
                    executor=SubprocessExecutor(1, retries=1))
    with pytest.raises(RuntimeError, match="campaign job 'gemm' failed"):
        camp.run([job])
    # both attempts journaled, campaign_end still written with the error
    assert [f["fault"] for f in db.records("worker_fault")] \
        == ["crash", "crash"]
    end = next(db.records("campaign_end"))
    assert "WorkerFault" in end["errors"][0]["error"]


def test_worker_timeout_is_a_workerfault(tmp_path):
    job = _job()
    job.inject = {"sleep_s": 60}
    ex = SubprocessExecutor(1, timeout_s=3.0, retries=0)
    out = ex.run([job], _ctx(cache=EvalCache(str(tmp_path / "ec.jsonl"))),
                 campaign_id="c0")
    assert isinstance(out[0], WorkerFault)
    assert out[0].kind == "timeout" and out[0].attempts == 1


# ------------------------------------------- cross-process cache dedup ---
def test_two_workers_racing_same_key_compute_once(tmp_path):
    """Two subprocess workers evaluating identical jobs (same case, same
    seed, different labels) race on every cache key; the per-key lock
    file must keep in-flight dedup intact across processes: each key is
    computed (and appended) exactly once."""
    cache_path = str(tmp_path / "ec.jsonl")
    camp = Campaign(TPUModelPlatform(), cache=EvalCache(cache_path),
                    executor=SubprocessExecutor(2))
    r1, r2 = camp.run([_job(label="gemm#a"), _job(label="gemm#b")])
    assert r1.best_variant == r2.best_variant
    with open(cache_path) as f:
        keys = [json.loads(line)["key"] for line in f if line.strip()]
    assert len(keys) == len(set(keys)), "a cache key was computed twice"
    assert len(keys) >= 3
    # the lock files of the computed keys stay behind (never unlinked)
    assert os.path.isdir(cache_path + ".locks")


HELPER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_evalcache_proc.py")


def test_get_or_compute_cross_process_lock(tmp_path):
    """Direct cross-process in-flight dedup: two separate processes call
    get_or_compute on the same key with a slow compute; the flock file
    must let exactly one compute run."""
    cache_path = str(tmp_path / "ec.jsonl")
    side = str(tmp_path / "computed.log")
    spec = canonical_spec("gemm", {"block_m": 64}, 256, "tpu-v5e-model",
                          r=5, k=1)
    procs = [subprocess.Popen([sys.executable, HELPER, "race",
                               cache_path, side]) for _ in range(2)]
    for p in procs:
        assert p.wait(timeout=60) == 0
    with open(side) as f:
        assert len(f.readlines()) == 1, "both processes computed the key"
    assert EvalCache(cache_path).lookup(spec).time_s == 2.5


# ------------------------------------------------- namespace + TTL -------
def test_measured_cache_namespace_rejection(tmp_path):
    path = str(tmp_path / "ec.jsonl")
    spec = canonical_spec("gemm", {"block_m": 64}, 256, "cpu", r=5, k=1)
    a = EvalCache(path, namespace="hostA:x86")
    a.get_or_compute(spec, lambda: EvalRecord(status="ok", time_s=1.0),
                     measured=True)
    # same namespace: replays
    assert EvalCache(path, namespace="hostA:x86").lookup(spec).time_s == 1.0
    # different namespace (another host / machine conditions): stale
    b = EvalCache(path, namespace="hostB:arm")
    assert b.lookup(spec) is None
    assert b.stats()["stale"] == 1
    # a stale hit falls through to recompute and re-publishes under the
    # new namespace
    rec, hit = b.get_or_compute(
        spec, lambda: EvalRecord(status="ok", time_s=2.0), measured=True)
    assert not hit and rec.time_s == 2.0
    assert EvalCache(path, namespace="hostB:arm").lookup(spec).time_s == 2.0


def test_measured_cache_ttl_expiry(tmp_path):
    path = str(tmp_path / "ec.jsonl")
    spec = canonical_spec("gemm", {"block_m": 64}, 256, "cpu", r=5, k=1)
    ns = "hostA:x86"
    EvalCache(path, namespace=ns).get_or_compute(
        spec, lambda: EvalRecord(status="ok", time_s=1.0), measured=True)
    fresh = EvalCache(path, namespace=ns, ttl_s=30.0)
    assert fresh.lookup(spec).time_s == 1.0
    time.sleep(0.15)
    expired = EvalCache(path, namespace=ns, ttl_s=0.1)
    assert expired.lookup(spec) is None
    assert expired.stats()["stale"] == 1


def test_analytic_records_immune_to_namespace_and_ttl(tmp_path):
    path = str(tmp_path / "ec.jsonl")
    spec = canonical_spec("gemm", {"block_m": 64}, 256, "tpu-v5e-model",
                          r=5, k=1)
    EvalCache(path, namespace="hostA").get_or_compute(
        spec, lambda: EvalRecord(status="ok", time_s=1.0))   # analytic
    time.sleep(0.15)
    c = EvalCache(path, namespace="hostB", ttl_s=0.1)
    assert c.lookup(spec).time_s == 1.0
    assert c.stats()["stale"] == 0


def test_ttl_env_default(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_TTL_S", "456.5")
    assert EvalCache(str(tmp_path / "e.jsonl")).ttl_s == 456.5
    monkeypatch.delenv("REPRO_CACHE_TTL_S")
    assert EvalCache(str(tmp_path / "e2.jsonl")).ttl_s is None


# ------------------------------------------------ multi-process journal --
def test_results_db_multiprocess_writers_no_torn_lines(tmp_path):
    """N separate processes appending concurrently: every line stays
    valid JSON and no record is lost (O_APPEND single-write atomicity —
    the fix for interleaved partial JSONL lines)."""
    db_path = str(tmp_path / "db.jsonl")
    n, writers = 200, 4
    procs = [subprocess.Popen([sys.executable, HELPER, "append",
                               db_path, str(w), str(n)])
             for w in range(writers)]
    for p in procs:
        assert p.wait(timeout=60) == 0
    with open(db_path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    assert len(records) == writers * n
    for w in range(writers):
        assert sorted(r["i"] for r in records if r["writer"] == w) \
            == list(range(n))


def test_measured_platform_fans_out_with_lease():
    """Measured platforms are no longer pinned to one exclusive slot:
    the cross-process timing lease serializes wall-clock slices, so the
    routing fans them out exactly like analytic platforms — and every
    measured spec must carry a lease path for the workers to share."""
    ex = SubprocessExecutor(3)
    assert ex._slots_for(_ctx(CPUPlatform()), 8) == [0, 1, 2]
    assert ex._slots_for(_ctx(TPUModelPlatform()), 8) == [0, 1, 2]
    # a measured spec always carries a lease, even cache-less (the
    # executor derives a campaign-scoped fallback path)
    spec = job_to_spec(_job(), _ctx(CPUPlatform()), "c-lease")
    assert spec["lease"] and "c-lease" in spec["lease"]
    # cache-backed context → the lease lives next to the cache file
    import tempfile as _tf
    with _tf.TemporaryDirectory() as d:
        cache = EvalCache(os.path.join(d, "ec.jsonl"))
        spec = job_to_spec(_job(), _ctx(CPUPlatform(), cache=cache), "c1")
        assert spec["lease"] == cache.path + ".timelease"
    # analytic platforms need no lease
    spec = job_to_spec(_job(), _ctx(TPUModelPlatform()), "c2")
    assert spec["lease"] is None


# ------------------------------------------------- local cluster ---------
def test_local_cluster_fans_out_measured_and_analytic():
    ex = LocalClusterExecutor(4)
    assert ex._slots_for(_ctx(TPUModelPlatform()), 8) == [0, 1, 2, 3]
    # pinning deleted: measured platforms use the same general slots
    assert ex._slots_for(_ctx(CPUPlatform()), 8) == [0, 1, 2, 3]
    ex.close()


def test_local_cluster_persists_workers_across_runs(tmp_path):
    ex = LocalClusterExecutor(2)
    try:
        ctx = _ctx(cache=EvalCache(str(tmp_path / "ec.jsonl")))
        out1 = ex.run([_job("gemm")], ctx, campaign_id="c1")
        procs1 = dict(ex._procs)
        out2 = ex.run([_job("syrk")], ctx, campaign_id="c2")
        assert isinstance(out1[0], OptResult)
        assert isinstance(out2[0], OptResult)
        # same worker process served both campaigns (persistent fabric)
        assert ex._procs[0] is procs1[0]
        assert ex._procs[0].alive()
    finally:
        ex.close()
    assert not any(w.alive() for w in procs1.values())


# --------------------------------------------------- LLM coalescing ------
def test_llm_batcher_one_endpoint_call_per_batch():
    calls = []

    def transport(prompt):
        calls.append(prompt)
        ids = [ln.split()[-1] for ln in prompt.splitlines()
               if ln.startswith("### ")]
        if not ids:                      # single-item batch: raw prompt
            return json.dumps([{"block_m": 64}])
        return json.dumps({i: [{"block_m": 64}] for i in ids})

    batcher = LLMBatcher(transport, max_batch=8, linger_s=5.0)
    for _ in range(3):
        batcher.register()
    out = [None] * 3
    threads = [threading.Thread(
        target=lambda i=i: out.__setitem__(
            i, batcher.submit(f"optimize kernel {i}")))
        for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert len(calls) == 1, "coalesced batch must make ONE endpoint call"
    assert batcher.calls == 1 and batcher.coalesced == 3
    for text in out:
        assert json.loads(text) == [{"block_m": 64}]
    # a single registered participant dispatches immediately (no linger)
    for _ in range(3):
        batcher.unregister()
    batcher.register()
    t0 = time.time()
    assert json.loads(batcher.submit("solo"))
    assert time.time() - t0 < 2.0
    assert len(calls) == 2


def test_campaign_coalesces_llm_round_prompts():
    """An in-process campaign over concurrent LLM-proposer cases makes
    one endpoint call per round wave, not one per case."""
    calls = []

    def transport(prompt):
        calls.append(prompt)
        ids = [ln.split()[-1] for ln in prompt.splitlines()
               if ln.startswith("### ")]
        if not ids:                      # single-item batch: raw prompt
            return json.dumps([{"block_m": 256}])
        return json.dumps({i: [{"block_m": 256}] for i in ids})

    cases = ["gemm", "syrk", "syr2k"]
    jobs = []
    proposers = []
    for name in cases:
        p = LLMProposer()
        proposers.append(p)
        jobs.append(CaseJob(get_case(name), p, cfg=OptConfig(
            d_rounds=1, n_candidates=2, r=5, k=1), constraints=FAST))
    ex = InProcessExecutor(len(jobs))
    camp = Campaign(TPUModelPlatform(), cache=EvalCache(), executor=ex)
    # the executor attaches one shared batcher; swap in the fake
    # transport before any round fires
    batcher_holder = {}
    orig = ex._attach_batcher

    def attach(jobs_):
        b = orig(jobs_)
        assert b is not None
        b._transport = transport
        batcher_holder["b"] = b
        return b

    ex._attach_batcher = attach
    results = camp.run(jobs)
    assert all(r.rounds for r in results)
    b = batcher_holder["b"]
    assert b.coalesced >= len(cases)
    assert b.calls < b.coalesced, \
        f"{b.calls} endpoint calls for {b.coalesced} prompts — no coalescing"
    assert all(p.batcher is b for p in proposers)
