"""Worker fabric on one host: the spec wire form, the executor factory,
worker start-up and tear-down, the stdio worker protocol, and the
journal rules the stores keep for files written by older versions.

The subprocess legs spawn real ``scripts/worker_main.py`` workers on
the CPU.  Kept apart from ``tests/test_workers.py`` so that xdist's
``--dist loadfile`` spreads the two files over two workers."""
import dataclasses
import importlib
import json
import os
import sys
import threading
import time
import warnings

import pytest

from repro.core import (CaseJob, CPUPlatform, Campaign, EvalCache,
                        EvalRecord, HeuristicProposer, InProcessExecutor,
                        LocalClusterExecutor, MEPConstraints, OptConfig,
                        OptResult, PatternStore, SubprocessExecutor,
                        TPUModelPlatform, WorkerContext, WorkerFault,
                        canonical_spec, get_case, make_executor)
from repro.core import evalcache
from repro.core.measure import MeasureConfig
from repro.core.workers import (_WorkerProc, _worker_cmd, _worker_env,
                                job_to_spec, lease_for_spec)
import repro.core.workers as workers_mod

FAST = MEPConstraints(t_max_s=2.0, r=5, k=1)
FAST_CFG = OptConfig(d_rounds=2, n_candidates=2, r=5, k=1)

# a journal line that compaction wrote before compaction markers went
_OLD_MARKER = json.dumps({"ev": "compact", "epoch": 1, "host": "old-host",
                          "pid": 1, "ts": 0.0})


def _ctx(platform=None, **kw):
    return WorkerContext(platform=platform or TPUModelPlatform(), **kw)


def _job(case="gemm", seed=0, label=""):
    return CaseJob(get_case(case), HeuristicProposer(seed), cfg=FAST_CFG,
                   constraints=FAST, seed=seed, label=label)


def _winners(results):
    return [(r.case_name, r.best_variant, round(r.best_time_s, 12))
            for r in results]


def _lines(path):
    with open(path, "rb") as f:
        return [ln for ln in f.read().split(b"\n") if ln.strip()]


# ------------------------------------------------- dataclass defaults ----
def test_casejob_defaults_are_not_aliased():
    """Per-job config mutation must never leak into other defaulted jobs
    (the old ``cfg: OptConfig = OptConfig()`` class-level instance)."""
    a = CaseJob(get_case("gemm"), HeuristicProposer(0))
    b = CaseJob(get_case("atax"), HeuristicProposer(0))
    assert a.cfg is not b.cfg
    assert a.constraints is not b.constraints


@pytest.mark.parametrize("module", [
    "campaign", "diagnosis", "evalcache", "kernelcase", "measure", "mep",
    "optimizer", "patterns", "population", "proposer", "workers"])
def test_no_shared_mutable_dataclass_defaults_in_core(module):
    """Audit: no dataclass in core/ may default a field to a shared
    *mutable* instance.  Frozen-dataclass defaults are fine (immutable,
    sharing is safe); anything else must use default_factory."""
    mod = importlib.import_module(f"repro.core.{module}")
    offenders = []
    for obj in vars(mod).values():
        if not (isinstance(obj, type) and dataclasses.is_dataclass(obj)
                and obj.__module__ == mod.__name__):
            continue
        for f in dataclasses.fields(obj):
            d = f.default
            if d is dataclasses.MISSING:
                continue
            if isinstance(d, (list, dict, set, bytearray)):
                offenders.append(f"{obj.__name__}.{f.name}")
            elif dataclasses.is_dataclass(d) \
                    and not type(d).__dataclass_params__.frozen:
                offenders.append(f"{obj.__name__}.{f.name}")
    assert not offenders, f"shared mutable defaults: {offenders}"


# ---------------------------------------------------- executor factory ---
@pytest.mark.parametrize("kind,cls", [
    ("inprocess", InProcessExecutor), ("in-process", InProcessExecutor),
    ("thread", InProcessExecutor), ("subprocess", SubprocessExecutor),
    ("local-cluster", LocalClusterExecutor),
    ("cluster", LocalClusterExecutor)])
def test_make_executor_builds_each_kind(kind, cls):
    ex = make_executor(kind, workers=2)
    try:
        assert type(ex) is cls
        width = ex.max_workers if cls is InProcessExecutor else ex.workers
        assert width == 2
    finally:
        ex.close()


@pytest.mark.parametrize("kind", ["remote", "fleet"])
def test_make_executor_refuses_removed_kinds(kind, monkeypatch):
    """A removed kind is an error naming the kinds that remain — never a
    silent fallback to the in-process pool."""
    monkeypatch.delenv("REPRO_CAMPAIGN_EXECUTOR", raising=False)
    with pytest.raises(ValueError, match=f"unknown executor '{kind}'") \
            as ei:
        make_executor(kind)
    choices = str(ei.value).split("choose from", 1)[1]
    assert [c.strip() for c in choices.split(",")] \
        == ["inprocess", "subprocess", "local-cluster"]


# ----------------------------------------------------- warm() fallout ----
_DIES_MID_PING = [sys.executable, "-u", "-c",
                  "import sys; sys.stdin.readline(); sys.exit(9)"]


def test_warm_replaces_worker_that_dies_mid_ping(monkeypatch):
    """A worker dying during the warm() ping goes through the same
    replace-and-retry path submit uses — no raw EOFError, no dead slot
    left in the fabric."""
    real = workers_mod._worker_cmd()
    spawns = {"n": 0}

    def cmd():
        spawns["n"] += 1
        return _DIES_MID_PING if spawns["n"] == 1 else real

    monkeypatch.setattr(workers_mod, "_worker_cmd", cmd)
    ex = SubprocessExecutor(1, retries=1)
    try:
        ex.warm(timeout_s=120)          # first ping EOFs → replace → pong
        assert spawns["n"] == 2
        assert ex._procs[0].alive()     # the replacement holds the slot
    finally:
        ex.close()


def test_warm_exhausted_retries_surface_workerfault(monkeypatch):
    monkeypatch.setattr(workers_mod, "_worker_cmd",
                        lambda: list(_DIES_MID_PING))
    ex = SubprocessExecutor(1, retries=1)
    try:
        with pytest.raises(WorkerFault) as ei:
            ex.warm(timeout_s=60)
        assert ei.value.kind == "crash"
        assert ei.value.attempts == 2
    finally:
        ex.close()


# ---------------------------------------------------- binary framing -----
class _PipeChannel(_WorkerProc):
    def __init__(self, fd):
        self._read_fd = fd
        self._buf = b""

    def _fd(self):
        return self._read_fd

    def alive(self):
        return True


def test_line_channel_survives_utf8_split_across_chunks():
    """The channel buffers raw bytes and decodes only complete lines, so
    a multi-byte UTF-8 sequence torn across read chunks (which the old
    per-chunk ``decode(errors="replace")`` corrupted) survives."""
    payload = json.dumps({"unit": "µs", "note": "naïve—reduction"},
                         ensure_ascii=False).encode()
    mid = payload.find("µ".encode()) + 1        # inside the 2-byte seq
    r, w = os.pipe()
    try:
        ch = _PipeChannel(r)
        os.write(w, payload[:mid])

        def finish():
            time.sleep(0.15)
            os.write(w, payload[mid:] + b"\n")

        t = threading.Thread(target=finish)
        t.start()
        got = ch.recv(10.0)
        t.join()
        assert got == {"unit": "µs", "note": "naïve—reduction"}
    finally:
        os.close(r)
        os.close(w)


def test_worker_pipes_are_binary():
    """The Popen must not wrap stdout in a TextIOWrapper: recv() reads
    the raw fd, and a text wrapper could strand bytes in its buffer."""
    ex = SubprocessExecutor(1)
    try:
        w = ex._ensure_worker(0)
        assert "b" in w.proc.stdout.mode
        assert "b" in w.proc.stdin.mode
        w.send({"ping": True})
        assert w.recv(120).get("pong")
    finally:
        ex.close()


# ------------------------------------------------------ worker protocol --
@pytest.mark.parametrize("case", ["malformed-line", "job-error"])
def test_worker_main_protocol(case):
    """A bad line or a failing job gets an error reply, and the worker
    goes on to serve the next spec."""
    w = _WorkerProc(_worker_cmd(), _worker_env(), "proto")
    try:
        if case == "malformed-line":
            w.proc.stdin.write(b"this is not json\n")
            w.proc.stdin.flush()
            reply = w.recv(120)
            assert reply["ok"] is False
            assert reply["type"] == "ProtocolError"
        else:
            spec = job_to_spec(_job(), _ctx(), "c-err")
            spec["platform"] = "dcu-z100"      # no such platform
            w.send(spec)
            reply = w.recv(120)
            assert reply["ok"] is False
            assert reply["type"] == "KeyError"
            assert "unknown platform" in reply["error"]
        w.send({"ping": True})
        assert w.recv(120).get("pong")
        assert w.alive()
    finally:
        w.kill()


# ------------------------------------------------------------ spec wire --
def test_namespaces_ship_verbatim(tmp_path):
    derived = EvalCache(str(tmp_path / "c.jsonl"))
    spec = job_to_spec(_job(), _ctx(cache=derived), "c0")
    assert spec["cache"]["ns"] == derived.namespace \
        == evalcache.default_namespace()
    pinned = EvalCache(str(tmp_path / "c2.jsonl"), namespace="nsA")
    spec = job_to_spec(_job(), _ctx(cache=pinned), "c0")
    assert spec["cache"]["ns"] == "nsA"
    store = PatternStore(str(tmp_path / "p.jsonl"))
    assert store.to_spec()["ns"] == store.namespace \
        == evalcache.default_namespace()
    assert PatternStore(str(tmp_path / "p2.jsonl"),
                        namespace="nsB").to_spec()["ns"] == "nsB"
    # nothing per host travels: the worker uses what the scheduler holds
    assert sorted(spec) == ["cache", "campaign", "db", "job", "lease",
                            "measure", "patterns", "platform",
                            "population", "stop", "verbose"]


def test_lease_ships_verbatim(tmp_path):
    """The worker times under the lease the scheduler holds: derived
    next to the cache, or pinned by the caller."""
    cache = EvalCache(str(tmp_path / "c.jsonl"))
    camp = Campaign(CPUPlatform(), cache=cache)
    ctx = _ctx(CPUPlatform(), cache=cache, lease_path=camp.lease_path)
    spec = job_to_spec(_job(), ctx, "cX")
    assert spec["lease"] == cache.path + ".timelease"
    assert lease_for_spec(spec) == camp.lease_path
    pinned = _ctx(CPUPlatform(), lease_path="/shared/pinned.lease")
    spec = job_to_spec(_job(), pinned, "cY")
    assert lease_for_spec(spec) == "/shared/pinned.lease"


def test_measured_records_reject_cross_host_analytic_replay(tmp_path,
                                                            monkeypatch):
    """The namespace rule: a measured record taken under host A's
    namespace must not replay on host B; analytic records (pure
    functions of the spec) replay everywhere."""
    path = str(tmp_path / "cache.jsonl")
    m_spec = canonical_spec("gemm", {"tile_m": 128}, 1, "cpu")
    a_spec = canonical_spec("gemm", {"tile_m": 128}, 1, "tpu-v5e-model")

    monkeypatch.setattr(evalcache, "this_host", lambda: "hostA")
    ca = EvalCache(path)
    rec, hit = ca.get_or_compute(m_spec, lambda: EvalRecord(time_s=1.0),
                                 measured=True)
    assert not hit and "hostA" in rec.ns
    ca.get_or_compute(a_spec, lambda: EvalRecord(time_s=2.0),
                      measured=False)

    monkeypatch.setattr(evalcache, "this_host", lambda: "hostB")
    cb = EvalCache(path)                      # same file, host B identity
    assert cb.lookup(m_spec) is None          # measured: rejected
    assert cb.stats()["stale"] == 1

    def never():
        raise AssertionError("analytic record should have replayed")

    rec, hit = cb.get_or_compute(a_spec, never, measured=False)
    assert hit and rec.time_s == 2.0          # analytic: replays
    # host B's own timing is stamped host B and serves host B
    rec, hit = cb.get_or_compute(m_spec, lambda: EvalRecord(time_s=3.0),
                                 measured=True)
    assert not hit and "hostB" in rec.ns
    assert cb.lookup(m_spec).time_s == 3.0


def test_subprocess_worker_records_under_scheduler_namespace(tmp_path,
                                                             monkeypatch):
    """A worker stamps its measured records with the namespace the
    scheduler shipped, so the scheduler replays them; a worker that
    derived its own would stamp the real hostname instead."""
    monkeypatch.setattr(evalcache, "this_host", lambda: "scheduler-ns")
    cache = EvalCache(str(tmp_path / "ec.jsonl"))
    assert cache.namespace == evalcache.default_namespace()
    cfg = OptConfig(d_rounds=1, n_candidates=1, r=5, k=1)
    camp = Campaign(CPUPlatform(), cache=cache,
                    executor=SubprocessExecutor(1),
                    measure=MeasureConfig(ci_rel=0.25))
    job = CaseJob(get_case("atax"), HeuristicProposer(0), cfg=cfg,
                  constraints=FAST)
    (res,) = camp.run([job])
    assert isinstance(res, OptResult)
    recs = [json.loads(ln) for ln in _lines(cache.path)]
    measured = [r for r in recs if r.get("measured")]
    assert measured
    assert {r["ns"] for r in measured} == {evalcache.default_namespace()}
    for r in measured:
        assert cache.lookup(r["spec"]) is not None


# ----------------------------------------- local cluster lifecycle -------
def test_local_cluster_close_is_idempotent_and_exception_safe(tmp_path):
    """close() twice is fine, and a run() that raises leaves the
    persistent workers for close() to tear down (no orphan worker
    processes)."""
    ex = LocalClusterExecutor(1)
    ex.warm()
    w = ex._procs[0]
    assert w.alive()
    ex.close()
    assert not w.alive() and ex._procs == {}
    ex.close()                                    # idempotent

    class Boom(RuntimeError):
        pass

    class BoomCache(EvalCache):
        def reload(self):
            raise Boom("scheduler-side error after the jobs ran")

    ex = LocalClusterExecutor(1)
    ctx = _ctx(cache=BoomCache(str(tmp_path / "ec.jsonl")))
    with pytest.raises(Boom):
        ex.run([_job()], ctx, campaign_id="boom")
    w = ex._procs[0]
    assert w.alive()                              # persistent: kept
    ex.close()
    assert not w.alive() and ex._procs == {}


def test_local_cluster_worker_killed_between_runs_is_replaced(tmp_path):
    """A persistent worker that died between two run() calls is replaced
    on the next dispatch instead of failing the campaign."""
    ex = LocalClusterExecutor(1)
    ctx = _ctx(cache=EvalCache(str(tmp_path / "cache.jsonl")))
    try:
        r1 = ex.run([_job("atax")], ctx, campaign_id="c1")
        assert isinstance(r1[0], OptResult)
        dead = ex._procs[0]
        dead.proc.kill()
        dead.proc.wait(timeout=30)
        r2 = ex.run([_job("atax")], ctx, campaign_id="c2")
        assert isinstance(r2[0], OptResult)
        assert ex._procs[0] is not dead and ex._procs[0].alive()
        assert not os.path.exists(dead.log.name)    # the dead one reaped
        assert _winners(r1) == _winners(r2)
    finally:
        ex.close()


# ------------------------------------------------------ journal rules ----
def test_evalcache_auto_compaction_thresholds(tmp_path):
    """Churning one key past the line/ratio thresholds triggers the
    automatic rewrite; the snapshot keeps last-wins semantics."""
    path = str(tmp_path / "cache.jsonl")
    cache = EvalCache(path)
    cache.COMPACT_MIN_LINES = 16      # CI-scale thresholds
    spec = canonical_spec("gemm", {"t": 0}, 1, "cpu")
    for i in range(40):
        # accept-veto forces a recompute + last-wins republish: the
        # documented way one key churns many journal lines
        cache.get_or_compute(spec,
                             lambda i=i: EvalRecord(time_s=float(i + 1)),
                             accept=lambda r: False)
    assert len(_lines(path)) < 40     # a rewrite happened
    assert EvalCache(path).lookup(spec).time_s == 40.0


def _with_old_markers(src, dst):
    """Copy journal ``src`` to ``dst`` with compaction-marker lines from
    an older version in the middle and at the end."""
    lines = _lines(src)
    mid = len(lines) // 2
    body = lines[:mid] + [_OLD_MARKER.encode()] + lines[mid:] \
        + [_OLD_MARKER.encode()]
    with open(dst, "wb") as f:
        f.write(b"\n".join(body) + b"\n")


def _cache_view(cache):
    return {k: r.time_s for k, r in cache._records.items()}


def _store_view(store):
    return (sorted(json.dumps(p.to_dict(), sort_keys=True)
                   for p in store.patterns),
            dict(store._acc))


@pytest.mark.parametrize("kind", ["EvalCache", "PatternStore"])
def test_journal_with_compaction_markers_still_replays(kind, tmp_path):
    """Journals written before compaction markers went still hold them:
    replay skips them (neither a record nor a corrupt line), and the
    next compaction writes none."""
    clean = str(tmp_path / "clean.jsonl")
    old = str(tmp_path / "old.jsonl")
    if kind == "EvalCache":
        c = EvalCache(clean)
        for i in range(6):
            c.get_or_compute(canonical_spec("gemm", {"t": i}, 1, "cpu"),
                             lambda i=i: EvalRecord(time_s=float(i + 1)))
        view = _cache_view
        open_store = EvalCache
    else:
        s = PatternStore(clean)
        base = dict(get_case("gemm").baseline_variant)
        for i in range(4):
            s.record(get_case("gemm"), "cpu", base,
                     dict(base, block_n=64 + i), 2.0 + i)
        (p,) = [q for q in s.patterns if q.delta == {"block_n": 67}]
        s.record_hint_outcome(get_case("syrk"), "cpu", p, won=True)
        view = _store_view
        open_store = PatternStore
    _with_old_markers(clean, old)

    with warnings.catch_warnings():
        warnings.simplefilter("error")        # no quarantine warning
        replayed = open_store(old)
    assert view(replayed) == view(open_store(clean))
    assert getattr(replayed, "quarantined", 0) == 0
    assert not os.path.exists(old + ".quarantine")

    replayed.compact()
    assert not any(b'"ev": "compact"' in ln for ln in _lines(old))
    assert view(open_store(old)) == view(open_store(clean))
