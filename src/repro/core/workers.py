"""Out-of-process evaluation fabric: campaign workers on one host.

The paper's premise is that MEPs make kernel evaluation cheap and
independent of the full application; this module makes it independent of
the *scheduler's process* too.  A campaign hands its ``CaseJob``s to an
``Executor`` and never touches an MEP directly:

* ``InProcessExecutor``   — today's bounded thread pool (default).  MEPs
  are deduped per (case, platform, seed, constraints, scale) so jobs on
  the same case share input generation and scale probing.
* ``SubprocessExecutor``  — one MEP per worker *process*.  Jobs travel
  as serialized eval specs (``job_to_spec``) over a line-JSON pipe to
  ``scripts/worker_main.py`` workers; results come back as full
  ``OptResult`` wire dicts.  The shared ``EvalCache`` JSONL (advisory
  file locks + namespace), the ``PatternStore`` journal (per-store
  flock; workers record wins round-by-round and re-read hints at round
  boundaries, so §3.2 Performance Pattern Inheritance flows *across*
  worker processes mid-campaign), and the ``ResultsDB`` journal (atomic
  O_APPEND lines) are the only shared state.
* ``LocalClusterExecutor`` — multiplexes N persistent subprocess
  workers.  Workers persist across campaigns, amortizing spawn cost for
  the serving autotuner's repeated cycles.

Measured (wall-clock) platforms fan out across workers like analytic
ones: every spec carries the campaign's **timing lease** (an flock'd
arbiter file, ``repro.core.measure.TimingLease``) and only the short
wall-clock slices serialize on it — build/compile/FE/LLM work overlaps
freely — so eq. 3's trimmed mean stays clean without the one-exclusive-
worker pinning this executor used to apply.

Process-level crashes and timeouts are folded into the AER taxonomy as
``WorkerFault`` (kind crash|timeout) with automatic worker replacement:
the dead worker is respawned and the job retried on the fresh process;
only a job that exhausts its retry budget surfaces the fault, which the
campaign records like any other job failure.

The LLM proposer's round prompts are coalesced across the concurrent
cases of an in-process campaign through a shared ``LLMBatcher`` (one
endpoint call per round wave); subprocess workers each coalesce within
their own process only.
"""
from __future__ import annotations

import json
import os
import queue
import select
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from repro.core.aer import AER, WorkerFault
from repro.core.diagnosis import diagnose_feedback
from repro.core.evalcache import EvalCache, ResultsDB, json_safe, this_host
from repro.core.kernelcase import KernelCase
from repro.core.measure import (MeasureConfig, default_lease_path,
                                resolve_lease)
from repro.core.mep import MEP, MEPConstraints, build_mep
from repro.core.optimizer import Evaluator, OptConfig, OptResult, RoundLog
from repro.core.patterns import Pattern, PatternStore
from repro.core.population import Population, PopulationConfig
from repro.core.profiler import Platform, platform_from_name
from repro.core.proposer import (LLMBatcher, LLMProposer, Proposer,
                                 RoundState, persona_proposers,
                                 proposer_from_spec)


@dataclass
class CaseJob:
    """One unit of campaign work: optimize ``case`` with ``proposer``."""
    case: KernelCase
    proposer: Proposer
    # default_factory, NOT a shared instance: OptConfig is mutable, so a
    # class-level default would alias per-job config mutation (setting
    # one job's cfg.measure would silently set every defaulted job's)
    cfg: OptConfig = field(default_factory=OptConfig)
    constraints: MEPConstraints = field(default_factory=MEPConstraints)
    seed: int = 0
    mep: Optional[MEP] = None       # pre-built MEP (else built & shared)
    label: str = ""                 # distinguishes jobs on the same case

    @property
    def name(self) -> str:
        return self.label or self.case.name


@dataclass
class WorkerContext:
    """Everything an executor needs beside the jobs themselves — the
    scheduler-owned shared state.  Executors must reach MEPs only
    through ``run_case_job``; the scheduler never builds one."""
    platform: Platform
    cache: Optional[EvalCache] = None
    patterns: Optional[PatternStore] = None
    db: Optional[ResultsDB] = None
    verbose: bool = False
    # campaign-level default measurement policy (per-job cfg.measure
    # wins) and the cross-process timing lease file shared by every
    # worker timing this campaign's wall-clock sections
    measure: Optional[MeasureConfig] = None
    lease_path: Optional[str] = None
    # campaign-level default population-search policy (per-job
    # cfg.population wins); None → the greedy §3.2 loop
    population: Optional[PopulationConfig] = None


# ---------------------------------------------------------------------------
# the paper's §3.2 search loop for ONE kernel — the unit every executor
# runs, in a pool thread (in-process) or a worker process (subprocess)
# ---------------------------------------------------------------------------
def run_case_job(job: CaseJob, platform: Platform, *,
                 campaign_id: str = "",
                 cache: Optional[EvalCache] = None,
                 patterns: Optional[PatternStore] = None,
                 db: Optional[ResultsDB] = None,
                 stop_event: Optional[threading.Event] = None,
                 verbose: bool = False,
                 mep: Optional[MEP] = None,
                 scale: Optional[int] = None,
                 measure: Optional[MeasureConfig] = None,
                 lease_path: Optional[str] = None,
                 population: Optional[PopulationConfig] = None
                 ) -> OptResult:
    """Round loop (eq. 5): propose → evaluate (build→FE→time, AER-wrapped,
    cache-served) → argmin, with the uniform early stop.  Serial per
    case; concurrency happens across cases, in whichever executor —
    measured platforms included, because wall-clock sections serialize
    on the campaign's timing lease (``lease_path``), not on worker
    exclusivity.

    With a ``PopulationConfig`` active (per-job ``cfg.population`` wins
    over the campaign-level ``population``) and a persona-capable
    proposer, the greedy loop is replaced by the evolutionary engine in
    ``repro.core.population`` — expert persona waves, tournament-by-
    racing selection, island migration through the PatternStore."""
    t_start = time.time()
    case, proposer, cfg = job.case, job.proposer, job.cfg
    # measurement policy: per-job cfg wins over the campaign default;
    # the campaign's lease path is folded in either way
    mcfg = resolve_lease(cfg.measure or measure, lease_path)
    if mep is None:
        # the auto-sizing probes carry the lease too: a worker's probe
        # must not wall-clock over another worker's leased eq. 3 slices
        mep = job.mep or build_mep(case, platform,
                                   constraints=job.constraints,
                                   seed=job.seed, scale=scale,
                                   budget=mcfg)
    aer = AER(case, mep.scale)
    evaluator = Evaluator(mep, case, platform.name, aer, proposer,
                          cfg, cache=cache,
                          measured=not getattr(platform,
                                               "concurrency_safe", False),
                          measure_cfg=mcfg)

    baseline_v = dict(case.baseline_variant)
    t_base = evaluator.measure_baseline(baseline_v)
    best_v, best_t = baseline_v, t_base
    res = OptResult(case.name, platform.name, proposer.name,
                    baseline_v, t_base, best_v, best_t,
                    mep_log=list(mep.log))

    history: List[Dict[str, Any]] = []
    errors: List[str] = []
    best_ci_rel = 0.0           # rel. CI of the timing behind best_t
    last_bottleneck = ""
    pcfg = cfg.population if cfg.population is not None else population
    clones = persona_proposers(proposer, pcfg.personae) \
        if pcfg is not None else None
    if clones:
        # population search: expert persona waves + tournament racing +
        # island migration (core.population).  A proposer kind without
        # persona support (e.g. DirectProposer) falls through to the
        # greedy loop below.
        engine = Population(case, platform, mep, evaluator, cfg, pcfg,
                            clones, patterns=patterns, db=db,
                            campaign_id=campaign_id, job_name=job.name,
                            seed=job.seed, verbose=verbose)
        last_bottleneck = engine.search(res, baseline_v, t_base,
                                        stop_event=stop_event)
        best_v, best_t = res.best_variant, res.best_time_s
    else:
        last_bottleneck = _greedy_rounds(
            job, platform, res, evaluator, mep, baseline_v, t_base,
            campaign_id=campaign_id, patterns=patterns, db=db,
            stop_event=stop_event, history=history, errors=errors)
        best_v, best_t = res.best_variant, res.best_time_s
    if not res.stop_reason:
        res.stop_reason = f"d_rounds={cfg.d_rounds} exhausted"

    res.aer_records = len(aer.records)
    res.cache_hits, res.cache_misses = evaluator.hits, evaluator.misses
    res.timing_reps = evaluator.timing_reps
    res.timing_reps_fixed = evaluator.timing_reps_fixed
    res.raced_out = evaluator.raced
    if evaluator.timing_reps and \
            evaluator.timing_reps < evaluator.timing_reps_fixed:
        res.mep_log.append(
            f"measurement: {evaluator.timing_reps} reps paid vs "
            f"{evaluator.timing_reps_fixed} fixed-R "
            f"({res.rep_savings:.2f}x savings, "
            f"{evaluator.raced} raced out)")
    res.wall_s = time.time() - t_start
    if patterns is not None:
        patterns.record(case, platform.name, baseline_v, best_v,
                        res.speedup, bottleneck=last_bottleneck)
    if db:
        db.append("case_result", campaign=campaign_id,
                  job=job.name, host=this_host(), **res.to_dict())
    if verbose:
        print(f"# campaign {job.name}: {res.best_time_s * 1e6:.2f}us, "
              f"{res.speedup:.2f}x over baseline, "
              f"{len(res.rounds)} rounds, {res.cache_hits} cache hits "
              f"[{res.stop_reason}]", flush=True)
    return res


def _greedy_rounds(job: CaseJob, platform: Platform, res: OptResult,
                   evaluator: Evaluator, mep: MEP, baseline_v, t_base, *,
                   campaign_id: str, patterns, db, stop_event,
                   history: List[Dict[str, Any]], errors: List[str]
                   ) -> str:
    """The paper's greedy one-variant-per-round loop (the pre-population
    baseline, still the default).  Fills ``res`` rounds/best/stop_reason
    and returns the last diagnosed bottleneck."""
    case, proposer, cfg = job.case, job.proposer, job.cfg
    best_v, best_t = dict(baseline_v), t_base
    best_ci_rel = 0.0           # rel. CI of the timing behind best_t
    last_bottleneck = ""
    for d in range(cfg.d_rounds):
        if stop_event is not None and stop_event.is_set():
            res.stop_reason = "stop requested"
            res.mep_log.append(f"round {d}: stopped (stop requested)")
            break
        # diagnose the incumbent: WHY is it slow?  The verdict routes
        # the proposer's move set, picks the PPI hint bucket, tags the
        # round journal, and stamps this round's recorded patterns
        feedback = platform.profile_feedback(case, best_v, mep.scale)
        diag = diagnose_feedback(feedback, ci_rel=best_ci_rel)
        last_bottleneck = diag.bottleneck
        hints: Optional[List[Pattern]] = None
        if patterns is not None:
            # round boundary: fold other workers' journal appends in, so
            # a win recorded by a concurrent case — possibly in another
            # process — reaches this round's proposal wave (§3.2 PPI).
            # ONE snapshot per round: the proposer consumes exactly the
            # hint deltas the round record journals below
            hints = patterns.suggest_patterns(case, platform.name,
                                              bottleneck=diag.bottleneck)
        state = RoundState(
            round=d, baseline_variant=best_v, baseline_time_s=best_t,
            feedback=feedback,
            history=history, errors=errors,
            hints=None if hints is None
            else [dict(p.delta) for p in hints],
            diagnosis=diag)
        cands = proposer.propose(case, state, cfg.n_candidates)
        rl = RoundLog(round=d, baseline_time_s=best_t,
                      diagnosis=diag.to_dict())
        for v in cands:
            # the current best is the incumbent: timing a candidate
            # aborts once its optimistic lower bound provably loses
            cl = evaluator.evaluate(v, incumbent_s=best_t)
            rl.candidates.append(cl)
            # raced_out is marked in the proposer-visible history too: a
            # truncated trimmed mean must not read as a near-miss full
            # measurement when later rounds steer proposals
            history.append({"variant": cl.variant, "time_s": cl.time_s,
                            "status": cl.status,
                            "raced_out": cl.raced_out})
            if cl.status != "ok":
                errors.append(cl.error)
        # a raced-out candidate is a loss by construction (its partial
        # trimmed mean is not a full eq. 3 measurement): it never enters
        # the argmin, so it can never become a winner
        feasible = [c for c in rl.candidates
                    if c.status == "ok" and not c.raced_out]
        raced = [c for c in rl.candidates if c.raced_out]
        # eq. 5 argmin + uniform early stop: ANY round (round 0
        # included) that fails to improve by > eps ends the loop,
        # with the reason logged.
        stop = ""
        if not feasible:
            stop = ("all candidates raced out (none can beat the "
                    "incumbent)") if raced else "no feasible candidates"
        else:
            winner = min(feasible, key=lambda c: c.time_s)
            rl.best_time_s = winner.time_s
            gain = best_t / winner.time_s if winner.time_s else float("inf")
            if winner.time_s < best_t:
                best_v, best_t = winner.variant, winner.time_s
                best_ci_rel = winner.ci_half_width_s / winner.time_s \
                    if winner.time_s else 0.0
            rl.improved = gain > 1.0 + cfg.improve_eps
            if not rl.improved:
                if gain <= 1.0:
                    stop = (f"winner did not beat baseline "
                            f"(gain {gain:.4f}x)")
                else:
                    stop = (f"round gain {gain:.4f}x below threshold "
                            f"{1.0 + cfg.improve_eps:.4f}x")
        rl.stop_reason = stop
        # per-hint acceptance evidence: did each suggested delta end up
        # in the round winner?  Journaled into the RoundLog AND fed back
        # to the store's acceptance ledger, so repeatedly-useless hints
        # decay out of future suggestion waves
        for p in hints or []:
            accepted = rl.improved and all(
                best_v.get(k) == val for k, val in p.delta.items())
            rl.hints.append({"delta": dict(p.delta),
                             "source": p.source_kernel, "gain": p.gain,
                             "bottleneck": diag.bottleneck,
                             "accepted": accepted,
                             "pid": p.pid, "ns": p.ns})
            res.hints_suggested += 1
            res.hints_accepted += int(accepted)
            if patterns is not None:
                patterns.record_hint_outcome(case, platform.name, p,
                                             won=accepted,
                                             bottleneck=diag.bottleneck)
        res.rounds.append(rl)
        if rl.improved and patterns is not None:
            # record the round's cumulative win immediately (not at job
            # end): concurrent cases' next rounds inherit it mid-campaign
            patterns.record(case, platform.name, baseline_v, best_v,
                            t_base / best_t if best_t else float("inf"),
                            bottleneck=diag.bottleneck)
        if db:
            db.append(
                "round", campaign=campaign_id, job=job.name,
                case=case.name, round=d, worker=os.getpid(),
                host=this_host(),
                baseline_time_s=rl.baseline_time_s,
                best_time_s=rl.best_time_s, improved=rl.improved,
                stop_reason=stop,
                diagnosis=rl.diagnosis,
                ppi_hints=[dict(h) for h in rl.hints],
                candidates=[{"variant": c.variant, "status": c.status,
                             "time_s": c.time_s, "cached": c.cached,
                             "reps": c.reps,
                             "ci_half_width_s": c.ci_half_width_s,
                             "raced_out": c.raced_out}
                            for c in rl.candidates])
        if stop:
            res.mep_log.append(f"round {d}: stopped ({stop})")
            res.stop_reason = stop
            break
    res.best_variant, res.best_time_s = best_v, best_t
    return last_bottleneck


# ---------------------------------------------------------------------------
# wire form
# ---------------------------------------------------------------------------
def job_to_spec(job: CaseJob, ctx: WorkerContext, campaign_id: str
                ) -> Dict[str, Any]:
    """Serialize one CaseJob + the shared-state coordinates into the eval
    spec a worker process consumes.  Raises TypeError/ValueError up
    front for anything that cannot cross the process boundary."""
    if ctx.cache is not None and not ctx.cache.path:
        raise ValueError(
            "subprocess executors need a file-backed EvalCache (or none): "
            "an in-memory cache cannot be shared across processes")
    # cross-process timing lease: every worker timing this campaign's
    # wall-clock sections must serialize on the same arbiter file.  The
    # campaign provides one (next to its cache); for direct executor
    # users the same rule is re-derived here, campaign-scoped — a
    # measured platform must never fan out lease-less.
    lease = ctx.lease_path
    if lease is None and not getattr(ctx.platform, "concurrency_safe",
                                     False):
        cache_path = ctx.cache.path if ctx.cache is not None else None
        lease = default_lease_path(cache_path, scope=campaign_id)
    return {
        "job": {
            "case": job.case.to_dict(),
            "proposer": job.proposer.to_spec(),
            "cfg": job.cfg.to_dict(),
            "constraints": job.constraints.to_dict(),
            "seed": job.seed,
            "label": job.label,
            # a pre-built MEP may be pinned to a non-default (observed
            # traffic) scale; the worker rebuilds at the same pin
            "scale": job.mep.scale if job.mep else None,
        },
        "platform": ctx.platform.name,
        # the namespace ships as the scheduler holds it: workers stamp
        # their measured records with it, so they replay in the
        # scheduler's view
        "cache": None if ctx.cache is None else {
            "path": ctx.cache.path,
            "ns": ctx.cache.namespace,
            "ttl_s": ctx.cache.ttl_s},
        # a file-backed PatternStore ships its coordinates so workers
        # record and suggest against the shared journal; an in-memory
        # store stays scheduler-side (recording on job completion only)
        "patterns": ctx.patterns.to_spec()
        if ctx.patterns is not None and ctx.patterns.path else None,
        "db": ctx.db.path if ctx.db else None,
        "measure": ctx.measure.to_dict() if ctx.measure else None,
        "population": ctx.population.to_dict()
        if ctx.population else None,
        "lease": lease,
        "campaign": campaign_id,
        "verbose": ctx.verbose,
        "stop": False,
    }


def job_from_spec(spec: Dict[str, Any]) -> Tuple[CaseJob, Optional[int]]:
    """Worker-side inverse of ``job_to_spec`` (job part only); returns the
    job plus the pinned MEP scale (None → auto-sized)."""
    j = spec["job"]
    job = CaseJob(
        case=KernelCase.from_dict(j["case"]),
        proposer=proposer_from_spec(j["proposer"]),
        cfg=OptConfig.from_dict(j["cfg"]),
        constraints=MEPConstraints.from_dict(j["constraints"]),
        seed=int(j.get("seed", 0)),
        label=j.get("label", ""))
    scale = j.get("scale")
    return job, (int(scale) if scale is not None else None)


def lease_for_spec(spec: Dict[str, Any]) -> Optional[str]:
    """The timing-lease path a worker uses for ``spec``: the one the
    scheduler shipped."""
    return spec["lease"]


# ---------------------------------------------------------------------------
# executors
# ---------------------------------------------------------------------------
class Executor:
    """Transport-agnostic evaluation backend.  ``run`` maps jobs to
    outcomes (``OptResult`` or the ``Exception`` that killed the job),
    in job order; it must not raise for a single job's failure."""

    name = "abstract"

    def run(self, jobs: List[CaseJob], ctx: WorkerContext, *,
            campaign_id: str = "",
            stop: Optional[threading.Event] = None) -> List[Any]:
        raise NotImplementedError

    def close(self) -> None:
        """Release any long-lived resources (persistent workers)."""


class InProcessExecutor(Executor):
    """Bounded thread pool in the scheduler's process — the default, and
    the reference semantics every other transport must match."""

    name = "inprocess"

    def __init__(self, max_workers: int = 4):
        self.max_workers = max(1, max_workers)
        self._mep_lock = threading.Lock()
        self._mep_locks: Dict[Tuple, threading.Lock] = {}
        self._meps: Dict[Tuple, MEP] = {}

    # ------------------------------------------------------------------
    def _get_mep(self, job: CaseJob, ctx: WorkerContext) -> MEP:
        # a pre-built MEP may be pinned to a non-default (e.g. observed
        # traffic) scale, so its scale is part of the dedup identity
        key = (job.case.name, ctx.platform.name, job.seed, job.constraints,
               job.mep.scale if job.mep else None)
        with self._mep_lock:
            lk = self._mep_locks.setdefault(key, threading.Lock())
        with lk:
            if key not in self._meps:
                self._meps[key] = job.mep or build_mep(
                    job.case, ctx.platform, constraints=job.constraints,
                    seed=job.seed,
                    budget=resolve_lease(job.cfg.measure or ctx.measure,
                                         ctx.lease_path))
            return self._meps[key]

    def _attach_batcher(self, jobs: List[CaseJob],
                        ctx: Optional[WorkerContext] = None
                        ) -> Optional[LLMBatcher]:
        """Coalesce LLM round prompts across the campaign's concurrent
        cases: all LLM proposers without their own batcher share one.
        Population jobs contribute one prompt per persona per wave, so
        ``max_batch`` is sized to the sum of the jobs' wave widths."""
        if ctx is None:      # run() stashes it; tests wrap 1-arg
            ctx = getattr(self, "_batch_ctx", None)
        props, width = [], 0
        for j in jobs:
            if not (isinstance(j.proposer, LLMProposer)
                    and j.proposer.batcher is None):
                continue
            props.append(j.proposer)
            pcfg = j.cfg.population if j.cfg.population is not None \
                else (ctx.population if ctx is not None else None)
            width += len(pcfg.personae) if pcfg is not None else 1
        if len(props) < 2 or self.max_workers < 2:
            return None
        batcher = LLMBatcher(max_batch=max(width, len(props)))
        for p in props:
            p.batcher = batcher
            batcher.register()
        return batcher

    def run(self, jobs, ctx, *, campaign_id="", stop=None):
        from concurrent.futures import ThreadPoolExecutor
        self._batch_ctx = ctx
        batcher = self._attach_batcher(jobs)

        def guarded(job: CaseJob):
            try:
                mep = self._get_mep(job, ctx)
                return run_case_job(
                    job, ctx.platform, campaign_id=campaign_id,
                    cache=ctx.cache, patterns=ctx.patterns, db=ctx.db,
                    stop_event=stop, verbose=ctx.verbose, mep=mep,
                    measure=ctx.measure, lease_path=ctx.lease_path,
                    population=ctx.population)
            except Exception as e:  # noqa: BLE001 — isolate job failures
                return e
            finally:
                if batcher is not None and \
                        getattr(job.proposer, "batcher", None) is batcher:
                    batcher.unregister()

        if self.max_workers == 1 or len(jobs) == 1:
            return [guarded(j) for j in jobs]
        with ThreadPoolExecutor(self.max_workers) as ex:
            return [f.result() for f in [ex.submit(guarded, j)
                                         for j in jobs]]


# ---------------------------------------------------------------------------
class _WorkerProc:
    """One worker subprocess + its line-JSON spec protocol.  stderr goes
    to a temp file whose tail becomes the fault diagnostic on crash.

    The stdio pipes are opened in *binary* mode and ``recv`` reads the
    raw fd: a ``text=True`` TextIOWrapper sitting on the same fd could
    strand bytes in its own buffer where the fd-level reader would never
    see them.  The receive buffer holds raw *bytes*; a line is decoded
    only once its terminating newline has arrived, so a multi-byte UTF-8
    sequence split across read chunks can never be torn (decoding chunk
    boundaries with ``errors="replace"`` used to corrupt it)."""

    def __init__(self, cmd: List[str], env: Dict[str, str], slot: Any):
        self.slot = slot
        self._buf = b""
        self.log = tempfile.NamedTemporaryFile(
            mode="w+b", prefix=f"repro-worker{slot}-", suffix=".log",
            delete=False)
        self.proc = subprocess.Popen(
            cmd, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self.log)

    def _fd(self) -> int:
        return self.proc.stdout.fileno()

    def alive(self) -> bool:
        return self.proc.poll() is None

    def send(self, spec: Dict[str, Any]) -> None:
        self.proc.stdin.write((json.dumps(spec) + "\n").encode())
        self.proc.stdin.flush()

    def recv(self, timeout_s: Optional[float]) -> Dict[str, Any]:
        """Read one protocol line; raises TimeoutError / EOFError."""
        deadline = None if timeout_s is None else \
            time.monotonic() + timeout_s
        fd = self._fd()
        while True:
            nl = self._buf.find(b"\n")
            if nl >= 0:
                line, self._buf = self._buf[:nl], self._buf[nl + 1:]
                if line.strip():
                    return json.loads(line.decode("utf-8",
                                                  errors="replace"))
                continue
            wait = None if deadline is None else deadline - time.monotonic()
            if wait is not None and wait <= 0:
                raise TimeoutError(f"no result within {timeout_s}s")
            ready, _, _ = select.select([fd], [], [],
                                        min(wait, 1.0) if wait else 1.0)
            if not ready:
                if not self.alive() and not self._buf:
                    raise EOFError(self.diagnostic())
                continue
            chunk = os.read(fd, 65536)
            if not chunk:
                raise EOFError(self.diagnostic())
            self._buf += chunk

    def diagnostic(self) -> str:
        code = self.proc.poll()
        tail = ""
        try:
            self.log.flush()
            with open(self.log.name, "rb") as f:
                f.seek(max(0, os.fstat(f.fileno()).st_size - 2000))
                tail = f.read().decode(errors="replace").strip()
        except OSError:
            pass
        return f"exit={code}" + (f"; stderr tail:\n{tail}" if tail else "")

    def kill(self) -> None:
        try:
            if self.alive():
                self.proc.kill()
            self.proc.wait(timeout=5)
        except (OSError, subprocess.TimeoutExpired):
            pass
        for h in (self.proc.stdin, self.proc.stdout, self.log):
            try:
                h.close()
            except OSError:
                pass
        try:
            os.unlink(self.log.name)
        except OSError:
            pass


def _worker_cmd() -> List[str]:
    """Spawn command for scripts/worker_main.py, falling back to an
    inline import when the repo layout isn't present (installed use)."""
    here = os.path.dirname(os.path.abspath(__file__))
    script = os.path.abspath(os.path.join(here, "..", "..", "..",
                                          "scripts", "worker_main.py"))
    if os.path.exists(script):
        return [sys.executable, "-u", script]
    return [sys.executable, "-u", "-c",
            "from repro.core.workers import worker_main; worker_main()"]


def _worker_env() -> Dict[str, str]:
    """Environment of a spawned worker.  Workers evaluate on the host CPU
    (``JAX_PLATFORMS=cpu``): a chip belongs to one process, and the parent
    may hold it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", ".."))
    parts = [src] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                     if p and p != src]
    env["PYTHONPATH"] = os.pathsep.join(parts)
    return env


class SubprocessExecutor(Executor):
    """One MEP per worker process: N workers each pull serialized eval
    specs off a FIFO work queue, evaluate them in their own interpreter
    (their own GIL, their own jit caches), and ship ``OptResult`` wire
    dicts back.  Crashes and timeouts become ``WorkerFault``s with
    automatic worker replacement; the cache/journal files are the only
    shared state."""

    name = "subprocess"
    persistent = False        # workers live for one run() call

    def __init__(self, workers: Optional[int] = None, *,
                 timeout_s: Optional[float] = None, retries: int = 1):
        if workers is None:
            workers = int(os.environ.get(
                "REPRO_CAMPAIGN_WORKERS", str(os.cpu_count() or 2)))
        self.workers = max(1, workers)
        if timeout_s is None:
            env = os.environ.get("REPRO_WORKER_TIMEOUT_S", "")
            timeout_s = float(env) if env else None
        self.timeout_s = timeout_s
        self.retries = max(0, retries)
        from collections import deque
        self.dispatch_log = deque(maxlen=4096)          # (job, slot)
        self._procs: Dict[Any, _WorkerProc] = {}        # slot → process
        self._slot_locks: Dict[Any, threading.Lock] = {}
        self._lock = threading.Lock()

    # -- overridable routing hook (kept for custom executors) --
    def _slots_for(self, ctx: WorkerContext, n_jobs: int) -> List[Any]:
        # measured platforms fan out like analytic ones: their
        # wall-clock sections serialize on the campaign's cross-process
        # timing lease (job_to_spec guarantees every spec carries one),
        # so worker exclusivity is no longer needed to protect eq. 3
        return list(range(min(self.workers, max(1, n_jobs))))

    def _slot_lock(self, slot: Any) -> threading.Lock:
        # one protocol exchange at a time per worker process, even when
        # a persistent executor serves overlapping campaigns
        with self._lock:
            return self._slot_locks.setdefault(slot, threading.Lock())

    def _inject(self, job: CaseJob, spec: Dict[str, Any]) -> None:
        """Test-only fault injection hook: jobs may carry an ``inject``
        attribute (set by tests) that the worker honors before
        evaluating."""
        inject = getattr(job, "inject", None)
        if inject:
            spec["inject"] = inject

    def run(self, jobs, ctx, *, campaign_id="", stop=None):
        # serialize everything first: a non-wire-safe job must fail the
        # campaign before any process is spawned
        specs = []
        for job in jobs:
            spec = job_to_spec(job, ctx, campaign_id)
            self._inject(job, spec)
            specs.append(spec)

        if not jobs:
            return []
        outcomes: List[Any] = [None] * len(jobs)
        slots = self._slots_for(ctx, len(jobs))
        # FIFO work queue of (idx, job, spec, attempt); a retry goes to
        # the back.  One None per slot closes it once every job has its
        # outcome.
        work: "queue.Queue[Optional[Tuple]]" = queue.Queue()
        for i, (job, spec) in enumerate(zip(jobs, specs)):
            work.put((i, job, spec, 0))
        remaining = [len(jobs)]

        def finish(idx: int, outcome: Any) -> None:
            outcomes[idx] = outcome
            with self._lock:
                remaining[0] -= 1
                if remaining[0] == 0:
                    for _ in slots:
                        work.put(None)

        def fault(idx, job, spec, attempt, kind, detail, slot):
            """AER worker-fault handling: journal, replace the worker,
            retry on the fresh one, surface WorkerFault when spent."""
            if ctx.db:
                try:
                    ctx.db.append("worker_fault", campaign=campaign_id,
                                  job=job.name, fault=kind,
                                  attempt=attempt + 1, slot=str(slot),
                                  detail=str(detail)[:500])
                except OSError:
                    pass     # a full disk must not turn a retry into a hang
            if attempt < self.retries:
                work.put((idx, job, spec, attempt + 1))
            else:
                finish(idx, WorkerFault(kind, job.name, str(detail)[:500],
                                        attempts=attempt + 1))

        def dispatch(slot, idx, job, spec, attempt) -> None:
            if stop is not None and stop.is_set():
                spec = dict(spec, stop=True)
            self.dispatch_log.append((job.name, slot))
            try:
                with self._slot_lock(slot):
                    worker = self._ensure_worker(slot)
                    worker.send(spec)
                    reply = worker.recv(self.timeout_s)
            except (EOFError, OSError, ValueError) as e:
                # TimeoutError is an OSError: a stalled worker is
                # replaced like a dead one
                self._replace_worker(slot)
                kind = "timeout" if isinstance(e, TimeoutError) \
                    else "crash"
                fault(idx, job, spec, attempt, kind, e, slot)
                return
            if reply.get("ok"):
                res = OptResult.from_dict(reply["result"])
                if ctx.patterns is not None and not ctx.patterns.path:
                    # in-memory store couldn't cross the process
                    # boundary: fall back to recording on completion
                    # (a file-backed store was shipped in the spec and
                    # already recorded worker-side, round by round)
                    ctx.patterns.record(job.case, ctx.platform.name,
                                        res.baseline_variant,
                                        res.best_variant, res.speedup)
                finish(idx, res)
            else:
                finish(idx, RuntimeError(
                    f"{reply.get('type', 'Error')}: "
                    f"{reply.get('error', 'worker error')}"))

        def slot_loop(slot: Any) -> None:
            while True:
                item = work.get()
                if item is None:
                    return
                idx, job, spec, attempt = item
                try:
                    dispatch(slot, idx, job, spec, attempt)
                except Exception as e:  # noqa: BLE001 — a scheduler-side
                    # error (bad reply shape, pattern-store I/O) must fail
                    # THIS job, not strand the whole campaign in get()
                    finish(idx, e)

        threads = [threading.Thread(target=slot_loop, args=(s,),
                                    name=f"exec-slot{s}", daemon=True)
                   for s in slots]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if ctx.cache is not None:
                ctx.cache.reload()   # fold workers' entries into our view
            if ctx.patterns is not None and ctx.patterns.path:
                ctx.patterns.reload()  # fold workers' patterns too
        finally:
            # exception-safe: a one-shot fabric must not leak worker
            # processes when a reload (or a start) raises
            if not self.persistent:
                self.close()
        return outcomes

    def warm(self, slots: Optional[List[Any]] = None,
             timeout_s: float = 120.0) -> None:
        """Pre-spawn the worker processes and wait until each answers a
        protocol ping (interpreter + jax import done).  A persistent
        fabric (LocalClusterExecutor, the serving autotuner) calls this
        once so campaign wall-clock measures evaluation, not startup.

        A worker dying mid-ping goes through the same replace-and-retry
        path ``run`` uses — the dead process is killed and respawned and
        the ping retried, honoring the retry budget — instead of leaving
        a dead slot behind and raising raw EOFError at the caller.  A
        slot that cannot come up surfaces as ``WorkerFault``."""
        for slot in (slots if slots is not None else range(self.workers)):
            last: Optional[BaseException] = None
            for attempt in range(self.retries + 1):
                try:
                    with self._slot_lock(slot):
                        w = self._ensure_worker(slot)
                        w.send({"ping": True})
                        w.recv(timeout_s)
                    last = None
                    break
                except (EOFError, OSError, ValueError) as e:
                    last = e
                    self._replace_worker(slot)
            if last is not None:
                kind = "timeout" if isinstance(last, TimeoutError) \
                    else "crash"
                raise WorkerFault(kind, f"warm:{slot}", str(last)[:500],
                                  attempts=self.retries + 1)

    # ------------------------------------------------------------------
    def _ensure_worker(self, slot: int) -> _WorkerProc:
        with self._lock:
            w = self._procs.get(slot)
            if w is None or not w.alive():
                if w is not None:
                    w.kill()        # reap a worker that died idle
                w = _WorkerProc(_worker_cmd(), _worker_env(), slot)
                self._procs[slot] = w
            return w

    def _replace_worker(self, slot: int) -> None:
        with self._lock:
            w = self._procs.pop(slot, None)
        if w is not None:
            w.kill()

    def close(self) -> None:
        with self._lock:
            procs, self._procs = list(self._procs.values()), {}
        for w in procs:
            w.kill()

    def __del__(self):  # best-effort cleanup for persistent executors
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


class LocalClusterExecutor(SubprocessExecutor):
    """N persistent subprocess workers.  Workers stay alive across
    ``run`` calls (campaign after campaign), so repeated autotune cycles
    don't re-pay interpreter+jax startup.  Measured (wall-clock)
    platforms fan out across the whole pool — the pinned exclusive slot
    they used to get is gone; the cross-process timing lease serializes
    only the wall-clock slices while build/compile/FE/LLM work overlaps
    freely."""

    name = "local-cluster"
    persistent = True


def make_executor(kind: Optional[str], *, workers: Optional[int] = None,
                  timeout_s: Optional[float] = None) -> Executor:
    """Executor factory behind the ``--executor=`` / ``executor=`` knobs
    (None → REPRO_CAMPAIGN_EXECUTOR, default in-process)."""
    if kind is None:
        kind = os.environ.get("REPRO_CAMPAIGN_EXECUTOR", "inprocess")
    kind = kind.replace("_", "-")
    if kind in ("inprocess", "in-process", "thread"):
        if workers is None:
            workers = int(os.environ.get("REPRO_CAMPAIGN_WORKERS", "4"))
        return InProcessExecutor(workers)
    if kind == "subprocess":
        return SubprocessExecutor(workers, timeout_s=timeout_s)
    if kind in ("local-cluster", "cluster"):
        return LocalClusterExecutor(workers, timeout_s=timeout_s)
    raise ValueError(f"unknown executor {kind!r}; choose from "
                     f"inprocess, subprocess, local-cluster")


# ---------------------------------------------------------------------------
# worker process entry point (spawned via scripts/worker_main.py)
# ---------------------------------------------------------------------------
def _apply_inject(inject: Dict[str, Any]) -> None:
    """Test-only fault hooks (documented in tests/test_workers.py):
    ``crash`` exits immediately; ``crash_once_flag`` crashes only if the
    flag file is absent (creating it first, so the retried attempt on
    the replacement worker succeeds); ``sleep_s`` stalls mid-eval to
    exercise the timeout path."""
    if inject.get("crash"):
        os._exit(int(inject.get("exit_code", 41)))
    flag = inject.get("crash_once_flag")
    if flag:
        if not os.path.exists(flag):
            with open(flag, "w") as f:
                f.write("crashed once\n")
            os._exit(int(inject.get("exit_code", 42)))
    if inject.get("sleep_s"):
        time.sleep(float(inject["sleep_s"]))


class _SpecServer:
    """The worker-side spec interpreter: one instance per worker
    *process*, handling eval specs one ``handle(spec) → reply`` call at
    a time.  Platform/cache/store/db handles are memoized per spec
    coordinates so a long-lived process serving many jobs keeps its
    warm jit/eval caches."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._platforms: Dict[str, Platform] = {}
        self._caches: Dict[Tuple, EvalCache] = {}
        self._stores: Dict[Tuple, PatternStore] = {}
        self._dbs: Dict[str, ResultsDB] = {}

    def handle(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        try:
            if spec.get("ping"):
                return {"ok": True, "pong": True, "host": this_host()}
            _apply_inject(spec.get("inject") or {})
            job, scale = job_from_spec(spec)
            pname = spec["platform"]
            with self._lock:
                if pname not in self._platforms:
                    self._platforms[pname] = platform_from_name(pname)
                platform = self._platforms[pname]
                cache = None
                if spec.get("cache"):
                    c = spec["cache"]
                    ck = (c["path"], c.get("ns"), c.get("ttl_s"))
                    if ck not in self._caches:
                        self._caches[ck] = EvalCache(
                            c["path"], namespace=c.get("ns"),
                            ttl_s=c.get("ttl_s"))
                    cache = self._caches[ck]
                patterns = None
                if spec.get("patterns"):
                    ps = spec["patterns"]
                    sk = (ps["path"], ps.get("ns"))
                    if sk not in self._stores:
                        self._stores[sk] = PatternStore.from_spec(ps)
                    patterns = self._stores[sk]
                db = None
                if spec.get("db"):
                    db = self._dbs.setdefault(spec["db"],
                                              ResultsDB(spec["db"]))
            stop_event = threading.Event()
            if spec.get("stop"):
                stop_event.set()
            measure = MeasureConfig.from_dict(spec["measure"]) \
                if spec.get("measure") else None
            pop_cfg = PopulationConfig.from_dict(spec["population"]) \
                if spec.get("population") else None
            res = run_case_job(
                job, platform, campaign_id=spec.get("campaign", ""),
                cache=cache, patterns=patterns, db=db,
                stop_event=stop_event,
                verbose=spec.get("verbose", False), scale=scale,
                measure=measure, lease_path=lease_for_spec(spec),
                population=pop_cfg)
            return {"ok": True, "result": res.to_dict(full=True)}
        except Exception as e:  # noqa: BLE001 — job errors go to scheduler
            import traceback
            return {"ok": False, "type": type(e).__name__,
                    "error": f"{e}"[:1000],
                    "traceback": traceback.format_exc()[-2000:]}


def worker_main() -> int:
    """Line-JSON worker loop over stdio: read an eval spec, run the §3.2
    search for its job, write the full OptResult back."""
    # The pipe to the scheduler is fd 1 at startup.  Everything else the
    # worker (or jax) prints must go to stderr, so dup the protocol fd
    # away and point stdout at stderr.
    proto = os.fdopen(os.dup(1), "w", buffering=1)
    os.dup2(2, 1)
    sys.stdout = os.fdopen(1, "w", buffering=1)

    server = _SpecServer()
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            spec = json.loads(line)
        except ValueError as e:
            reply: Dict[str, Any] = {"ok": False, "type": "ProtocolError",
                                     "error": f"{e}"[:1000]}
        else:
            reply = server.handle(spec)
        proto.write(json.dumps(json_safe(reply), default=str) + "\n")
        proto.flush()
    return 0
