"""Shared benchmark machinery: one function per paper table, all driven
through the campaign engine.

Each table submits its whole suite to a ``Campaign`` — the heuristic
(iterative, paper §3.2) and direct (one-shot baseline) jobs for every
kernel — and reports the paper's three indicators: Standalone speedup
(in the MEP), Integrated speedup (kernel reinstalled in the application
/ composite context), and Direct LLM Optimization (one-shot, no feedback
loop).  A ``BenchContext`` threads the shared PatternStore, EvalCache,
and ResultsDB through the tables, so cross-table Performance Pattern
Inheritance and cross-run evaluation caching both happen automatically.

CSV rows: ``name,us_per_call,derived`` where ``us_per_call`` is the
optimized kernel's trimmed-mean time and ``derived`` carries the speedups.
``--full`` uses the paper's parameters (D=6/10, N=3/5, R=30, k=3); the
default CI mode shrinks R/D so the whole suite stays minutes-scale.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional

from repro.core import (Campaign, CaseJob, DirectProposer, EvalCache,
                        HeuristicProposer, MeasureConfig, MEPConstraints,
                        OptConfig, PatternStore, PopulationConfig,
                        ResultsDB)

FULL = os.environ.get("REPRO_BENCH_FULL", "0") == "1"


def params_for(suite: str):
    """Paper's iteration parameters: PolyBench D=6,N=3; others D=10,N=5;
    R=30,k=3.  CI mode: R=5,k=1 and half the rounds."""
    d, n = (6, 3) if suite == "polybench" else (10, 5)
    if FULL:
        return OptConfig(d_rounds=d, n_candidates=n, r=30, k=3), \
            MEPConstraints(r=30, k=3, t_max_s=30.0)
    return OptConfig(d_rounds=max(2, d // 2), n_candidates=n, r=5, k=1), \
        MEPConstraints(r=5, k=1, t_max_s=2.0)


@dataclass
class BenchContext:
    """Shared campaign state flowing through the tables."""
    store: PatternStore
    cache: Optional[EvalCache] = None
    db: Optional[ResultsDB] = None
    max_workers: Optional[int] = None
    executor: Optional[str] = None   # inprocess | subprocess | local-cluster
    measure: Optional[MeasureConfig] = None   # adaptive-engine policy
    # population-search policy (table 11; None → each table's default /
    # the greedy loop elsewhere)
    population: Optional[PopulationConfig] = None

    def campaign(self, platform) -> Campaign:
        # --workers applies to measured platforms too: their wall-clock
        # slices serialize on the campaign's timing lease, so fan-out no
        # longer threatens eq. 3
        return Campaign(platform, patterns=self.store, cache=self.cache,
                        db=self.db, max_workers=self.max_workers,
                        executor=self.executor, measure=self.measure,
                        verbose=True)


def ensure_ctx(ctx) -> BenchContext:
    """Accept a BenchContext, a bare PatternStore (legacy call sites), or
    None (standalone table run)."""
    if ctx is None:
        return BenchContext(PatternStore())
    if isinstance(ctx, PatternStore):
        return BenchContext(ctx)
    return ctx


@dataclass
class Row:
    name: str
    us_per_call: float
    standalone: float
    integrated: Optional[float]
    direct: float
    cache_hits: int = 0

    def csv(self) -> str:
        integ = f"{self.integrated:.2f}" if self.integrated else ""
        return (f"{self.name},{self.us_per_call:.2f},"
                f"standalone={self.standalone:.2f}x integrated={integ}x "
                f"direct={self.direct:.2f}x")


def run_suite(suite: str, platform, ctx, *,
              integrated_fn=None, seed: int = 0) -> List[Row]:
    ctx = ensure_ctx(ctx)
    cfg, cons = params_for(suite)
    direct_cfg = OptConfig(d_rounds=1, n_candidates=1, r=cfg.r, k=cfg.k,
                           fe_input_sets=cfg.fe_input_sets)
    suite_cases = _suite_cases(suite)
    jobs: List[CaseJob] = []
    for case in suite_cases:
        jobs.append(CaseJob(case, HeuristicProposer(seed, ctx.store,
                                                    platform.name),
                            cfg=cfg, constraints=cons, seed=seed))
        jobs.append(CaseJob(case, DirectProposer(), cfg=direct_cfg,
                            constraints=cons, seed=seed,
                            label=f"{case.name}#direct"))
    results = ctx.campaign(platform).run(jobs)
    rows: List[Row] = []
    for i, case in enumerate(suite_cases):
        res, direct = results[2 * i], results[2 * i + 1]
        integ = integrated_fn(case, res) if integrated_fn else None
        rows.append(Row(case.name, res.best_time_s * 1e6, res.speedup,
                        integ, direct.speedup,
                        cache_hits=res.cache_hits + direct.cache_hits))
        print(rows[-1].csv(), flush=True)
    return rows


def _suite_cases(suite: str):
    from repro.core import cases
    return cases(suite)


def summarize(table: str, rows: List[Row]) -> Dict:
    import numpy as np
    avg = lambda xs: float(np.mean([x for x in xs if x])) if any(xs) else 0.0
    rec = {
        "table": table,
        "avg_standalone": avg([r.standalone for r in rows]),
        "avg_integrated": avg([r.integrated for r in rows]),
        "avg_direct": avg([r.direct for r in rows]),
        "cache_hits": int(sum(r.cache_hits for r in rows)),
        "rows": [r.csv() for r in rows],
    }
    print(f"# {table}: avg standalone {rec['avg_standalone']:.2f}x, "
          f"integrated {rec['avg_integrated']:.2f}x, "
          f"direct {rec['avg_direct']:.2f}x", flush=True)
    return rec
