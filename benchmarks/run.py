"""Benchmark runner: one function per paper table, on the campaign engine.

Prints ``name,us_per_call,derived`` CSV per kernel plus per-table averages,
and writes the aggregate JSON next to the dry-run results.

  PYTHONPATH=src python -m benchmarks.run [--tables 1,2,3,4] [--full]
                                          [--workers N] [--executor KIND]
                                          [--out results/bench.json]

``--workers N`` sets the evaluation-fabric width and ``--executor``
picks the transport (inprocess | subprocess | local-cluster); table 6
(``--tables 6``) is the worker-fabric demonstration — in-process vs
subprocess equivalence plus the wall-clock scaling table, written to
``results/workers_demo.json``.

``--full`` (or REPRO_BENCH_FULL=1) uses the paper's parameters
(D=6/10, N=3/5, R=30, k=3); default CI mode keeps the suite minutes-scale.
A shared PatternStore flows Table1 -> Table2 -> Table3 -> Table4,
reproducing the paper's cross-kernel and cross-platform Performance
Pattern Inheritance, and a shared EvalCache (persisted as JSONL next to
``--out``) guarantees that re-running a table against the same results
database never rebuilds/re-checks/re-times a variant it has already
evaluated.  The output JSON is stamped with the git SHA, platform name,
and campaign wall-clock so BENCH_*.json snapshots are comparable across
PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import platform as _platform
import subprocess
import time


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tables", default="1,2,3,4")
    ap.add_argument("--full", action="store_true",
                    help="paper iteration parameters (slow)")
    ap.add_argument("--out", default="results/bench.json")
    ap.add_argument("--jobs", "--workers", dest="workers", type=int,
                    default=None,
                    help="evaluation-fabric width "
                         "(default: env/platform policy)")
    ap.add_argument("--executor", default=None,
                    choices=["inprocess", "subprocess", "local-cluster"],
                    help="evaluation transport (default: in-process; "
                         "REPRO_CAMPAIGN_EXECUTOR overrides)")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the persistent evaluation cache")
    ap.add_argument("--patterns", default=None, metavar="PATH",
                    help="persistent Performance Pattern Inheritance "
                         "store (JSONL journal; shared with subprocess/"
                         "cluster workers).  Default: patterns.jsonl "
                         "next to --out; 'none' keeps the store in "
                         "memory only")
    ap.add_argument("--fixed-r", action="store_true",
                    help="disable the adaptive measurement engine: every "
                         "timing pays the full eq. 3 R cap (no CI early "
                         "stop, no incumbent racing)")
    ap.add_argument("--ci-rel", type=float, default=None, metavar="X",
                    help="adaptive stop threshold: end a timing once the "
                         "CI half-width falls under X x the trimmed mean "
                         "(default: engine default, 0.05)")
    ap.add_argument("--no-race", action="store_true",
                    help="keep adaptive reps but disable incumbent racing")
    ap.add_argument("--pop-size", type=int, default=None,
                    help="table 11: population size (individuals kept)")
    ap.add_argument("--pop-generations", type=int, default=None,
                    help="table 11: generation cap")
    ap.add_argument("--pop-per-persona", type=int, default=None,
                    help="table 11: candidates per expert per wave")
    ap.add_argument("--no-migrate", action="store_true",
                    help="table 11: disable island migration through "
                         "the PatternStore")
    args = ap.parse_args()
    if args.full:
        os.environ["REPRO_BENCH_FULL"] = "1"

    from repro.core import (EvalCache, MeasureConfig, PatternStore,
                            PopulationConfig, ResultsDB)
    from benchmarks.common import BenchContext
    from benchmarks import (perf_hillclimb, table1_polybench_a,
                            table2_polybench_b, table3_appsdk,
                            table4_hotspots, table5_serve, table6_workers,
                            table7_ppi, table8_measure,
                            table10_diagnosis, table11_population)

    measure = None
    if args.fixed_r or args.ci_rel is not None or args.no_race:
        measure = MeasureConfig(
            adaptive=not args.fixed_r,
            ci_rel=args.ci_rel if args.ci_rel is not None
            else MeasureConfig.ci_rel,
            race=not (args.fixed_r or args.no_race))

    population = None
    if args.pop_size or args.pop_generations or args.pop_per_persona \
            or args.no_migrate:
        base = PopulationConfig()
        population = PopulationConfig(
            size=args.pop_size or base.size,
            generations=args.pop_generations or base.generations,
            per_persona=args.pop_per_persona or base.per_persona,
            migrate=not args.no_migrate)

    if args.out:
        res_dir = os.path.dirname(args.out) or "."
        os.makedirs(res_dir, exist_ok=True)
        cache = None if args.no_cache else EvalCache(
            os.path.join(res_dir, "evalcache.jsonl"))
        pat_path = args.patterns
        if not pat_path:
            pat_path = os.path.join(res_dir, "patterns.jsonl")
            legacy = os.path.join(res_dir, "patterns.json")
            if not os.path.exists(pat_path) and os.path.exists(legacy):
                # results dir from before the journal store: keep the
                # learned patterns (migration rewrites it in place)
                pat_path = legacy
        store = PatternStore() if args.patterns == "none" \
            else PatternStore(pat_path)
        ctx = BenchContext(
            store=store,
            cache=cache,
            db=ResultsDB(os.path.join(res_dir, "campaign.jsonl")),
            max_workers=args.workers, executor=args.executor,
            measure=measure, population=population)
    else:           # --out '': leave no state on disk
        cache = None if args.no_cache else EvalCache()
        store = PatternStore(args.patterns) \
            if args.patterns and args.patterns != "none" else PatternStore()
        ctx = BenchContext(store=store, cache=cache,
                           max_workers=args.workers, executor=args.executor,
                           measure=measure, population=population)

    tables = {
        "1": ("table1_polybench_a", table1_polybench_a.main),
        "2": ("table2_polybench_b", table2_polybench_b.main),
        "3": ("table3_appsdk", table3_appsdk.main),
        "4": ("table4_hotspots", table4_hotspots.main),
        "5": ("table5_serve_autotune", table5_serve.main),
        "6": ("table6_workers", table6_workers.main),
        "7": ("table7_ppi", table7_ppi.main),
        "8": ("table8_measure", table8_measure.main),
        "10": ("table10_diagnosis", table10_diagnosis.main),
        "11": ("table11_population", table11_population.main),
        "hillclimb": ("perf_hillclimb", perf_hillclimb.main),
    }
    table_ids = [t.strip() for t in args.tables.split(",")]
    for tid in table_ids:
        if tid not in tables:
            ap.error(f"unknown table {tid!r}; choose from "
                     f"{','.join(sorted(tables))}")
    results = {}
    t0 = time.time()
    for tid in table_ids:
        name, fn = tables[tid]
        print(f"== {name} ==", flush=True)
        results[name] = fn(ctx)
    results["wall_s"] = round(time.time() - t0, 1)
    results["patterns_learned"] = len(ctx.store)
    # provenance stamp: BENCH_*.json snapshots comparable across PRs
    results["meta"] = {
        "git_sha": _git_sha(),
        "platform": _platform.platform(),
        "python": _platform.python_version(),
        "campaign_wall_s": results["wall_s"],
        "full": os.environ.get("REPRO_BENCH_FULL", "0") == "1",
    }
    if cache:
        stats = cache.stats()
        results["evalcache"] = stats
        where = "persisted" if cache.path else "in-memory"
        print(f"# evalcache: {stats['hits']} hits / {stats['misses']} misses "
              f"this run ({stats['entries']} entries, {where})", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=str)
    print(f"# done in {results['wall_s']}s; patterns learned: "
          f"{len(ctx.store)}")


if __name__ == "__main__":
    main()
