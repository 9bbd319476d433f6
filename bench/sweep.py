#!/usr/bin/env python3
"""Find a cell's knee once: serve its traffic at several fixed rates, each
on several seeds (the order of the same sizes and gaps), in one process,
and report for each the offered and completed output tokens per second,
the TTFT tails of the first and second half of the window, and the
backlog at its end.  A rate is sustained where, on every seed, both
halves' TTFT p90 stay under ``TTFT_LIMIT_MS`` and every request
finishes in the drain: a queue that grows, or a clump of arrivals that
fills every slot, reads seconds there.  The knee is the highest rate
sustained with every rate below it; a cell is then set at about four
fifths of it (``rate_rps`` in ``cells/<workload>.json``).

    python3 bench/sweep.py --workload <cell> --rates 1.5,2,2.5 \
        --seeds 1,2,3 --seconds 30
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# A chat user's first-token target, and about ten times an unqueued TTFT
# (a decode step and a prefill); a slot-full clump reads seconds.
TTFT_LIMIT_MS = 500.0


def slowest_steps(run, k=5):
    """The ``k`` longest steps of the window: seconds into it, duration,
    and the prompt lengths it prefilled."""
    starts = run.window.step_starts
    dur = [(b - a, i) for i, (a, b) in enumerate(zip(starts, starts[1:]))]
    out = []
    for d, i in sorted(dur, reverse=True)[:k]:
        prompts = [len(s.prompt) for s in run.window.served
                   if s.tokens.steps and s.tokens.steps[0] == i]
        out.append([round(starts[i] - run.t0, 3), round(d, 3), prompts])
    return out


def one_rate(server, cell, rate, seconds, seed, compiles):
    from bench import harness, stats
    run = harness.serve_window(server, cell, seed=seed, seconds=seconds,
                               rate=rate, trace=False, compiles=compiles,
                               drain_s=30.0)
    served = run.window.served
    half = run.t0 + seconds / 2
    ttft = {"first": [], "second": []}
    for s in served:
        if s.tokens.times:
            ttft["first" if s.due < half else "second"].append(
                s.tokens.times[0] - s.due)
    offered = sum(s.max_new for s in served) / seconds
    done_in_window = stats.tokens_in_window(
        [s.tokens.times for s in served], run.t0, run.t0 + seconds)
    backlog = sum(1 for s in served if s.tokens.steps and
                  s.tokens.steps[0] >= run.steps[1]) + \
        sum(1 for s in served if not s.tokens.steps)
    out = {"rate": rate, "requests": len(served),
           "offered_tok_s": offered,
           "completed_tok_s": done_in_window / seconds,
           "ttft_p90_ms_first_half": 1e3 * stats.percentile(
               ttft["first"], 90) if ttft["first"] else None,
           "ttft_p90_ms_second_half": 1e3 * stats.percentile(
               ttft["second"], 90) if ttft["second"] else None,
           "waiting_at_close": backlog,
           "unfinished_after_drain": sum(not s.req.done for s in served),
           "slowest_steps": slowest_steps(run)}
    # anything left after the drain is dropped before the next rate
    server.queue.clear()
    server.active = [None] * server.slots
    server.pos[:] = 0
    return out


def sustained(row, ttft_limit_ms: float) -> bool:
    halves = (row["ttft_p90_ms_first_half"], row["ttft_p90_ms_second_half"])
    return row["unfinished_after_drain"] == 0 and all(
        h is not None and h <= ttft_limit_ms for h in halves)


def knee(rows, ttft_limit_ms: float):
    """The highest rate sustained on every seed, with every rate below
    it; None where the lowest rate is not."""
    best = None
    for rate in sorted({r["rate"] for r in rows}):
        if not all(sustained(r, ttft_limit_ms) for r in rows
                   if r["rate"] == rate):
            break
        best = rate
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from bench import harness
    from bench.spec import Bench
    harness.cache_dir(ROOT)
    cell = Bench().cell(args.workload)
    compiles = harness.CompileLog()
    _, server, parts = harness.start_server(cell, seeds[0], compiles)
    print("setup: " + json.dumps(parts), flush=True)
    print("memory: " + json.dumps(jax.devices()[0].memory_stats()),
          flush=True)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            row = dict(one_rate(server, cell, rate, args.seconds, seed,
                                compiles), seed=seed)
            row["sustained"] = sustained(row, TTFT_LIMIT_MS)
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(f"knee: {knee(rows, TTFT_LIMIT_MS)} req/s (TTFT p90 limit "
          f"{TTFT_LIMIT_MS} ms, seeds {args.seeds})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
