#!/usr/bin/env python3
"""Readings that the limits on ``correct`` are set from, many seeds in one
process: for each seed, the weights from that seed, a window of the
cell's own traffic at its own rate, and the numbers the comparison reads
for the program's served tokens and, on the same prompts and tokens, for
the int8 control.  The limits go between the program's largest reading
and the control's smallest (``oracle.py``; ``cells/<workload>.json``).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    from bench import harness, weights
    from bench.spec import Bench
    harness.cache_dir(ROOT)
    bench = Bench()
    cell = bench.cell(args.workload)
    compiles = harness.CompileLog()
    seeds = [int(s) for s in args.seeds.split(",")]
    params, server, parts = harness.start_server(cell, seeds[0], compiles)
    print("setup: " + json.dumps(parts), flush=True)
    for seed in seeds:
        if params is None:
            m = cell.config["model"]
            params = weights.make(cell.family.shapes(m), m, seed)
            server.params = params
        run = harness.serve_window(server, cell, seed=seed,
                                   seconds=args.seconds,
                                   rate=cell.settings["rate_rps"],
                                   trace=False, compiles=compiles)
        served = run.window.served
        row = {"seed": seed, "requests": len(served)}
        for control in (False, True):
            chk = harness.reference_check(cell, params, served, seed,
                                          control=control)
            row["control" if control else "program"] = {
                **chk["readings"], "correct": chk["correct"]}
        print(json.dumps(row), flush=True)
        server.params = None
        params = None
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
