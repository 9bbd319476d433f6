#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Earlier lines give the set-up's parts, how late the generator
ran, and the readings of the comparison with the reference; the last
lines on standard error, and the result's ``check`` key, give each number
compared beside its limit.  The run fails, with no result line, where
JAX's first device is not a TPU or there are fewer chips than the cell
asks for: there is no CPU fallback.

``--control`` judges, in the server's place, the plain reference computed
in int8 on the same prompts and served tokens: the control that the
limits on ``correct`` are set against, which has to come out not
correct.  The benchmark's own runs never pass it.
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


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench.spec import Bench
    bench = Bench()
    cell = bench.cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, but JAX's first device is "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX has "
              f"{len(devs)}", file=sys.stderr)
        return 2
    from bench import harness
    harness.cache_dir(ROOT)
    out = harness.run_cell(bench, args.workload, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           t_process=T_PROCESS, control=args.control,
                           log=lambda s: print(s, flush=True))
    lines = out.pop("_check_lines")
    print(json.dumps(out), flush=True)
    for line in lines:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
