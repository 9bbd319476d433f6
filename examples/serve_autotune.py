"""Online serving autotune demo: live traffic + background campaigns.

A reduced-config model serves continuous-batching traffic while a
``ServeAutotuner`` thread watches the per-site telemetry.  The server
tags every prefill/decode event with the request's prefill bucket, so
the autotuner sees each ``(site, bucket)`` pair as its own hotspot —
campaign keys look like ``attention@b16`` — and re-optimizes each
bucket's traffic at that bucket's observed scale.  Winners hot-swap into
the ops registry through guarded installs (FE-checked at the observed
scale, auto-rollback on regression); the server picks each swap up at a
step boundary — watch the ``swap epochs`` counter — without interrupting
in-flight requests.

    PYTHONPATH=src python examples/serve_autotune.py [--arch glm4-9b]
                                                     [--requests 8]
"""
import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    from repro.core import (EvalCache, MEPConstraints, OptConfig,
                            PatternStore, ResultsDB, TPUModelPlatform)
    from repro.kernels import ops
    from repro.serve import AutotuneConfig, BatchedServer, ServeAutotuner

    cfg = dataclasses.replace(get_config(args.arch).reduced(),
                              param_dtype="float32")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ops.clear_all()
    ops.telemetry.reset()
    srv = BatchedServer(model, params, slots=3, max_len=64)
    print(f"server: buckets {srv.buckets}, {srv.aot_compiles} AOT "
          f"executables")

    tuner = ServeAutotuner(
        TPUModelPlatform(),
        config=AutotuneConfig(
            interval_s=0.5, min_tokens=16,
            opt=OptConfig(d_rounds=2, n_candidates=3, r=5, k=1),
            constraints=MEPConstraints(r=5, k=1, t_max_s=2.0),
            probe_r=2, probe_k=0, max_regression=20.0),
        cache=EvalCache(), db=ResultsDB("results/serve_autotune.jsonl"),
        patterns=PatternStore(), verbose=True)
    tuner.start()

    rng = np.random.default_rng(0)

    def serve_wave(n):
        # ragged traffic across two prefill buckets: short chat prompts
        # and a longer-context tail
        reqs = [srv.submit(rng.integers(
                    0, cfg.vocab_size,
                    int(rng.integers(6, 14)) if i % 2 else
                    int(rng.integers(20, 30))).astype(np.int32),
                           max_new=args.max_new)
                for i in range(n)]
        t0 = time.time()
        srv.run(max_steps=2000)
        dt = time.time() - t0
        toks = sum(len(r.tokens) for r in reqs)
        print(f"wave: {sum(r.done for r in reqs)}/{n} requests, {toks} "
              f"tokens, {dt:.2f}s ({toks / dt:.1f} tok/s), "
              f"{srv.swap_epochs} swap epochs so far", flush=True)

    # wave 1 builds up per-bucket telemetry; then give the background
    # loop room to finish a campaign + guarded install; wave 2 serves
    # through the swap
    serve_wave(args.requests)
    print(f"bucket traffic: "
          f"{ops.telemetry.site_buckets('attention')} tokens/bucket")
    deadline = time.time() + 120
    while time.time() < deadline and not any(r.installed or r.rolled_back
                                             for r in tuner.reports):
        time.sleep(0.2)
    serve_wave(args.requests)
    tuner.stop()
    print(f"tuned (site@bucket -> scale): {tuner.tuned_scales}")
    for rep in tuner.reports:
        for swap in rep.swaps:
            print(f"cycle {rep.cycle}: {swap.site} -> {swap.variant} "
                  f"[{swap.reason}] gen {swap.generation_before}->"
                  f"{swap.generation}")
    active = {site: ops.active_entry(site).info.get("variant")
              for site in ops.active_sites()}
    print(f"active installs: {active or 'none'}")


if __name__ == "__main__":
    main()
