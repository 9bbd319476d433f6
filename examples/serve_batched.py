"""Continuous-batching serving example on a reduced config.

Mixed-length traffic is served two ways:

1. fixed-batch ``generate()`` — everything padded into one rectangle,
2. ``BatchedServer`` — ragged per-slot decode, bucketed packed prefill,
   per-bucket AOT executables built at startup.

The continuous engine's greedy tokens are checked against ``generate()``
per request: the throughput win never changes a single output token.

    PYTHONPATH=src python examples/serve_batched.py [--arch glm4-9b]
"""
import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.serve import BatchedServer, generate


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=10)
    ap.add_argument("--slots", type=int, default=3)
    args = ap.parse_args()

    cfg = dataclasses.replace(get_config(args.arch).reduced(),
                              param_dtype="float32")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    # ragged traffic: chat-style short prompts plus a long-context tail
    rng = np.random.default_rng(0)
    lens = [int(rng.integers(6, 18)) if rng.random() < 0.75
            else int(rng.integers(40, 60)) for _ in range(args.requests)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    longest = max(lens)

    # 1. fixed-batch generate(): one rectangle, padded to the longest
    batch = jnp.asarray(np.stack([np.pad(p, (0, longest - len(p)))
                                  for p in prompts]))
    t0 = time.time()
    out = generate(model, params, batch, max_new=args.max_new)
    dt = time.time() - t0
    print(f"generate(): {out.shape} in {dt:.2f}s "
          f"({out.size / dt:.1f} tok/s, all prompts padded to {longest})")

    # 2. continuous engine: ragged decode + bucketed packed prefill
    srv = BatchedServer(model, params, slots=args.slots, max_len=96)
    print(f"BatchedServer: buckets {srv.buckets}, "
          f"{srv.aot_compiles} AOT executables")
    reqs = [srv.submit(p, max_new=args.max_new) for p in prompts]
    t0 = time.time()
    srv.run(max_steps=2000)
    dt = time.time() - t0
    toks = sum(len(r.tokens) for r in reqs)
    print(f"BatchedServer: {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, ragged lengths {sorted(set(lens))})")

    # greedy equivalence: served tokens == generate() per request
    for r, p in zip(reqs, prompts):
        ref = generate(model, params, jnp.asarray(p[None, :]),
                       max_new=r.max_new)[0]
        assert r.tokens == [int(t) for t in ref[:len(r.tokens)]], \
            f"request {r.rid} diverged"
    print(f"equivalence: all {len(reqs)} requests match generate() "
          f"token for token")


if __name__ == "__main__":
    main()
