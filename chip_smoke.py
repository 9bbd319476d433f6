#!/usr/bin/env python
"""Chip smoke: the serving path and its Pallas kernels on one TPU.

One process, four phases, in order; the first failed check ends the run
with a non-zero exit code:

1. device — JAX's first device must be a TPU (there is no CPU fallback).
2. kernels — the ops-registry kernels, compiled for the chip (not
   interpreted) at the widths of the configs that use them, in bf16,
   against their references in ``kernels/ref.py``.
3. serving — ``stablelm-3b`` at its published widths, with bf16 weights
   drawn from ``--seed``, served through ``BatchedServer`` with every
   executable compiled ahead of time.  Each request's first token must
   equal ``serve.generate()``'s; later tokens are counted against it, and
   each divergence is printed with the logit gap that explains it.
4. reintegration — the attention case's Pallas build is installed at the
   ``attention`` site through ``core.integrate.install``; the same server
   rebuilds its executables at its next step and serves the prompts again.

Each phase prints its wall time, compile time, compile count and the
device's peak bytes on a line of its own.  The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py [--seed N]

The phases are functions of a config, so the CPU tests run them on
``stablelm-3b``'s ``reduced()`` preset with Pallas interpreted.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time
from typing import Dict, List, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from repro.configs import ModelConfig, get_config   # noqa: E402
from repro.core import integrate                    # noqa: E402
from repro.core.kernelcase import get_case          # noqa: E402
from repro.kernels import ops, ref                  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.moe_gemm import grouped_matmul   # noqa: E402
from repro.kernels.rwkv_wkv import wkv_pallas       # noqa: E402
from repro.kernels.ssd_scan import ssd_pallas       # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import get_model                  # noqa: E402
from repro.models.ssm import mamba_dims             # noqa: E402
from repro.serve import BatchedServer, generate     # noqa: E402

SERVE_ARCH = "stablelm-3b"
# the configs whose widths each registry kernel is checked at
KERNEL_ARCHS = ("stablelm-3b", "glm4-9b", "qwen2-moe-a2.7b", "rwkv6-7b",
                "hymba-1.5b")
TOL_BF16 = 5e-2            # tests/test_kernels.py: TOL[jnp.bfloat16]
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """XLA compiles (a persistent-cache hit counts as one, with its
    retrieval time) and their seconds, from JAX's monitoring events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += secs

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------- kernels --
def _kernel_cases(cfgs: Dict[str, ModelConfig], seq: int, rng):
    """(name, pallas fn, reference fn, bf16 inputs, tolerance) per kernel."""
    bf16 = jnp.bfloat16

    def randn(shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, bf16)

    cases = []
    for arch in ("stablelm-3b", "glm4-9b"):
        c = cfgs[arch]
        H, KV, hd = c.n_heads, c.n_kv_heads, c.resolved_head_dim
        qkv = [randn((1, seq, H, hd)), randn((1, seq, KV, hd)),
               randn((1, seq, KV, hd))]
        cases.append((f"flash_attention[{arch}: {H}/{KV} heads, hd {hd}]",
                      functools.partial(flash_attention, causal=True),
                      functools.partial(ref.attention_ref, causal=True),
                      qkv, TOL_BF16))

    m = cfgs["qwen2-moe-a2.7b"]
    E, K, N = m.moe.n_experts, m.d_model, m.moe.d_ff_expert
    cases.append((f"grouped_matmul[qwen2-moe: {E} experts, {K}->{N}]",
                  grouped_matmul, ref.grouped_matmul_ref,
                  [randn((E, 128, K)), randn((E, K, N))],
                  TOL_BF16 * math.sqrt(K)))   # as tests/test_kernels.py

    r = cfgs["rwkv6-7b"]
    Kh = r.ssm.head_dim
    Hr = r.d_model // Kh
    shp = (1, seq, Hr, Kh)
    cases.append((f"wkv_pallas[rwkv6: {Hr} heads x {Kh}]",
                  wkv_pallas, lambda *a: ref.wkv_ref(*a)[0],
                  [randn(shp, 0.5), randn(shp, 0.5), randn(shp, 0.5),
                   (-jnp.abs(randn(shp)) - 0.01).astype(bf16),
                   randn((Hr, Kh), 0.5)], TOL_BF16))

    h = cfgs["hymba-1.5b"]
    d_in, Hs, P = mamba_dims(h)
    Ns = h.ssm.state_dim
    cases.append((f"ssd_pallas[hymba: inner {d_in} = {Hs} x {P}, "
                  f"state {Ns}]",
                  ssd_pallas, lambda *a: ref.ssd_ref(*a)[0],
                  [randn((1, seq, Hs, P)),
                   (jnp.abs(randn((1, seq, Hs), 0.3)) + 0.01).astype(bf16),
                   randn((Hs,), 0.3), randn((1, seq, Ns)),
                   randn((1, seq, Ns))], TOL_BF16))
    return cases


def kernel_phase(cfgs: Dict[str, ModelConfig], *, seq: int, seed: int,
                 log=print) -> int:
    """Compile each registry kernel ahead of time, run it, and compare it
    with its reference (computed at full f32 matmul precision).  Returns
    the number of kernels checked; a mismatch raises."""
    rng = np.random.default_rng(seed)
    cases = _kernel_cases(cfgs, seq, rng)
    for name, fn, want_fn, args, tol in cases:
        got = jax.jit(fn).lower(*args).compile()(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(want_fn)(*args)
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        log(f"  kernel {name}: max abs err {err:.3e} (tol {tol:.3g})")
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol,
                                   err_msg=name)
    return len(cases)


# ---------------------------------------------------------------- serving --
def make_prompts(vocab: int, buckets: Sequence[int], seed: int
                 ) -> List[np.ndarray]:
    """Eight seeded prompts, two of each of four lengths spread over both
    buckets (a quarter of the small one, each bucket exactly, and midway
    between them), interleaved so every admission wave packs both."""
    b0, b1 = buckets
    lengths = [b0 // 4, b0 + (b1 - b0) // 2, b0, b1] * 2
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in lengths]


def build_model(cfg: ModelConfig, seed: int):
    model = get_model(cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(seed))
    return model, params


def serve_all(server: BatchedServer, prompts, max_new: int
              ) -> List[List[int]]:
    reqs = [server.submit(p, max_new=max_new) for p in prompts]
    server.run(max_steps=100 * len(prompts) * max_new)
    return [list(r.tokens) for r in reqs]


def check_tokens(tokens: List[List[int]], max_new: int, vocab: int) -> None:
    for i, t in enumerate(tokens):
        if len(t) != max_new:
            raise AssertionError(f"request {i} returned {len(t)} tokens, "
                                 f"expected {max_new}")
        if not all(0 <= x < vocab for x in t):
            raise AssertionError(f"request {i} has a token outside "
                                 f"[0, {vocab}): {t}")


def generate_reference(model, params, prompts, max_new: int
                       ) -> List[List[int]]:
    """``generate()`` once per distinct prompt length (equal lengths are
    batched: generate takes a rectangular batch)."""
    out: List[List[int]] = [[] for _ in prompts]
    by_len: Dict[int, List[int]] = {}
    for i, p in enumerate(prompts):
        by_len.setdefault(len(p), []).append(i)
    for idx in by_len.values():
        toks = generate(model, params,
                        jnp.asarray(np.stack([prompts[i] for i in idx])),
                        max_new=max_new)
        for row, i in enumerate(idx):
            out[i] = [int(x) for x in toks[row]]
    return out


def explain_divergence(model, params, prompts, served, want, log) -> None:
    """For each request that differs from generate(), print where, and the
    logits of both candidate tokens from a third, whole-prefix prefill: a
    gap of a bf16 step or two means a near-tie broken by rounding, not a
    different computation."""
    prefill = jax.jit(model.prefill)
    for i, (s, w) in enumerate(zip(served, want)):
        if s == w:
            continue
        j = next(k for k in range(len(w)) if s[k] != w[k])
        prefix = np.concatenate([prompts[i], np.asarray(w[:j], np.int32)])
        logits, _ = prefill(params, jnp.asarray(prefix[None]))
        lg = np.asarray(logits[0, -1, :model.cfg.vocab_size], np.float32)
        top2 = np.sort(lg)[-2:]
        log(f"  DIVERGED request {i} (prompt length {len(prompts[i])}) at "
            f"token {j}: served {s[j]} (logit {lg[s[j]]:.6f}), generate "
            f"{w[j]} (logit {lg[w[j]]:.6f}); top-2 gap of the whole-prefix "
            f"prefill {top2[1] - top2[0]:.6f}, max |logit| "
            f"{np.max(np.abs(lg)):.4f}")


def serving_phase(cfg: ModelConfig, *, seed: int, slots: int, max_len: int,
                  buckets: Sequence[int], max_new: int, compiles: CompileLog,
                  log=print):
    """Serve eight seeded prompts through ``BatchedServer`` (every
    executable compiled ahead of time) and compare the output with
    ``generate()``'s.  Returns (server, prompts, tokens)."""
    model, params = build_model(cfg, seed)
    prompts = make_prompts(cfg.vocab_size, buckets, seed)

    logits, _ = jax.jit(model.prefill)(params, jnp.asarray(prompts[0][None]))
    if not np.all(np.isfinite(np.asarray(logits, np.float32))):
        raise AssertionError("prefill logits are not finite")

    server = BatchedServer(model, params, slots=slots, max_len=max_len,
                           buckets=buckets, telemetry=ops.Telemetry())
    n_exec = len(server._exec)
    log(f"  executables {n_exec}, aot_compiles {server.aot_compiles}")
    if server.aot_compiles != n_exec:
        raise AssertionError(f"{server.aot_compiles} AOT compiles for "
                             f"{n_exec} executables")

    before = compiles.count
    tokens = serve_all(server, prompts, max_new)
    if compiles.count != before or server.aot_compiles != n_exec:
        raise AssertionError(f"{compiles.count - before} compiles while "
                             f"serving")
    check_tokens(tokens, max_new, cfg.vocab_size)

    want = generate_reference(model, params, prompts, max_new)
    n_same = sum(a == b for s, w in zip(tokens, want) for a, b in zip(s, w))
    log(f"  tokens equal to generate(): {n_same}/{len(prompts) * max_new}")
    explain_divergence(model, params, prompts, tokens, want, log)
    # Only each request's first token is held to generate()'s.  The two
    # paths compute the same thing in different XLA programs, and with
    # random weights the bf16 logits are nearly flat: on a TPU v5e at
    # seed 0, 112 of 128 tokens agree, and every later divergence is a
    # near-tie whose top-2 gap is one or two bf16 steps (CHANGES.md).
    first = [t[0] for t in tokens]
    if first != [w[0] for w in want]:
        raise AssertionError(f"first tokens {first} differ from "
                             f"generate()'s {[w[0] for w in want]}")
    return server, prompts, tokens


def reintegration_phase(server: BatchedServer, prompts, base_tokens, *,
                        max_new: int, log=print) -> int:
    """Install the attention case's Pallas build at the ``attention`` site
    and serve the prompts again on the same server: its next step must
    rebuild every executable ahead of time, once.  Returns how many
    tokens agree with the jnp path."""
    case = get_case("attention_prefill")
    variant = dict(case.baseline_variant, block_q=128, block_k=128)
    epochs, compiled = server.swap_epochs, server.aot_compiles
    n_exec = len(server._exec)
    integrate.install(case, variant, impl="pallas")
    try:
        tokens = serve_all(server, prompts, max_new)
    finally:
        integrate.uninstall(case)
    if server.swap_epochs != epochs + 1:
        raise AssertionError(f"swap_epochs went {epochs} -> "
                             f"{server.swap_epochs}, expected one rebuild")
    if server.aot_compiles != compiled + n_exec:
        raise AssertionError(f"{server.aot_compiles - compiled} AOT "
                             f"compiles for {n_exec} executables")
    check_tokens(tokens, max_new, server.model.cfg.vocab_size)
    agree = sum(a == b for s, w in zip(tokens, base_tokens)
                for a, b in zip(s, w))
    log(f"  tokens equal to the jnp path: {agree}/{len(prompts) * max_new}")
    return agree


# ------------------------------------------------------------------- main --
class _Phase:
    """Prints one phase's wall time, compile time and count, AOT compiles
    and the device's peak bytes (counts, not metrics)."""

    def __init__(self, name: str, compiles: CompileLog, dev):
        self.name, self.compiles, self.dev = name, compiles, dev
        self.aot_compiles = 0

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = (self.compiles.count, self.compiles.seconds)
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            stats = self.dev.memory_stats() or {}
            print(f"[{self.name}] wall {time.perf_counter() - self.t0:.3f} s,"
                  f" compile {self.compiles.seconds - self.c0[1]:.3f} s in "
                  f"{self.compiles.count - self.c0[0]} XLA compiles, "
                  f"aot_compiles {self.aot_compiles}, peak_bytes_in_use "
                  f"{stats.get('peak_bytes_in_use')}", flush=True)
        return False


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    compiles = CompileLog()
    t_all = time.perf_counter()
    with _Phase("kernels", compiles, dev):
        kernel_phase({a: get_config(a) for a in KERNEL_ARCHS}, seq=1024,
                     seed=args.seed)
    with _Phase("serving", compiles, dev) as ph:
        server, prompts, tokens = serving_phase(
            get_config(SERVE_ARCH), seed=args.seed, slots=4, max_len=1024,
            buckets=(128, 512), max_new=16, compiles=compiles)
        ph.aot_compiles = server.aot_compiles
    with _Phase("reintegration", compiles, dev) as ph:
        aot0 = server.aot_compiles
        reintegration_phase(server, prompts, tokens, max_new=16)
        ph.aot_compiles = server.aot_compiles - aot0
    print(f"[total] wall {time.perf_counter() - t_all:.3f} s, compile "
          f"{compiles.seconds:.3f} s in {compiles.count} XLA compiles",
          flush=True)
    compiles.close()

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
