"""A tiny cell for the CPU tests: a copy of the benchmark tree with a
two-layer configuration, a short mix and a cell of its own added as new
files and entries, exactly as a later change would add them."""
from __future__ import annotations

import copy
import json
import os
import shutil

from bench.spec import BENCH_DIR, ROOT, Bench

TINY_MODEL = {
    "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 512,
    "rope_theta": 10000.0, "partial_rotary": 0.5, "act": "swiglu",
    "qkv_bias": True, "tie_embeddings": False, "norm_eps": 1e-5,
    "param_dtype": "bfloat16",
}
TINY_CONFIG = {
    "name": "tiny", "source": "https://example.org/tiny", "arch":
    "glm4-9b", "reference": "dense", "config": {"num_layers": 2},
    "model": TINY_MODEL, "keymap": {"n_layers": "num_layers"},
    "reduced": [], "published": {}, "assumed": {}, "deployment": "test",
    "departures": [],
}
TINY_TRAFFIC = {
    "arrivals": {"kind": "poisson"},
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 30},
    "max_new": {"dist": "uniform", "min": 2, "max": 12},
}
TINY_CELL = {
    "slots": 4, "max_len": 48, "buckets": [16, 32], "rate_rps": 8.0,
    "check": {"min_tokens": 24, "min_requests": 3,
              "limits": {"widest_gap": 0.05, "mean_gap": 0.005}},
}
WORKLOAD = "tiny.chat"


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(tmp: str, *, cell=None) -> Bench:
    """Copy the benchmark tree under ``tmp`` and add the tiny cell."""
    root = os.path.join(tmp, "checkout")
    bench_dir = os.path.join(root, "bench")
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec = copy.deepcopy(spec)
    spec["configs"].append({"name": "tiny", "source": TINY_CONFIG["source"],
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": WORKLOAD, "config": "tiny",
                              "traffic": "tiny_chat", "chips": 1,
                              "why": "test"})
    _write(os.path.join(root, "BENCHMARK.json"), spec)
    _write(os.path.join(bench_dir, "configs", "tiny.json"), TINY_CONFIG)
    _write(os.path.join(bench_dir, "traffic", "tiny_chat.json"),
           TINY_TRAFFIC)
    _write(os.path.join(bench_dir, "cells", WORKLOAD + ".json"),
           cell or TINY_CELL)
    return Bench(root=root, bench_dir=bench_dir)
