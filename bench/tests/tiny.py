"""A tiny cell for the CPU tests: a copy of the benchmark tree with a
two-layer configuration, a short mix and a cell of its own added as new
files and entries, exactly as a later change would add them; and, the
same way, a tiny mixture-of-experts family with its own configuration,
mix and cell (``add_moe``)."""
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

# A family of its own: two layers of 8 experts, top-2, one shared expert.
# A capacity factor of 4.0 gives each expert room for every token of a
# prefill (``top_k * 4.0 / n_experts`` = 1), so the program drops none.
TINY_MOE_MODEL = dict(
    TINY_MODEL, family="moe", d_ff=32,
    moe={"n_experts": 8, "top_k": 2, "d_ff_expert": 32, "n_shared": 1,
         "d_ff_shared": 64, "capacity_factor": 4.0})
TINY_MOE_CONFIG = dict(
    TINY_CONFIG, name="tiny_moe", source="https://example.org/tiny-moe",
    arch="qwen2-moe-a2.7b", reference="tiny_moe",
    config={"num_layers": 2, "num_experts_per_tok": 2},
    model=TINY_MOE_MODEL,
    keymap={"n_layers": "num_layers", "moe.top_k": "num_experts_per_tok"})
MOE_WORKLOAD = "tiny_moe.chat"
MOE_FAMILY = os.path.join(os.path.dirname(__file__), "tiny_moe.py")


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


def add_moe(bench: Bench) -> Bench:
    """Add the tiny MoE family's module, configuration, mix and cell to
    the copy ``make`` made: new files and new entries only."""
    root = bench.root
    shutil.copyfile(MOE_FAMILY, os.path.join(bench.dir, "references",
                                             "tiny_moe.py"))
    _write(os.path.join(bench.dir, "configs", "tiny_moe.json"),
           TINY_MOE_CONFIG)
    _write(os.path.join(bench.dir, "traffic", "tiny_moe_chat.json"),
           TINY_TRAFFIC)
    _write(os.path.join(bench.dir, "cells", MOE_WORKLOAD + ".json"),
           TINY_CELL)
    bench.spec["configs"].append({
        "name": "tiny_moe", "source": TINY_MOE_CONFIG["source"],
        "file": "bench/configs/tiny_moe.json", "reduced": [],
        "why": "test"})
    bench.spec["workloads"].append({
        "name": MOE_WORKLOAD, "config": "tiny_moe",
        "traffic": "tiny_moe_chat", "chips": 1, "why": "test"})
    _write(os.path.join(root, "BENCHMARK.json"), bench.spec)
    return bench
