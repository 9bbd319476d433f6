"""The work a step needs, from the shapes: it agrees with the program's
own parameter count and with the weights the benchmark makes."""
import json
import math
import os

import pytest

from bench import flops, weights
from bench.harness import build_model_config
from bench.spec import BENCH_DIR
from bench.tests import tiny


def _config(name):
    if name == "tiny":             # grouped KV heads and QKV biases
        return tiny.TINY_CONFIG
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["stablelm-3b", "tiny"])
def test_counts_agree_with_param_counts(name):
    c = _config(name)
    m = c["model"]
    total, active = build_model_config(m, c["arch"]).param_counts()
    emb = weights.padded_vocab(m) * m["d_model"]
    assert total == active == m["n_layers"] * flops.layer_params(m) + 2 * emb
    # the benchmark's weights are those, plus the final norm
    table = weights.shapes(m)
    specs = list(table.pop("layers").values()) + list(table.values())
    assert sum(math.prod(shape) for shape, _ in specs) == \
        total + m["d_model"]


@pytest.mark.parametrize("name", ["stablelm-3b", "tiny"])
def test_step_work(name):
    m = _config(name)["model"]
    per_token = 2 * m["n_layers"] * flops.layer_matmul_params(m)
    head = 2 * m["d_model"] * m["vocab_size"]
    attn = 4 * m["n_layers"] * m["n_heads"] * flops.head_dim(m)
    assert flops.decode_flops(m, 100) == per_token + head + 100 * attn
    assert flops.prefill_flops(m, 3) == 3 * per_token + head + 6 * attn
    kv = 2 * 2 * m["n_layers"] * m["n_kv_heads"] * flops.head_dim(m)
    assert flops.kv_bytes_per_token(m) == kv
    one = flops.decode_step_bytes(m, [10])
    two = flops.decode_step_bytes(m, [10, 30])
    assert two - one == kv * 31 + 2 * m["d_model"]
    assert one > flops.weight_bytes(m) > 2 * m["n_layers"] * \
        flops.layer_matmul_params(m)


def test_published_sizes():
    s = _config("stablelm-3b")["model"]
    assert flops.weight_bytes(s) == pytest.approx(5.33e9, rel=0.01)
    assert flops.kv_bytes_per_token(s) == 327680       # 320 KiB a token
