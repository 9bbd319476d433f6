"""The work a step needs, from the shapes: the dense family's counts
(``references/dense.py``) agree with the program's own parameter count
and with the weights the benchmark makes, and read what they read before
the counts and the table moved there."""
import hashlib
import json
import math
import os

import numpy as np
import pytest

from bench import flops, weights
from bench.harness import build_model_config
from bench.references import dense
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
    assert total == active == m["n_layers"] * dense.layer_params(m) + 2 * emb
    # the benchmark's weights are those, plus the final norm
    table = dense.shapes(m)
    specs = list(table.pop("layers").values()) + list(table.values())
    assert sum(math.prod(shape) for shape, _ in specs) == \
        total + m["d_model"]


@pytest.mark.parametrize("name", ["stablelm-3b", "tiny"])
def test_step_work(name):
    m = _config(name)["model"]
    per_token = 2 * m["n_layers"] * dense.layer_matmul_params(m)
    head = 2 * m["d_model"] * m["vocab_size"]
    attn = 4 * m["n_layers"] * m["n_heads"] * flops.head_dim(m)
    assert dense.decode_flops(m, 100) == per_token + head + 100 * attn
    assert dense.prefill_flops(m, 3) == 3 * per_token + head + 6 * attn
    kv = 2 * 2 * m["n_layers"] * m["n_kv_heads"] * flops.head_dim(m)
    assert flops.kv_bytes_per_token(m) == kv
    one = dense.decode_step_bytes(m, [10])
    two = dense.decode_step_bytes(m, [10, 30])
    assert two - one == kv * 31 + 2 * m["d_model"]
    assert one > dense.weight_bytes(m) > 2 * m["n_layers"] * \
        dense.layer_matmul_params(m)


def test_published_sizes():
    s = _config("stablelm-3b")["model"]
    assert dense.weight_bytes(s) == pytest.approx(5.33e9, rel=0.01)
    assert flops.kv_bytes_per_token(s) == 327680       # 320 KiB a token


def test_stablelm_counts_are_unchanged():
    """The four counts the roofline readers divide by, as they read
    before the family module held them."""
    s = _config("stablelm-3b")["model"]
    assert dense.weight_bytes(s) == 5332997120
    assert dense.decode_step_bytes(s, [128] * 16) == 6009410560
    assert dense.decode_flops(s, 256) == 5416550400
    assert dense.prefill_flops(s, 128) == 652576686080


def test_stablelm_table_is_unchanged():
    L, d, f, v = 32, 2560, 6912, 50432
    w, w2 = d ** -0.5, f ** -0.5
    assert dense.shapes(_config("stablelm-3b")["model"]) == {
        "layers": {"ln1": ((L, d), 0.0), "ln2": ((L, d), 0.0),
                   "wq": ((L, d, d), w), "wk": ((L, d, d), w),
                   "wv": ((L, d, d), w), "wo": ((L, d, d), w),
                   "w1": ((L, d, f), w), "w3": ((L, d, f), w),
                   "w2": ((L, f, d), w2)},
        "embed": ((v, d), 1.0), "final_ln": ((d,), 0.0),
        "lm_head": ((d, v), w)}


def _digest(tree):
    import jax
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,digest", [
    (0, "23ee5af3d068cb9263c3652ffecc1f7ed66b940dbdd5d9edb8e84712d7e33a15"),
    (2**33 + 7,
     "1718bb39601cf5fa2d0adbc97c15cf66db6c4bfc83f5699468f810d908dd3241"),
])
def test_weights_are_bit_identical_to_the_fixed_table_path(seed, digest):
    """``weights.make`` over the dense table gives every bit it gave when
    the table was fixed in ``weights.py`` (digests taken then)."""
    m = tiny.TINY_MODEL
    assert _digest(weights.make(dense.shapes(m), m, seed)) == digest
