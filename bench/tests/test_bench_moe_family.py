"""The mixture-of-experts family (``references/moe.py``): its counts for
the benchmark's cut of qwen2-moe-a2.7b against hand counts, its table
against the program's layout, and its held share."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec
from bench.references import moe

# a small model of the family: two layers of 16 experts, top-4, four held
# (experts 4-7), a shared expert
TINY = {
    "family": "moe", "n_layers": 2, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 4, "head_dim": 16, "d_ff": 32, "vocab_size": 512,
    "rope_theta": 1e6, "partial_rotary": 1.0, "act": "swiglu",
    "qkv_bias": True, "tie_embeddings": False, "norm_eps": 1e-6,
    "param_dtype": "bfloat16",
    "moe": {"n_experts": 16, "top_k": 4, "d_ff_expert": 32, "n_shared": 1,
            "d_ff_shared": 64, "norm_topk_prob": False,
            "held_experts": 4, "first_expert": 4},
}


def qwen():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "qwen2-moe-a2.7b.json")) as f:
        return json.load(f)


def test_counts_of_the_cut_equal_hand_counts():
    m = qwen()["model"]
    # a layer: attention 16,777,216 and QKV biases 6,144; two norms
    # 4,096; router 2048 x 60; 15 held experts of 3 x 2048 x 1408; the
    # shared expert 3 x 2048 x 5632 and its gate 2048.  Then the final
    # norm, the embedding and the head over the real vocabulary.
    layer = (16_777_216 + 6_144 + 4_096 + 122_880 + 15 * 8_650_752
             + 34_603_008 + 2_048)
    assert layer == 181_276_672
    assert moe.weight_params(m) == 24 * layer + 2_048 + 2 * 2048 * 151936 \
        == 4_972_972_032
    assert moe.routed_load(m) == 1.0            # 4 picks x 15 / 60
    # held experts are two thirds of the weight bytes a decode step reads
    # (every weight but the embedding table, one row of which is read)
    experts = 24 * 15 * 8_650_752
    assert experts / (moe.weight_params(m) - 2048 * 151936) == \
        pytest.approx(0.668, abs=0.001)
    # a token's operations through the weights: attention, router,
    # shared expert and gate, and one held expert at the held share's load
    per_token = 2 * 24 * (16_777_216 + 122_880 + 34_605_056 + 8_650_752)
    assert moe.decode_flops(m, 100) == per_token + 4 * 24 * 16 * 128 * 100 \
        + 2 * 2048 * 151936
    assert moe.prefill_flops(m, 3) == 3 * per_token + 4 * 24 * 16 * 128 * 6 \
        + 2 * 2048 * 151936
    # a decode step of 24 live tokens: the held experts they touch in
    # expectation, 15 x (1 - (56/60)^24)
    touched = 15 * (1 - (56 / 60) ** 24)
    assert moe.expert_step_bytes(m, 24) == int(
        2 * 24 * touched * 8_650_752)
    dense = 24 * (16_777_216 + 6_144 + 4_096 + 122_880 + 34_605_056) \
        + 2048 * (1 + 151936)
    kv = 2 * 2 * 24 * 16 * 128
    assert moe.decode_step_bytes(m, [10, 20]) == (
        2 * dense + moe.expert_step_bytes(m, 2) + kv * 32 + 2 * 2048 * 2)


def test_configuration_reaches_the_program_in_its_layout():
    config = qwen()
    harness.check_keymap(config)
    m = config["model"]
    cfg = harness.build_model_config(m, config["arch"])
    assert (cfg.moe.n_experts, cfg.moe.n_held, cfg.moe.first_expert,
            cfg.moe.top_k, cfg.moe.norm_topk_prob) == (60, 15, 0, 4, False)
    from repro.models import get_model
    program = get_model(cfg)
    want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)),
                        program.abstract_params())
    table = moe.shapes(m)
    layers = table.pop("layers")
    got = {k: (s, "bfloat16") for k, (s, _) in table.items()}
    got["layers"] = {k: (s, "bfloat16") for k, (s, _) in layers.items()}
    assert got == want
    assert set(config["published"]) == set(config["reduced"])


def test_four_shares_of_the_reference_layer_add_up_to_the_whole():
    """The reference's expert layer held whole and as four shares: the
    shares add up to the whole, the shared expert counted once."""
    rng = np.random.default_rng(0)
    m = dict(TINY, param_dtype="float32")
    w = {k: jnp.asarray(rng.standard_normal(s[1:]) * 0.2, jnp.float32)
         for k, (s, _) in moe.shapes(dict(m, moe=dict(
             m["moe"], held_experts=0, first_expert=0)))["layers"].items()}
    h = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)

    def layer(first, held):
        md = dict(m, moe=dict(m["moe"], first_expert=first,
                              held_experts=held))
        ws = dict(w, **{k: w[k][first:first + held]
                        for k in ("we1", "we2", "we3")})
        with jax.default_matmul_precision("highest"):
            return moe._experts(h, ws, md, moe._dot)

    whole = layer(0, 16)
    a = jax.nn.silu(h @ w["ws1"]) * (h @ w["ws3"])
    shared = (a @ w["ws2"]) * jax.nn.sigmoid(h @ w["ws_gate"])
    parts = sum(layer(4 * i, 4) for i in range(4))
    np.testing.assert_allclose(np.asarray(parts - 3 * shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-4)
