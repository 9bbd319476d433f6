"""The served expert layer against the mixture-of-experts family's plain
float32 reference (``bench/references/moe.py``), on seeded random weights
at a small size: prefill then decode through the server's cache, the
server's own tokens, a prompt whose tokens all pick the same experts (which
a capacity dispatch would drop), both gate normalisations, and the held
share: four shares of one layer add up to the uncut layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.references import moe as ref
from repro.models import get_model
from repro.models import layers as L
from repro.serve import BatchedServer
from repro.sharding.ctx import ShardCtx

# 16 experts, top-4, a shared expert; float32 so that program and
# reference differ by rounding alone
MODEL = {
    "family": "moe", "n_layers": 2, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 4, "head_dim": 16, "d_ff": 32, "vocab_size": 512,
    "rope_theta": 1e6, "partial_rotary": 1.0, "act": "swiglu",
    "qkv_bias": True, "tie_embeddings": False, "norm_eps": 1e-6,
    "param_dtype": "float32",
    "moe": {"n_experts": 16, "top_k": 4, "d_ff_expert": 32, "n_shared": 1,
            "d_ff_shared": 64, "norm_topk_prob": False,
            "held_experts": 4, "first_expert": 4},
}
# float32 products at HIGHEST on both sides: what is left is the order
# of summation, about 1e-6 relative on logits of order one
TOL = 1e-4


def model_block(norm=False, held=4, first=4):
    return dict(MODEL, moe=dict(MODEL["moe"], norm_topk_prob=norm,
                                held_experts=held, first_expert=first))


def build(m, seed=3):
    program = get_model(harness.build_model_config(m, "qwen2-moe-a2.7b"))
    params = weights.make(ref.shapes(m), m, seed)
    weights.check_layout(params, program.abstract_params())
    return program, params


SHARES = {"held-share": (4, 4), "all-held": (0, 0)}


@pytest.mark.parametrize("norm", [False, True], ids=["gates", "renormed"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_prefill_then_decode_match_the_reference(norm, share):
    m = model_block(norm, *SHARES[share])
    program, params = build(m)
    rng = np.random.default_rng(0)
    lens = np.array([11, 5, 16], np.int32)        # right-padded, as packed
    toks = np.zeros((3, 16), np.int32)
    seqs = [rng.integers(0, m["vocab_size"], n).astype(np.int32)
            for n in lens]
    for r, s in enumerate(seqs):
        toks[r, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        logits, cache = jax.jit(program.prefill, static_argnums=2)(
            params, jnp.asarray(toks), 32, jnp.asarray(lens))
        got = [np.asarray(logits[:, -1, :m["vocab_size"]])]
        pos = lens.copy()
        for _ in range(4):
            nxt = np.argmax(got[-1], axis=-1).astype(np.int32)
            seqs = [np.append(s, t) for s, t in zip(seqs, nxt)]
            lg, cache = jax.jit(program.decode_step)(
                params, cache, jnp.asarray(nxt[:, None]), jnp.asarray(pos))
            got.append(np.asarray(lg[:, -1, :m["vocab_size"]]))
            pos += 1
    for r, s in enumerate(seqs):
        want = ref.logits(params, m, s, int(lens[r]) - 1)
        np.testing.assert_allclose(np.stack([g[r] for g in got]), want,
                                   rtol=TOL, atol=TOL)


def test_batched_server_serves_the_reference_tokens():
    m = model_block()
    program, params = build(m, seed=11)
    rng = np.random.default_rng(5)
    server = BatchedServer(program, params, slots=3, max_len=48,
                           buckets=(16, 32))
    reqs = [server.submit(rng.integers(0, m["vocab_size"], n).astype(
        np.int32), max_new=6) for n in (4, 17, 9, 30, 12)]
    with jax.default_matmul_precision("highest"):
        server.run()
    for r in reqs:
        seq = np.concatenate([r.prompt, r.tokens[:-1]]).astype(np.int32)
        want = ref.logits(params, m, seq, len(r.prompt) - 1)
        gap = want.max(axis=1) - want[np.arange(len(r.tokens)), r.tokens]
        assert gap.max() <= TOL, gap


def test_tokens_that_all_pick_the_same_experts_are_all_computed():
    """Every token's hidden state points one way and the router favours
    four held experts along it, so each of the 32 tokens picks the same
    four: a capacity of 1.25 x 32 x 4 / 16 rows an expert (12) would drop
    most of them.  The program computes all, as the reference does."""
    m = model_block(norm=True, held=8, first=0)
    program, params = build(m, seed=2)
    d, vp = m["d_model"], weights.padded_vocab(m)
    u = jax.random.normal(jax.random.PRNGKey(9), (d,))
    u = u / jnp.linalg.norm(u)
    noise = 0.1 * jax.random.normal(jax.random.PRNGKey(10), (vp, d))
    params = dict(params, embed=u * np.sqrt(d) + noise)
    router = params["layers"]["router"].at[:, :, :4].add(
        8.0 * u[None, :, None])
    params["layers"] = dict(params["layers"], router=router)
    toks = np.random.default_rng(1).integers(0, m["vocab_size"], 32)
    h = np.asarray(params["embed"])[toks]
    h = h / np.sqrt(np.mean(h * h, axis=-1, keepdims=True) + 1e-6)
    picks = np.argsort(-(h @ np.asarray(router[0])), axis=-1)[:, :4]
    assert (np.sort(picks, axis=-1) == np.arange(4)).all()
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(program.prefill)(params, jnp.asarray(toks[None]))
    want = ref.logits(params, m, toks.astype(np.int32), 31)
    np.testing.assert_allclose(np.asarray(logits[0, -1, :m["vocab_size"]]),
                               want[0], rtol=TOL, atol=TOL)


def test_four_shares_add_up_to_the_uncut_layer():
    """One layer's expert block held whole, and as four chips' shares of
    four experts each: the shares' outputs add up to the whole's, with
    the shared expert, which every chip computes, counted once."""
    m = model_block(held=0, first=0)
    cfg = harness.build_model_config(m, "qwen2-moe-a2.7b")
    _, params = build(m, seed=4)
    p = {k: v[0] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 7, m["d_model"]))
    ctx = ShardCtx.null()
    with jax.default_matmul_precision("highest"):
        whole = L.moe_block(x, p, cfg, ctx)
        shares = []
        for i in range(4):
            c = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, held_experts=4, first_expert=4 * i))
            pi = dict(p, **{k: p[k][4 * i:4 * i + 4]
                            for k in ("we1", "we2", "we3")})
            shares.append(L.moe_block(x, pi, c, ctx))
        a = jax.nn.silu(x @ p["ws1"]) * (x @ p["ws3"])
        shared = (a @ p["ws2"]) * jax.nn.sigmoid(x @ p["ws_gate"])
    np.testing.assert_allclose(np.asarray(sum(shares) - 3 * shared),
                               np.asarray(whole), rtol=TOL, atol=TOL)
