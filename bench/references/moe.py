"""Plain float32 reference of a mixture-of-experts decoder that holds one
chip's share of its experts, written from the published description
(Qwen1.5-MoE: ``Qwen2MoeSparseMoeBlock``) and independent of the program
under test.

Pre-norm blocks.  Attention as the dense family's: grouped KV heads,
optional QKV bias, rotary embedding on the first ``partial_rotary`` share
of each head in the rotate-half layout, causal softmax scaled by
``1/sqrt(head_dim)``.  Then RMSNorm and the expert layer: a router over
all ``n_experts`` (a float32 softmax of its logits), the ``top_k`` largest
probabilities as gates, renormalised to sum to one only where
``norm_topk_prob`` says so; each picked expert a SiLU-gated MLP weighted
by its gate; a shared SiLU-gated MLP behind a sigmoid gate added to every
token.  A final RMSNorm and the output head.

The share: under expert parallelism this chip holds ``held_experts``
experts from ``first_expert`` (0 held: all).  Every token is routed over
all experts, and only picks of held experts add their part here; what the
experts held elsewhere would add is left out, as the program leaves it
out.  The shared expert is every chip's, counted once.

Every product runs in float32 at ``Precision.HIGHEST`` (under
``jax.default_matmul_precision("highest")``), one layer at a time over one
whole sequence, each held expert over every token with its gate zero where
the token did not pick it, so a long sequence fits next to the served
weights.  ``int8=True`` is the control: the same computation with every
weight product in int8 (weights per output column, activations per token,
symmetric absmax scales, int32 accumulation), the step below the bf16
the configurations state.

It also holds the family's weight table (``shapes``) and the operations
and bytes a served step needs: the contract of
``bench/references/__init__.py``.  Routed experts are counted at the load
uniform routing gives the held share (``routed_load``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.flops import (BF16, attention_flops, head_dim, head_flops,
                         kv_bytes_per_token)
from bench.references.dense import (BLOCK, HI, F32, _dot, _dot_int8, _head,
                                    _pow2, _rms, _rope)
from bench.weights import padded_vocab

Model = Dict[str, Any]


def held(m: Model):
    """(first held expert, how many are held)."""
    e = m["moe"]
    return e.get("first_expert", 0), e.get("held_experts") or e["n_experts"]


def shapes(m: Model) -> Dict[str, Any]:
    """Name -> (shape, std); std 0 means ones.  The program's layout:
    ``router`` [d, n_experts]; the held experts ``we1``/``we3``
    [held, d, f] and ``we2`` [held, f, d]; the shared expert
    ``ws1``/``ws3``/``ws2`` and its gate ``ws_gate`` [d, 1]; each stacked
    over layers.  Norm scales one; matrices normal with standard deviation
    ``1/sqrt(fan_in)``; the embedding 1; QKV biases 0.01.

    The biases are ten times smaller than the dense family's because a
    bias is the same for every token: at 0.1 the direction it adds to
    every hidden state leads a random router to pick the same experts for
    many tokens (24 tokens touched 86% of the held experts that uniform
    routing touches, at 24 layers of width 512), where a trained router
    is balanced by its training loss.  At 0.01 they touch 99% there."""
    L, d, e = m["n_layers"], m["d_model"], m["moe"]
    E, f, fs = e["n_experts"], e["d_ff_expert"], e["d_ff_shared"]
    n = held(m)[1]
    hd = head_dim(m)
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    layers = {
        "ln1": ((L, d), 0.0), "ln2": ((L, d), 0.0),
        "wq": ((L, d, q), d ** -0.5), "wk": ((L, d, kv), d ** -0.5),
        "wv": ((L, d, kv), d ** -0.5), "wo": ((L, q, d), q ** -0.5),
        "router": ((L, d, E), d ** -0.5),
        "we1": ((L, n, d, f), d ** -0.5), "we3": ((L, n, d, f), d ** -0.5),
        "we2": ((L, n, f, d), f ** -0.5),
    }
    if e["n_shared"]:
        layers.update({"ws1": ((L, d, fs), d ** -0.5),
                       "ws3": ((L, d, fs), d ** -0.5),
                       "ws2": ((L, fs, d), fs ** -0.5),
                       "ws_gate": ((L, d, 1), d ** -0.5)})
    if m.get("qkv_bias"):
        layers.update({"bq": ((L, q), 0.01), "bk": ((L, kv), 0.01),
                       "bv": ((L, kv), 0.01)})
    vp = padded_vocab(m)
    top = {"embed": ((vp, d), 1.0), "final_ln": ((d,), 0.0)}
    if not m.get("tie_embeddings"):
        top["lm_head"] = ((d, vp), d ** -0.5)
    return {"layers": layers, **top}


# ----------------------------------------------------------------- counts --
def _attn_params(m: Model) -> int:
    d, hd = m["d_model"], head_dim(m)
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return d * (q + 2 * kv) + q * d


def _dense_params(m: Model) -> int:
    """Weights every token multiplies through in one layer, outside the
    routed experts: attention, router, shared expert and its gate."""
    e, d = m["moe"], m["d_model"]
    shared = (3 * e["d_ff_shared"] + 1) * d if e["n_shared"] else 0
    return _attn_params(m) + d * e["n_experts"] + shared


def expert_params(m: Model) -> int:
    """One routed expert's weights (three matrices)."""
    return 3 * m["d_model"] * m["moe"]["d_ff_expert"]


def routed_load(m: Model) -> float:
    """Picks a token makes, on average, of the held experts: ``top_k``
    picks spread uniformly over all experts, the held share of them."""
    return m["moe"]["top_k"] * held(m)[1] / m["moe"]["n_experts"]


def experts_read(m: Model, tokens: int) -> float:
    """Held experts that ``tokens`` tokens touch in expectation, each
    picking ``top_k`` distinct experts uniformly: a held expert is read
    unless every token passes it by."""
    e = m["moe"]
    return held(m)[1] * (1.0 - (1.0 - e["top_k"] / e["n_experts"])
                         ** tokens)


def weight_params(m: Model) -> int:
    """Every weight held: each layer (matrices, QKV biases, two norm
    scales, the held experts), the final norm, the embedding and the head
    over the real vocabulary."""
    hd = head_dim(m)
    d = m["d_model"]
    bias = (m["n_heads"] + 2 * m["n_kv_heads"]) * hd if m.get("qkv_bias") \
        else 0
    layer = (_dense_params(m) + bias + 2 * d
             + held(m)[1] * expert_params(m))
    heads = 1 if m.get("tie_embeddings") else 2
    return m["n_layers"] * layer + d + heads * d * m["vocab_size"]


def _token_flops(m: Model) -> float:
    """Operations of one token through every layer's weights."""
    return 2 * m["n_layers"] * (_dense_params(m)
                                + routed_load(m) * expert_params(m))


def prefill_flops(m: Model, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` true tokens, causal, its routed experts
    at the held share's load, and the logits of its last position."""
    s = int(prompt_len)
    return int(_token_flops(m) * s + attention_flops(m, s * (s + 1) // 2)
               + head_flops(m))


def decode_flops(m: Model, keys: int) -> int:
    """One decoded token attending ``keys`` keys, and its logits."""
    return int(_token_flops(m) + attention_flops(m, keys) + head_flops(m))


def expert_step_bytes(m: Model, tokens: int) -> int:
    """Routed-expert weight bytes a decode step over ``tokens`` live
    tokens needs: the held experts those tokens touch in expectation,
    every layer.  What the model needs, not what a kernel reads: a token
    of a dead slot or an uneven draw of experts is not counted."""
    return int(BF16 * m["n_layers"] * experts_read(m, tokens)
               * expert_params(m))


def decode_step_bytes(m: Model, keys: Iterable[int]) -> int:
    """Bytes one decode step needs: every weight outside the routed
    experts once, the held experts its live tokens touch, each live
    slot's cached keys and values up to its own length, its new entry,
    its embedding row."""
    keys = list(keys)
    d = m["d_model"]
    hd = head_dim(m)
    bias = (m["n_heads"] + 2 * m["n_kv_heads"]) * hd if m.get("qkv_bias") \
        else 0
    dense = m["n_layers"] * (_dense_params(m) + bias + 2 * d) \
        + d * (1 + m["vocab_size"])
    return (BF16 * dense + expert_step_bytes(m, len(keys))
            + kv_bytes_per_token(m) * (sum(keys) + len(keys))
            + BF16 * d * len(keys))


# -------------------------------------------------------------- reference --
def _silu(x):
    return x * jax.nn.sigmoid(x)


def _attention(h, w, md, dot):
    S = h.shape[0]
    H, KV = md["n_heads"], md["n_kv_heads"]
    hd = md.get("head_dim") or md["d_model"] // H
    pos = jnp.arange(S)
    q, k, v = dot(h, w["wq"]), dot(h, w["wk"]), dot(h, w["wv"])
    if md.get("qkv_bias"):
        q, k, v = (q + w["bq"].astype(F32), k + w["bk"].astype(F32),
                   v + w["bv"].astype(F32))
    q = _rope(q.reshape(S, H, hd), pos, md["rope_theta"],
              md["partial_rotary"])
    k = _rope(k.reshape(S, KV, hd), pos, md["rope_theta"],
              md["partial_rotary"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v.reshape(S, KV, hd), H // KV, axis=1)

    def block(b):
        qb = lax.dynamic_slice_in_dim(q, b * BLOCK, BLOCK, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        qpos = b * BLOCK + jnp.arange(BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = lax.map(block, jnp.arange(S // BLOCK)).reshape(S, H * hd)
    return dot(o, w["wo"])


def _experts(h, w, md, dot):
    """The routed experts this chip holds and the shared expert."""
    e = dict(md["moe"])
    first, n = e["first_expert"], e["held_experts"]
    probs = jax.nn.softmax(dot(h, w["router"]), axis=-1)   # [S, E]
    gate, pick = lax.top_k(probs, e["top_k"])
    if e["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    def expert(out, i):
        wgt = jnp.sum(jnp.where(pick == first + i, gate, 0.0), axis=-1)
        y = dot(_silu(dot(h, w["we1"][i])) * dot(h, w["we3"][i]),
                w["we2"][i])
        return out + wgt[:, None] * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(n))
    if e["n_shared"]:
        y = dot(_silu(dot(h, w["ws1"])) * dot(h, w["ws3"]), w["ws2"])
        out = out + y * jax.nn.sigmoid(dot(h, w["ws_gate"]))
    return out


@functools.partial(jax.jit, static_argnames=("m", "int8"))
def _layer(x, layers, i, *, m, int8=False):
    """One block over x [S, d]; ``layers`` are the stacked bf16 weights."""
    md = dict(m)
    dot = _dot_int8 if int8 else _dot
    eps = md["norm_eps"]
    w = {k: lax.dynamic_index_in_dim(v, i, keepdims=False)
         for k, v in layers.items()}
    x = x + _attention(_rms(x, w["ln1"], eps), w, md, dot)
    return x + _experts(_rms(x, w["ln2"], eps), w, md, dot)


def _static(m: Model):
    """The model block as a hashable static argument; the ``moe`` block
    with its share and gate normalisation stated."""
    first, n = held(m)
    e = dict(m["moe"], first_expert=first, held_experts=n,
             norm_topk_prob=m["moe"].get("norm_topk_prob", True))
    flat = {k: v for k, v in m.items()
            if isinstance(v, (int, float, str, bool))}
    flat["moe"] = tuple(sorted((k, v) for k, v in e.items()
                               if isinstance(v, (int, float, str, bool))))
    return tuple(sorted(flat.items()))


def logits(params: Dict[str, Any], m: Model, tokens: np.ndarray,
           start: int, *, int8: bool = False) -> np.ndarray:
    """float32 logits [len(tokens) - start, vocab] of positions
    ``start ..`` of ``tokens``; position ``p`` predicts token ``p + 1``."""
    n = len(tokens)
    count = _pow2(n - start)            # few shapes, so few compiles
    S = max(BLOCK, _pow2(start + count))   # pads at the end: causal, unread
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    key = _static(m)
    flat = {k: v for k, v in m.items()
            if isinstance(v, (int, float, str, bool))}
    head_key = tuple(sorted(flat.items()))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(toks), axis=0).astype(F32)
        for i in range(m["n_layers"]):
            x = _layer(x, params["layers"], jnp.int32(i), m=key, int8=int8)
        head = (params["embed"].T if m.get("tie_embeddings")
                else params["lm_head"])
        out = _head(x, params["final_ln"], head, jnp.int32(start),
                    count=count, m=head_key, int8=int8)
    return np.asarray(out, np.float32)[:n - start]
