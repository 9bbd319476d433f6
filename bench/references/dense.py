"""Plain float32 reference of a dense decoder, written from the published
description and independent of the program under test.

Pre-norm blocks: RMSNorm, attention with grouped KV heads (query head
``h`` reads KV head ``h // (n_heads / n_kv_heads)``), optional QKV bias,
rotary embedding on the first ``partial_rotary`` share of each head in
the rotate-half layout, causal softmax scaled by ``1/sqrt(head_dim)``,
then RMSNorm and a SiLU-gated MLP; a final RMSNorm and the output head.

Every product runs at ``Precision.HIGHEST`` in float32, one layer at a
time over one whole sequence, with queries in blocks, so that a long
sequence fits next to the served weights.  It reads only the bf16
weights the benchmark made and the configuration's ``model`` block.

``int8=True`` is the control: the same computation with every weight
product in int8 (weights per output column, activations per token,
symmetric absmax scales, int32 accumulation), the step below the bf16
the configurations state.

It also holds the family's weight table (``shapes``) and the operations
and bytes a served step needs (``prefill_flops``, ``decode_flops``,
``decode_step_bytes``): the contract of ``bench/references/__init__.py``.
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
from bench.weights import padded_vocab

HI = lax.Precision.HIGHEST
F32 = jnp.float32
BLOCK = 512
Model = Dict[str, Any]


def shapes(m: Model) -> Dict[str, Any]:
    """Name -> (shape, std) of every parameter; std 0 means ones.

    Every norm scale is one, as in a trained checkpoint, so random-weight
    logits have a spread of about one and are not bf16 near-ties.
    Matrices are normal with standard deviation ``1/sqrt(fan_in)``, the
    embedding has standard deviation 1 and QKV biases 0.1, so the bias
    path is exercised."""
    L, d, f = m["n_layers"], m["d_model"], m["d_ff"]
    hd = head_dim(m)
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    vp = padded_vocab(m)
    layers = {
        "ln1": ((L, d), 0.0), "ln2": ((L, d), 0.0),
        "wq": ((L, d, q), d ** -0.5), "wk": ((L, d, kv), d ** -0.5),
        "wv": ((L, d, kv), d ** -0.5), "wo": ((L, q, d), q ** -0.5),
        "w1": ((L, d, f), d ** -0.5), "w3": ((L, d, f), d ** -0.5),
        "w2": ((L, f, d), f ** -0.5),
    }
    if m.get("qkv_bias"):
        layers.update({"bq": ((L, q), 0.1), "bk": ((L, kv), 0.1),
                       "bv": ((L, kv), 0.1)})
    top = {"embed": ((vp, d), 1.0), "final_ln": ((d,), 0.0)}
    if not m.get("tie_embeddings"):
        top["lm_head"] = ((d, vp), d ** -0.5)
    return {"layers": layers, **top}


def layer_matmul_params(m: Model) -> int:
    """Weights one token multiplies through in one layer."""
    d, hd = m["d_model"], head_dim(m)
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    n_mlp = 3 if m.get("act", "swiglu") == "swiglu" else 2
    return d * (q + 2 * kv) + q * d + n_mlp * d * m["d_ff"]


def layer_params(m: Model) -> int:
    """Every weight of one layer: matrices, QKV biases, two norm scales."""
    hd = head_dim(m)
    bias = (m["n_heads"] + 2 * m["n_kv_heads"]) * hd if m.get("qkv_bias") \
        else 0
    return layer_matmul_params(m) + bias + 2 * m["d_model"]


def prefill_flops(m: Model, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` true tokens, causal (position ``i``
    attends ``i + 1`` keys), and the logits of its last position only."""
    s = int(prompt_len)
    return (2 * m["n_layers"] * layer_matmul_params(m) * s
            + attention_flops(m, s * (s + 1) // 2) + head_flops(m))


def decode_flops(m: Model, keys: int) -> int:
    """One decoded token whose query attends ``keys`` cached keys (itself
    included), and its logits."""
    return (2 * m["n_layers"] * layer_matmul_params(m)
            + attention_flops(m, keys) + head_flops(m))


def weight_bytes(m: Model) -> int:
    """Bytes of weights a decode step must read once: every layer, the
    final norm and the output head over the real vocabulary (bf16)."""
    return BF16 * (m["n_layers"] * layer_params(m) + m["d_model"]
                   + m["d_model"] * m["vocab_size"])


def decode_step_bytes(m: Model, keys: Iterable[int]) -> int:
    """Bytes one decode step needs: the weights once, each live slot's
    cached keys and values up to its own length (not the cache's
    ``max_len``), its new entry written, and its embedding row read."""
    keys = list(keys)
    return (weight_bytes(m) + kv_bytes_per_token(m) * (sum(keys) + len(keys))
            + BF16 * m["d_model"] * len(keys))


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, pos, theta, partial):
    """x [S, H, hd] float32, pos [S]."""
    hd = x.shape[-1]
    rot = int(hd * partial)
    rot -= rot % 2
    if rot == 0 or theta <= 0:
        return x
    inv = theta ** -(jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x[..., :rot], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _dot(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HI)


def _quant(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0 + 1e-30
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _dot_int8(a, b):
    qa, sa = _quant(a, axis=-1)
    qb, sb = _quant(b.astype(F32), axis=0)
    acc = jnp.matmul(qa, qb, preferred_element_type=jnp.int32)
    return acc.astype(F32) * sa * sb


@functools.partial(jax.jit, static_argnames=("m", "int8"))
def _layer(x, layers, i, *, m, int8=False):
    """One block over x [S, d]; ``layers`` are the stacked bf16 weights."""
    md = dict(m)
    dot = _dot_int8 if int8 else _dot
    S = x.shape[0]
    H, KV = md["n_heads"], md["n_kv_heads"]
    hd = md.get("head_dim") or md["d_model"] // H
    eps = md["norm_eps"]
    w = {k: lax.dynamic_index_in_dim(v, i, keepdims=False)
         for k, v in layers.items()}
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], eps)
    q, k, v = dot(h, w["wq"]), dot(h, w["wk"]), dot(h, w["wv"])
    if md.get("qkv_bias"):
        q, k, v = (q + w["bq"].astype(F32), k + w["bk"].astype(F32),
                   v + w["bv"].astype(F32))
    q = _rope(q.reshape(S, H, hd), pos, md["rope_theta"],
              md["partial_rotary"])
    k = _rope(k.reshape(S, KV, hd), pos, md["rope_theta"],
              md["partial_rotary"])
    v = v.reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)

    def block(b):
        qb = lax.dynamic_slice_in_dim(q, b * BLOCK, BLOCK, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        qpos = b * BLOCK + jnp.arange(BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = lax.map(block, jnp.arange(S // BLOCK)).reshape(S, H * hd)
    x = x + dot(o, w["wo"])
    h2 = _rms(x, w["ln2"], eps)
    return x + dot(jax.nn.silu(dot(h2, w["w1"])) * dot(h2, w["w3"]),
                   w["w2"])


@functools.partial(jax.jit, static_argnames=("count", "m", "int8"))
def _head(x, final_ln, head, start, *, count, m, int8=False):
    md = dict(m)
    rows = lax.dynamic_slice_in_dim(x, start, count, axis=0)
    h = _rms(rows, final_ln, md["norm_eps"])
    return (_dot_int8 if int8 else _dot)(h, head[:, :md["vocab_size"]])


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def logits(params: Dict[str, Any], m: Dict[str, Any], tokens: np.ndarray,
           start: int, *, int8: bool = False) -> np.ndarray:
    """float32 logits [len(tokens) - start, vocab] of positions
    ``start ..`` of ``tokens``; position ``p`` predicts token ``p + 1``."""
    n = len(tokens)
    count = _pow2(n - start)            # few shapes, so few compiles
    S = max(BLOCK, _pow2(start + count))   # pads at the end: causal, unread
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    key = tuple(sorted((k, v) for k, v in m.items()
                       if isinstance(v, (int, float, str, bool))))
    x = jnp.take(params["embed"], jnp.asarray(toks), axis=0).astype(F32)
    for i in range(m["n_layers"]):
        x = _layer(x, params["layers"], jnp.int32(i), m=key, int8=int8)
    head = (params["embed"].T if m.get("tie_embeddings")
            else params["lm_head"])
    out = _head(x, params["final_ln"], head, jnp.int32(start), count=count,
                m=key, int8=int8)
    return np.asarray(out, np.float32)[:n - start]
