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
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
BLOCK = 512


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
