"""A test-only family: a small mixture-of-experts decoder, the module that
the CPU tests copy to ``references/tiny_moe.py`` as a later change would
add a family (``bench/references/__init__.py`` states the contract).

The reference is plain float32 NumPy over one whole sequence, written
from the description below and importing nothing of the program.  The
attention is the dense family's (grouped KV heads, QKV bias, rotary on
the first ``partial_rotary`` share of each head, rotate-half).  The
expert layer follows the program, not a checkpoint: a softmax over every
expert, the ``top_k`` largest renormalised to sum to one, each chosen
expert a SiLU-gated MLP; and a shared expert, a SiLU-gated MLP behind a
sigmoid gate, added to every token.

``int8=True`` is the control: every weight product in int8 (weights per
output column, activations per token, symmetric absmax scales).
"""
from __future__ import annotations

from typing import Any, Dict, Iterable

import numpy as np

from bench.flops import (BF16, attention_flops, head_dim, head_flops,
                         kv_bytes_per_token)
from bench.weights import padded_vocab

Model = Dict[str, Any]
F32 = np.float32


def shapes(m: Model) -> Dict[str, Any]:
    """Name -> (shape, std); std 0 means ones.  The program's layout:
    ``router`` [d, E], experts ``we1``/``we3`` [E, d, f], ``we2``
    [E, f, d], the shared expert ``ws1``/``ws3``/``ws2`` and its gate
    ``ws_gate`` [d, 1]; each stacked over layers."""
    L, d, e = m["n_layers"], m["d_model"], m["moe"]
    E, f, fs = e["n_experts"], e["d_ff_expert"], e["d_ff_shared"]
    hd = head_dim(m)
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    layers = {
        "ln1": ((L, d), 0.0), "ln2": ((L, d), 0.0),
        "wq": ((L, d, q), d ** -0.5), "wk": ((L, d, kv), d ** -0.5),
        "wv": ((L, d, kv), d ** -0.5), "wo": ((L, q, d), q ** -0.5),
        "router": ((L, d, E), d ** -0.5),
        "we1": ((L, E, d, f), d ** -0.5), "we3": ((L, E, d, f), d ** -0.5),
        "we2": ((L, E, f, d), f ** -0.5),
    }
    if e["n_shared"]:
        layers.update({"ws1": ((L, d, fs), d ** -0.5),
                       "ws3": ((L, d, fs), d ** -0.5),
                       "ws2": ((L, fs, d), fs ** -0.5),
                       "ws_gate": ((L, d, 1), d ** -0.5)})
    if m.get("qkv_bias"):
        layers.update({"bq": ((L, q), 0.1), "bk": ((L, kv), 0.1),
                       "bv": ((L, kv), 0.1)})
    vp = padded_vocab(m)
    top = {"embed": ((vp, d), 1.0), "final_ln": ((d,), 0.0)}
    if not m.get("tie_embeddings"):
        top["lm_head"] = ((d, vp), d ** -0.5)
    return {"layers": layers, **top}


def _dense_params(m: Model) -> int:
    """Weights one token multiplies through in one layer, outside the
    routed experts: attention, router, shared expert and its gate."""
    d, hd, e = m["d_model"], head_dim(m), m["moe"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    shared = (3 * e["d_ff_shared"] + 1) * d if e["n_shared"] else 0
    return d * (q + 2 * kv) + q * d + d * e["n_experts"] + shared


def _token_params(m: Model) -> int:
    """Weights one token multiplies through in one layer: the dense part
    and its ``top_k`` experts."""
    e = m["moe"]
    return _dense_params(m) + e["top_k"] * 3 * m["d_model"] * e["d_ff_expert"]


def prefill_flops(m: Model, prompt_len: int) -> int:
    s = int(prompt_len)
    return (2 * m["n_layers"] * _token_params(m) * s
            + attention_flops(m, s * (s + 1) // 2) + head_flops(m))


def decode_flops(m: Model, keys: int) -> int:
    return (2 * m["n_layers"] * _token_params(m) + attention_flops(m, keys)
            + head_flops(m))


def decode_step_bytes(m: Model, keys: Iterable[int]) -> int:
    """The least a decode step reads: the weights outside the experts,
    ``top_k`` experts a layer (every live token may pick the same), the
    head; each live slot's keys and values, its new entry, its embedding
    row."""
    keys = list(keys)
    weights = m["n_layers"] * _token_params(m) + m["d_model"] * (
        1 + m["vocab_size"])
    return (BF16 * weights + kv_bytes_per_token(m) * (sum(keys) + len(keys))
            + BF16 * m["d_model"] * len(keys))


def _f32(x) -> np.ndarray:
    return np.asarray(x, F32)


def _dot(a, b):
    return a @ _f32(b)


def _quant(x, axis):
    scale = np.abs(x).max(axis=axis, keepdims=True) / 127.0 + 1e-30
    return np.clip(np.round(x / scale), -127, 127), scale


def _dot_int8(a, b):
    qa, sa = _quant(a, -1)
    qb, sb = _quant(_f32(b), 0)
    acc = qa.astype(np.int64) @ qb.astype(np.int64)
    return acc.astype(F32) * sa * sb


def _rms(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def _rope(x, theta, partial):
    """x [S, H, hd]; positions 0 .. S-1."""
    hd = x.shape[-1]
    rot = int(hd * partial)
    rot -= rot % 2
    if rot == 0 or theta <= 0:
        return x
    inv = theta ** -(np.arange(0, rot, 2, dtype=F32) / rot)
    ang = np.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = np.split(x[..., :rot], 2, axis=-1)
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           x[..., rot:]], axis=-1)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _attention(h, w, m, dot):
    S = h.shape[0]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], head_dim(m)
    q, k, v = dot(h, w["wq"]), dot(h, w["wk"]), dot(h, w["wv"])
    if m.get("qkv_bias"):
        q, k, v = q + _f32(w["bq"]), k + _f32(w["bk"]), v + _f32(w["bv"])
    q = _rope(q.reshape(S, H, hd), m["rope_theta"], m["partial_rotary"])
    k = _rope(k.reshape(S, KV, hd), m["rope_theta"], m["partial_rotary"])
    k = np.repeat(k, H // KV, axis=1)
    v = np.repeat(v.reshape(S, KV, hd), H // KV, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(F32(hd))
    s = np.where(np.tril(np.ones((S, S), bool))[None], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
    return dot(o, w["wo"])


def _experts(h, w, m, dot):
    e = m["moe"]
    logits = dot(h, w["router"])
    probs = np.exp(logits - logits.max(axis=-1, keepdims=True))
    probs /= probs.sum(axis=-1, keepdims=True)
    pick = np.argsort(-probs, axis=-1, kind="stable")[:, :e["top_k"]]
    gate = np.take_along_axis(probs, pick, axis=-1)
    gate /= gate.sum(axis=-1, keepdims=True)
    out = np.zeros_like(h)
    for i in range(e["n_experts"]):
        rows, slot = np.nonzero(pick == i)
        if rows.size:
            x = h[rows]
            y = dot(_silu(dot(x, w["we1"][i])) * dot(x, w["we3"][i]),
                    w["we2"][i])
            out[rows] += gate[rows, slot][:, None] * y
    if e["n_shared"]:
        y = dot(_silu(dot(h, w["ws1"])) * dot(h, w["ws3"]), w["ws2"])
        g = 1.0 / (1.0 + np.exp(-dot(h, w["ws_gate"])))
        out += y * g
    return out


def logits(params: Dict[str, Any], m: Model, tokens: np.ndarray,
           start: int, *, int8: bool = False) -> np.ndarray:
    """float32 logits [len(tokens) - start, vocab] of positions
    ``start ..`` of ``tokens``; position ``p`` predicts token ``p + 1``."""
    dot = _dot_int8 if int8 else _dot
    eps = m["norm_eps"]
    layers = {k: np.asarray(v) for k, v in params["layers"].items()}
    x = _f32(np.asarray(params["embed"])[np.asarray(tokens)])
    for i in range(m["n_layers"]):
        w = {k: v[i] for k, v in layers.items()}
        x = x + _attention(_rms(x, w["ln1"], eps), w, m, dot)
        x = x + _experts(_rms(x, w["ln2"], eps), w, m, dot)
    head = (np.asarray(params["embed"]).T if m.get("tie_embeddings")
            else np.asarray(params["lm_head"]))
    h = _rms(x[start:], np.asarray(params["final_ln"]), eps)
    return dot(h, head[:, :m["vocab_size"]])
