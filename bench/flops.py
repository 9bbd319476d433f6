"""Operations and bytes that a served step needs, from the configuration's
shapes and the live token counts alone.

Never read from the compiled program: a change that does less work, pads
less or reads less dead cache leaves these numbers where they are and
raises the measured share honestly.  ``m`` is a configuration file's
``model`` block (dense decoder: attention with grouped KV heads, a gated
MLP, an untied or tied output head).  Matrix products count 2 operations
per multiply-add; norms, rotary, softmax and biases are left out.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable

Model = Dict[str, Any]
BF16 = 2


def head_dim(m: Model) -> int:
    return int(m.get("head_dim") or m["d_model"] // m["n_heads"])


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


def head_flops(m: Model) -> int:
    """Logits of one position over the real vocabulary."""
    return 2 * m["d_model"] * m["vocab_size"]


def attention_flops(m: Model, keys: int) -> int:
    """Scores and weighted values of one query against ``keys`` keys."""
    return 4 * m["n_layers"] * m["n_heads"] * head_dim(m) * keys


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


def kv_bytes_per_token(m: Model) -> int:
    """Keys and values of one position over every layer (bf16)."""
    return BF16 * 2 * m["n_layers"] * m["n_kv_heads"] * head_dim(m)


def decode_step_bytes(m: Model, keys: Iterable[int]) -> int:
    """Bytes one decode step needs: the weights once, each live slot's
    cached keys and values up to its own length (not the cache's
    ``max_len``), its new entry written, and its embedding row read."""
    keys = list(keys)
    return (weight_bytes(m) + kv_bytes_per_token(m) * (sum(keys) + len(keys))
            + BF16 * m["d_model"] * len(keys))
