"""Counts that every family shares, from the configuration's shapes alone.

A family's own counts (``prefill_flops``, ``decode_flops``,
``decode_step_bytes``) live in its module ``references/<family>.py``,
whose contract ``bench/references/__init__.py`` states; they build on
these.  Never read from the compiled program: a change that does less
work, pads less or reads less dead cache leaves these numbers where they
are and raises the measured share honestly.  ``m`` is a configuration
file's ``model`` block.  Matrix products count 2 operations per
multiply-add; norms, rotary, softmax and biases are left out.
"""
from __future__ import annotations

from typing import Any, Dict

Model = Dict[str, Any]
BF16 = 2


def head_dim(m: Model) -> int:
    return int(m.get("head_dim") or m["d_model"] // m["n_heads"])


def head_flops(m: Model) -> int:
    """Logits of one position over the real vocabulary."""
    return 2 * m["d_model"] * m["vocab_size"]


def attention_flops(m: Model, keys: int) -> int:
    """Scores and weighted values of one query against ``keys`` keys."""
    return 4 * m["n_layers"] * m["n_heads"] * head_dim(m) * keys


def kv_bytes_per_token(m: Model) -> int:
    """Keys and values of one position over every layer (bf16)."""
    return BF16 * 2 * m["n_layers"] * m["n_kv_heads"] * head_dim(m)
