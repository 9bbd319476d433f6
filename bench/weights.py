"""Weights from the seed, made on the device in one jitted call, in the
type they are served in (bf16), laid out as the server takes them.

Every norm scale is one, as in a trained checkpoint, so random-weight
logits have a spread of about one and are not bf16 near-ties.  Matrices
are normal with standard deviation ``1/sqrt(fan_in)``, the embedding has
standard deviation 1 and QKV biases 0.1, so the bias path is exercised.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.flops import head_dim


def padded_vocab(m: Dict[str, Any], multiple: int = 256) -> int:
    v = m["vocab_size"]
    return -(-v // multiple) * multiple


def shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """Name -> (shape, std) of every parameter; std 0 means ones."""
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


def _seed_key(seed: int) -> jax.Array:
    s = seed % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def make(m: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The parameter tree for ``seed``, on the default device."""
    table = shapes(m)
    dtype = jnp.dtype(m.get("param_dtype", "bfloat16"))

    def leaf(key, shape, std):
        if std == 0.0:
            return jnp.ones(shape, dtype)
        return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)

    def build(key):
        out: Dict[str, Any] = {}
        for i, (name, spec) in enumerate(sorted(table.items())):
            if name == "layers":
                out[name] = {
                    k: leaf(jax.random.fold_in(key, 1000 + j), *spec[k])
                    for j, k in enumerate(sorted(spec))}
            else:
                out[name] = leaf(jax.random.fold_in(key, i), *spec)
        return out

    return jax.jit(build)(_seed_key(seed))


def check_layout(params: Any, abstract: Any) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes the program
    expects (``abstract`` is its ``jax.eval_shape`` of an init)."""
    want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), abstract)
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError(f"benchmark weights do not match the program's "
                         f"layout:\n want {want}\n got  {got}")
