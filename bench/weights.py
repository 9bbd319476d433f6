"""Weights from the seed, made on the device in one jitted call, in the
type they are served in (bf16), laid out as the server takes them.

What the leaves are is the family's: its table ``shapes(m)`` in
``references/<family>.py``, name -> (shape, std), std 0 meaning ones.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def padded_vocab(m: Dict[str, Any], multiple: int = 256) -> int:
    v = m["vocab_size"]
    return -(-v // multiple) * multiple


def _seed_key(seed: int) -> jax.Array:
    s = seed % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def make(table: Dict[str, Any], m: Dict[str, Any], seed: int
         ) -> Dict[str, Any]:
    """The parameter tree of ``table`` for ``seed``, on the default device,
    in ``m``'s ``param_dtype``."""
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
