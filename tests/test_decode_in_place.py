"""The decode step reads the stored KV cache in place and writes each
layer's new row once, after the layer scan.

``test_decode_step_matches_the_per_layer_write`` holds it to the
formulation it replaced, kept here as the reference: the cache in its old
[L, B, T, KV, hd] layout goes through the layer scan as ``xs``, each layer
writes its row into its slice, attends up to ``pos + 1`` and hands the
slice back as ``ys``.  ``test_decode_and_pick_writes_the_cache_once``
checks the structure of the served step's jaxpr."""
import dataclasses
from unittest import mock

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.configs import REGISTRY
from repro.models import get_model
from repro.models import layers as L
from repro.models.lm import LM
from repro.serve import BatchedServer

B, T = 4, 16
RAGGED = np.array([0, T - 1, 5, 9], np.int32)   # a dead slot, a full one
KV_KEYS = ("k", "v", "k_scale", "v_scale")


# ---- the reference: the per-layer write in the old layout ----------------
def ref_cache_update(cache, new, pos):
    new = new.astype(cache.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        idx = (jnp.zeros((), jnp.int32), pos) + (jnp.zeros((), jnp.int32),
                                                 ) * (cache.ndim - 2)
        return lax.dynamic_update_slice(cache, new, idx)
    return cache.at[jnp.arange(cache.shape[0]), pos].set(new[:, 0])


def ref_attention_decode(q, k_cache, v_cache, length, softcap=0.0,
                         k_scale=None, v_scale=None):
    if k_scale is not None:
        k_cache = L.kv_dequantize(k_cache, k_scale)
        v_cache = L.kv_dequantize(v_cache, v_scale)
    Bq, _, H, hd = q.shape
    Tc, KV = k_cache.shape[1], k_cache.shape[2]
    qh = q.reshape(Bq, KV, H // KV, hd)
    s = jnp.einsum("bkgh,btkh->bkgt", qh, k_cache).astype(jnp.float32)
    s = s / math.sqrt(hd)
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    valid = jnp.arange(Tc)[None, :] < length[:, None]
    s = jnp.where(valid[:, None, None, :], s, L.NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgt,btkh->bkgh", p, v_cache)
    return out.reshape(Bq, 1, H, hd)


ORIG_ATTN = LM._attn


def ref_attn(self, x, p, positions, mode, cache=None, pos=None):
    if mode != "dec":
        return ORIG_ATTN(self, x, p, positions, mode, cache, pos)
    q, k, v = L._project_qkv(x, p, self.cfg, self.ctx, positions)
    if self.kv_quant:
        k_cache, v_cache, ks_cache, vs_cache = cache
        kq, ks = L.kv_quantize(k)
        vq, vs = L.kv_quantize(v)
        new_kv = (ref_cache_update(k_cache, kq, pos),
                  ref_cache_update(v_cache, vq, pos),
                  ref_cache_update(ks_cache, ks, pos),
                  ref_cache_update(vs_cache, vs, pos))
        scales = {"k_scale": new_kv[2], "v_scale": new_kv[3]}
    else:
        new_kv = (ref_cache_update(cache[0], k, pos),
                  ref_cache_update(cache[1], v, pos))
        scales = {}
    length = jnp.broadcast_to(jnp.asarray(pos, jnp.int32) + 1, (x.shape[0],))
    out = ref_attention_decode(q, new_kv[0], new_kv[1], length,
                               self.cfg.logit_softcap, **scales)
    out = jnp.einsum("bsq,qd->bsd", out.reshape(x.shape[0], x.shape[1], -1),
                     p["wo"])
    return out, new_kv


def ref_decode_step(model, params, cache, token, pos):
    """The replaced ``LM.decode_step``; ``cache`` in the old layout."""
    x = model._embed(params, token)
    pos = jnp.asarray(pos, jnp.int32)
    positions = (jnp.full((1, 1), pos, jnp.int32) if pos.ndim == 0
                 else pos[:, None])

    def body(x, xs):
        lp, cache_l = xs
        x, new_cache_l, _ = model._block(x, lp, positions, "dec",
                                         cache=cache_l, pos=pos)
        return x, new_cache_l

    with mock.patch.object(LM, "_attn", ref_attn):
        x, new_cache = lax.scan(body, x, (params["layers"], cache))
    x = L.rms_norm(x, params["final_ln"], model.cfg.norm_eps)
    return model.logits_fn(params, x), new_cache


def to_old_layout(cache):
    """[L, B, KV, hd, T] → [L, B, T, KV, hd] for the K/V entries."""
    return {k: jnp.moveaxis(c, -1, 2) if k in KV_KEYS else c
            for k, c in cache.items()}


def random_cache(model, seed):
    rng = np.random.default_rng(seed)
    out = {}
    for k, s in model.cache_shapes(B, T).items():
        if s.dtype == jnp.int8:
            out[k] = jnp.asarray(rng.integers(-127, 128, s.shape), jnp.int8)
        elif k.endswith("_scale"):
            out[k] = jnp.asarray(rng.uniform(0.005, 0.02, s.shape), s.dtype)
        else:
            out[k] = jnp.asarray(rng.standard_normal(s.shape), s.dtype)
    return out


# arch, pos, kv_quant, param dtype, tolerance
CASES = {
    "dense-ragged": ("stablelm-3b", RAGGED, False, "float32", 1e-5),
    "dense-gqa-ragged": ("glm4-9b", RAGGED, False, "float32", 1e-5),
    "dense-scalar": ("stablelm-3b", np.int32(7), False, "float32", 1e-5),
    "dense-scalar-last": ("glm4-9b", np.int32(T - 1), False, "float32",
                          1e-5),
    "moe-ragged": ("qwen2-moe-a2.7b", RAGGED, False, "float32", 1e-5),
    "hybrid-ragged": ("hymba-1.5b", RAGGED, False, "float32", 1e-5),
    "kv_quant-scalar": ("codeqwen1.5-7b", np.int32(7), True, "float32",
                        1e-5),
    "kv_quant-ragged": ("codeqwen1.5-7b", RAGGED, True, "float32", 1e-5),
    # the served dtype: attention outputs round in another order (the new
    # token's term is added to the cache's), so both sides differ by one or
    # two bf16 ulps (2**-8 relative); the tolerance is about five
    "dense-ragged-bf16": ("stablelm-3b", RAGGED, False, "bfloat16", 2e-2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_step_matches_the_per_layer_write(case):
    arch, pos, kv_quant, dtype, tol = CASES[case]
    cfg = dataclasses.replace(REGISTRY[arch].reduced(), param_dtype=dtype)
    model = get_model(cfg, kv_quant=kv_quant)
    params = model.init_params(jax.random.PRNGKey(0))
    cache = random_cache(model, seed=1)
    tok = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0,
                             cfg.vocab_size)
    got, new = jax.jit(model.decode_step)(params, cache, tok, pos)
    want, want_cache = jax.jit(
        lambda p, c, t, q: ref_decode_step(model, p, c, t, q))(
        params, to_old_layout(cache), tok, pos)
    v = cfg.vocab_size
    np.testing.assert_allclose(np.asarray(got[..., :v], np.float32),
                               np.asarray(want[..., :v], np.float32),
                               atol=tol, rtol=tol)
    assert sorted(new) == sorted(want_cache)
    for k, c in to_old_layout(new).items():
        # later layers' rows carry the rounding of the earlier layers'
        # outputs through their projections: held to the tensor's scale
        w = np.asarray(want_cache[k], np.float32)
        np.testing.assert_allclose(np.asarray(c, np.float32), w,
                                   atol=tol * np.abs(w).max(), rtol=tol,
                                   err_msg=k)


def test_bf16_kv_quant_ragged_decode_keeps_the_carry_type():
    """The int8 cache dequantizes to float32; the attention output takes
    the query's dtype, so a bf16 model's layer scan keeps its carry."""
    model = get_model(REGISTRY["stablelm-3b"].reduced(), kv_quant=True)
    params = model.init_params(jax.random.PRNGKey(0))
    cache = random_cache(model, seed=1)
    logits, new = jax.jit(model.decode_step)(
        params, cache, jnp.ones((B, 1), jnp.int32), RAGGED)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert jax.tree.map(lambda c: (c.shape, c.dtype), new) == \
        jax.tree.map(lambda c: (c.shape, c.dtype), cache)


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, with those of its sub-jaxprs."""
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


def test_decode_and_pick_writes_the_cache_once():
    """In the served step's jaxpr the layer scan takes no stacked cache as
    a carry or ``xs`` and returns none, and writes nothing inside.  After
    it each of K and V is written once: a select of the new rows into the
    input cache, in the pass that makes the output buffer."""
    cfg = REGISTRY["stablelm-3b"].reduced()
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    server = BatchedServer(model, params, slots=B, max_len=T, aot=False)
    step = server._get_decode()
    closed = jax.make_jaxpr(step)(params, server.cache,
                                  jnp.zeros((B, 1), jnp.int32),
                                  jnp.asarray(RAGGED))
    (call,) = closed.jaxpr.eqns
    assert call.params["name"] == "decode_and_pick"
    top = call.params["jaxpr"].jaxpr
    stacked = model.cache_shapes(B, T)["k"].shape
    layer = stacked[1:]
    rows = stacked[:-1] + (1,)

    (scan,) = [e for e in top.eqns if e.primitive.name == "scan"]
    n_consts = scan.params["num_consts"]
    looped = scan.invars[n_consts:] + scan.outvars
    assert not [v for v in looped if v.aval.shape in (stacked, layer)]
    assert not [e for e in _eqns(scan.params["jaxpr"].jaxpr)
                if e.primitive.name in ("scatter", "dynamic_update_slice")]

    writes = [e for e in top.eqns
              if any(v.aval.shape == stacked for v in e.outvars)]
    cache_in = set(top.invars[-4:-2])     # toks and pos come last
    assert len(writes) == 2               # K and V
    for e in writes:
        assert [v.aval.shape for v in e.invars] == [(1, B, 1, 1, T), rows,
                                                    stacked]
        assert e.invars[2] in cache_in
        assert "select_n" in {x.primitive.name for x in _eqns(
            e.params["jaxpr"].jaxpr)}
