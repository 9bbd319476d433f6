"""Core transformer layers: norms, RoPE, chunked/decode attention, MLP, MoE.

All functions are pure and mesh-agnostic: sharding enters only through the
``ShardCtx`` constraints, and compute hot-spots consult the kernel-variant
registry (``repro.kernels.ops``) so MEP-optimized Pallas variants can be
swapped in (the paper's "reintegration" step) without touching model code.

Shapes follow [batch, seq, heads, head_dim]; softmax/norm statistics are
computed in fp32 regardless of the activation dtype.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.sharding.ctx import ShardCtx

NEG_INF = -1e30


# --------------------------------------------------------------------------
# param-spec machinery: one table drives both init and logical axes
# --------------------------------------------------------------------------
def init_from_spec(key: jax.Array, spec: Dict[str, Tuple[Tuple[int, ...], Tuple]],
                   dtype) -> Dict[str, jax.Array]:
    params = {}
    for i, (name, (shape, _axes)) in enumerate(sorted(spec.items())):
        k = jax.random.fold_in(key, i)
        if name.startswith("ln") or name.endswith("_scale"):
            params[name] = jnp.ones(shape, dtype)
        elif name.startswith("b") or name.endswith("_bias"):
            params[name] = jnp.zeros(shape, dtype)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            std = 1.0 / math.sqrt(max(fan_in, 1))
            params[name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
    return params


def axes_from_spec(spec) -> Dict[str, Tuple]:
    return {name: axes for name, (shape, axes) in spec.items()}


# --------------------------------------------------------------------------
# norms and activations
# --------------------------------------------------------------------------
def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def act_fn(name: str):
    if name == "swiglu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu_sq":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# --------------------------------------------------------------------------
# rotary embeddings (partial-rotary aware)
# --------------------------------------------------------------------------
def rope(x, positions, theta: float, partial: float = 1.0):
    """x: [B, S, H, hd]; positions: [B, S] (or [S]) int32."""
    if theta <= 0.0:
        return x
    hd = x.shape[-1]
    rot = int(hd * partial)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    freqs = jnp.arange(0, rot, 2, dtype=jnp.float32) / rot
    inv = theta ** -freqs                                  # [rot/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions.astype(jnp.float32)[:, :, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# --------------------------------------------------------------------------
# attention parameter spec
# --------------------------------------------------------------------------
def attn_param_spec(cfg: ModelConfig, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    spec = {
        "wq": ((d, cfg.q_dim), ("d_model", "heads")),
        "wk": ((d, cfg.kv_dim), ("d_model", "kv_heads")),
        "wv": ((d, cfg.kv_dim), ("d_model", "kv_heads")),
        "wo": ((cfg.q_dim, d), ("heads", "d_model")),
    }
    if cfg.qkv_bias:
        spec["bq"] = ((cfg.q_dim,), ("heads",))
        spec["bk"] = ((cfg.kv_dim,), ("kv_heads",))
        spec["bv"] = ((cfg.kv_dim,), ("kv_heads",))
    if cfg.qk_norm:
        spec["q_scale"] = ((hd,), (None,))
        spec["k_scale"] = ((hd,), (None,))
    return spec


def _project_qkv(x, p, cfg: ModelConfig, ctx: ShardCtx, positions, x_kv=None):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    xk = x if x_kv is None else x_kv
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dq->bsq", xk, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", xk, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, xk.shape[1], cfg.n_kv_heads, hd)
    v = v.reshape(B, xk.shape[1], cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_scale"], cfg.norm_eps)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta, cfg.partial_rotary)
        kpos = positions if x_kv is None else jnp.arange(xk.shape[1])
        k = rope(k, kpos, cfg.rope_theta, cfg.partial_rotary)
    if ctx.attn_impl == "cp" and q.shape[1] > 1:
        # context parallel: everything stays sequence-sharded; the cp
        # attention wrapper gathers K/V itself
        q = ctx.constrain(q, "batch", "seq", None, None)
        k = ctx.constrain(k, "batch", "seq", None, None)
        v = ctx.constrain(v, "batch", "seq", None, None)
    else:
        q = ctx.constrain(q, "batch", None, "heads", None)
        k = ctx.constrain(k, "batch", None, "kv_heads", None)
        v = ctx.constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


# --------------------------------------------------------------------------
# chunked attention (train / prefill XLA reference path)
# --------------------------------------------------------------------------
def attention_chunked(q, k, v, *, causal: bool, ctx: ShardCtx,
                      q_chunk: int = 256, softcap: float = 0.0,
                      q_offset=0, use_impl: bool = True):
    """Flash-style q-chunked attention: O(S·chunk) score memory.

    This is the XLA reference lowering; when a Pallas flash-attention
    variant is activated in the kernel registry it takes over (TPU path).
    ``q_offset`` shifts the causal mask for context-parallel shards.
    """
    if use_impl:
        from repro.kernels import ops  # late import: kernels are optional
        impl = ops.get_impl("attention")
        if impl is not None:
            return impl(q, k, v, causal=causal, softcap=softcap)

    B, S, H, hd = q.shape
    T = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    q_chunk = min(q_chunk, S)
    while S % q_chunk:        # non-divisible seq (whisper's 1500 frames):
        q_chunk -= 1          # largest divisor ≤ requested chunk
    n_chunk = S // q_chunk
    qc = q.reshape(B, n_chunk, q_chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(T)

    def one_chunk(start_idx, qb):
        # qb: [B, c, KV, G, hd]
        s = jnp.einsum("bckgh,btkh->bkgct", qb, k).astype(jnp.float32) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        if causal:
            qpos = q_offset + start_idx * q_chunk + jnp.arange(q_chunk)
            mask = kpos[None, :] <= qpos[:, None]          # [c, t]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgct,btkh->bckgh", p, v)

    # chunk index lives in the scan *carry* so the causal mask is computed
    # in-loop rather than hoisted into an O(S²) precomputed buffer
    def scan_body(idx, qb):
        return idx + 1, one_chunk(idx, qb)

    _, outs = lax.scan(scan_body, jnp.zeros((), jnp.int32), qc)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)
    return ctx.constrain(out, "batch", None, "heads", None)


def attention_context_parallel(q, k, v, *, ctx: ShardCtx, q_chunk: int = 256,
                               softcap: float = 0.0):
    """Context-parallel causal attention: q stays sequence-sharded on the
    model axis; K/V (small under GQA) are all-gathered inside a shard_map
    and each shard attends its own query chunk with a shifted causal mask.
    Collective cost per layer = 2·|K,V| instead of 2·|residual| — the §Perf
    winner for GQA prefill (EXPERIMENTS.md §Perf)."""
    if not ctx.enabled:
        return attention_chunked(q, k, v, causal=True, ctx=ctx,
                                 q_chunk=q_chunk, softcap=softcap)
    from jax.sharding import PartitionSpec as P
    tp = ctx.tp
    n = ctx.axis_size(tp)
    B, S, H, hd = q.shape
    assert S % n == 0, (S, n)
    null = ShardCtx.null()

    def local(ql, kl, vl):
        kf = lax.all_gather(kl, tp, axis=1, tiled=True)
        vf = lax.all_gather(vl, tp, axis=1, tiled=True)
        off = lax.axis_index(tp) * (S // n)
        return attention_chunked(ql, kf, vf, causal=True, ctx=null,
                                 q_chunk=min(q_chunk, S // n),
                                 softcap=softcap, q_offset=off)

    spec = P(ctx.dp, tp, None, None)
    return jax.shard_map(local, mesh=ctx.mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ---- decode cache (shared-position and ragged per-slot) -----------------
# The stored K/V cache is [L, B, KV, hd, T]: per layer the [hd, T] operand
# the decode dots read, with the sequence minor.  That is also the TPU's
# own layout for such an array, so the step reads it in place and writes a
# row into it without relaying out the whole cache.
def to_cache_layout(x, seq_axis: int):
    """K/V (or scales) [..., S, KV, hd] with S at ``seq_axis`` → the
    cache's [..., KV, hd, S]."""
    return jnp.moveaxis(x, seq_axis, -1)


def cache_update(cache, new, pos):
    """Write ``new`` [L, B, KV, hd, 1] into the layer-stacked ``cache``
    [L, B, KV, hd, T] at ``pos``: every layer's row of a slot in one write.

    ``pos`` is either a scalar (all rows share one decode position — the
    fixed-batch path) or a [B] vector of per-slot positions (ragged
    continuous-batching decode, where every slot advances independently).
    """
    # A select over the whole cache: the step's output cache is a new
    # buffer (its input is not donated), so the copy is paid anyway and the
    # rows ride along in the same pass.  Along the minor sequence axis a
    # scatter makes the TPU compiler relayout the whole cache around it,
    # and an in-place update costs about as much as this copy (PERF.md).
    B, T = cache.shape[1], cache.shape[-1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    hit = jnp.arange(T) == pos[:, None]                     # [B, T]
    hit = hit.reshape((1, B) + (1,) * (cache.ndim - 3) + (T,))
    return jnp.where(hit, new.astype(cache.dtype), cache)


def decode_lengths(pos, batch: int):
    """Cached KV length per row before the token decoded at ``pos``
    (scalar or [B])."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,))


# ---- int8 KV-cache quantization (per-position, per-kv-head scales) ------
def kv_quantize(x):
    """x [..., hd] → (int8 values, bf16 scales [..., 1])."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def kv_dequantize(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def _decode_scores(qh, k, scale, softcap):
    s = jnp.einsum("bkgh,bkht->bkgt", qh, k).astype(jnp.float32) * scale
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    return s


def attention_decode(q, k_cache, v_cache, length: Optional[jax.Array] = None,
                     softcap: float = 0.0, k_scale=None, v_scale=None,
                     k_new=None, v_new=None):
    """Single-token decode: q [B, 1, H, hd] vs caches [B, KV, hd, T]
    (optionally int8 with per-position scales [B, KV, 1, T]).

    With ``k_new``/``v_new`` [B, KV, hd, 1], the token's own key and value,
    which the cache does not hold yet: it attends to the ``length`` cached
    positions and to itself in one softmax, so the cache is only read."""
    if k_scale is not None:
        k_cache = kv_dequantize(k_cache, k_scale)
        v_cache = kv_dequantize(v_cache, v_scale)
    B, _, H, hd = q.shape
    KV, T = k_cache.shape[1], k_cache.shape[-1]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qh = q.reshape(B, KV, G, hd)
    s = _decode_scores(qh, k_cache, scale, softcap)
    if length is not None:
        valid = jnp.arange(T)[None, :] < length[:, None]    # [B, T]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    if k_new is None:
        p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
        out = jnp.einsum("bkgt,bkht->bkgh", p, v_cache)
        return out.reshape(B, 1, H, hd)
    s_new = _decode_scores(qh, k_new, scale, softcap)       # [B, KV, G, 1]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_new)
    e, e_new = jnp.exp(s - m), jnp.exp(s_new - m)
    den = jnp.sum(e, axis=-1, keepdims=True) + e_new
    p = (e / den).astype(v_cache.dtype)
    p_new = (e_new / den).astype(v_cache.dtype)
    out = (jnp.einsum("bkgt,bkht->bkgh", p, v_cache,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgt,bkht->bkgh", p_new, v_new,
                        preferred_element_type=jnp.float32))
    return out.astype(q.dtype).reshape(B, 1, H, hd)


def flash_decode_sharded(q, k_cache, v_cache, ctx: ShardCtx,
                         length: Optional[jax.Array] = None, *, k_new, v_new,
                         seq_axes=None, batch_axes=(), k_scale=None,
                         v_scale=None):
    """Distributed flash-decode: the KV cache sequence dim (the last of
    [B, KV, hd, T]) is sharded over ``seq_axes``; each shard computes
    partial attention and the shards are combined with a log-sum-exp
    reduction (shard_map + psum).  The token's own ``k_new``/``v_new`` (see
    ``attention_decode``) join that combine as one more term.

    Two production uses:
      * long_500k — batch 1, seq over the data axes (seq_axes=ctx.dp)
      * decode_32k — batch over dp, seq over the model axis
        (batch_axes=ctx.dp, seq_axes=('model',)) so the cache fits HBM even
        when GQA head counts don't divide the TP degree."""
    if not ctx.enabled:
        return attention_decode(q, k_cache, v_cache, length, k_scale=k_scale,
                                v_scale=v_scale, k_new=k_new, v_new=v_new)
    seq_axes = tuple(seq_axes if seq_axes is not None else ctx.dp)
    batch_axes = tuple(batch_axes)

    B, _, H, hd = q.shape
    KV, T = k_cache.shape[1], k_cache.shape[-1]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    n_seq = ctx.axis_size(seq_axes)
    assert T % n_seq == 0, (T, seq_axes)
    quant = k_scale is not None

    def local(qh, kl, vl, lens, *rest):
        # qh [b,KV,G,hd]; kl/vl [b, KV, hd, T/n]; all batch-local shards;
        # int8 caches are dequantized per shard (tiny vs the full cache)
        if quant:
            ks, vs, *rest = rest
            kl = kv_dequantize(kl, ks)
            vl = kv_dequantize(vl, vs)
        tl = kl.shape[-1]
        shard = jnp.zeros((), jnp.int32)
        for ax in seq_axes:
            shard = shard * ctx.mesh.shape[ax] + lax.axis_index(ax)
        kpos = shard * tl + jnp.arange(tl)
        s = jnp.einsum("bkgh,bkht->bkgt", qh, kl).astype(jnp.float32) * scale
        if lens is not None:
            valid = kpos[None, :] < lens[:, None]
            s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)                               # [b,KV,G]
        e = jnp.exp(s - m[..., None])
        num = jnp.einsum("bkgt,bkht->bkgh", e, vl.astype(jnp.float32))
        den = jnp.sum(e, axis=-1)                             # [b,KV,G]
        # the new token's term is the same on every shard: it joins after
        # the reduction, once
        kn, vn = rest
        s_new = (jnp.einsum("bkgh,bkht->bkgt", qh, kn)[..., 0]
                 .astype(jnp.float32) * scale)                  # [b,KV,G]
        m_all = jnp.maximum(lax.pmax(m, seq_axes), s_new)
        c = jnp.exp(m - m_all)
        e_new = jnp.exp(s_new - m_all)
        num = (lax.psum(num * c[..., None], seq_axes)
               + e_new[..., None] * vn[:, :, None, :, 0].astype(jnp.float32))
        den = lax.psum(den * c, seq_axes) + e_new
        return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)

    qh = q.reshape(B, KV, G, hd)
    from jax.sharding import PartitionSpec as P
    bspec = batch_axes if batch_axes else None
    q_spec = P(bspec, None, None, None) if bspec else P()
    kv_spec = P(bspec, None, None, seq_axes)
    len_spec = P(bspec) if bspec else P()
    args, specs = [qh, k_cache, v_cache, length], [q_spec, kv_spec, kv_spec,
                                                   len_spec]
    if quant:
        args += [k_scale, v_scale]
        specs += [kv_spec, kv_spec]
    args += [k_new, v_new]
    specs += [q_spec, q_spec]
    out = jax.shard_map(local, mesh=ctx.mesh, in_specs=tuple(specs),
                        out_specs=q_spec, check_vma=False)(*args)
    return out.reshape(B, 1, H, hd)


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------
def mlp_param_spec(cfg: ModelConfig, d_ff: Optional[int] = None,
                   ffn_axis: str = "ffn"):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    spec = {
        "w1": ((d, f), ("d_model", ffn_axis)),
        "w2": ((f, d), (ffn_axis, "d_model")),
    }
    if cfg.act == "swiglu":
        spec["w3"] = ((d, f), ("d_model", ffn_axis))
    if cfg.mlp_bias:
        spec["b1"] = ((f,), (ffn_axis,))
        spec["b2"] = ((d,), ("d_model",))
    return spec


def mlp(x, p, cfg: ModelConfig, ctx: ShardCtx):
    a = act_fn(cfg.act)
    h = jnp.einsum("bsd,df->bsf", x, p["w1"])
    if cfg.mlp_bias:
        h = h + p["b1"]
    h = a(h)
    if cfg.act == "swiglu":
        h = h * jnp.einsum("bsd,df->bsf", x, p["w3"])
    if ctx.attn_impl == "cp":
        h = ctx.constrain(h, "batch", "seq", None)   # tokens stay sharded
    else:
        h = ctx.constrain(h, "batch", None, "ffn")   # Megatron TP
    out = jnp.einsum("bsf,fd->bsd", h, p["w2"])
    if cfg.mlp_bias:
        out = out + p["b2"]
    return out


# --------------------------------------------------------------------------
# Mixture of Experts
# --------------------------------------------------------------------------
def moe_param_spec(cfg: ModelConfig):
    """The router spans every expert; the expert weights are this chip's
    held block (``MoESpec.n_held`` of them, all by default)."""
    m = cfg.moe
    d, fe, eh = cfg.d_model, m.d_ff_expert, m.n_held
    spec = {
        "router": ((d, m.n_experts), ("d_model", "experts")),
        "we1": ((eh, d, fe), ("experts", "d_model", "expert_ffn")),
        "we2": ((eh, fe, d), ("experts", "expert_ffn", "d_model")),
        "we3": ((eh, d, fe), ("experts", "d_model", "expert_ffn")),
    }
    if m.n_shared:
        spec.update({
            "ws1": ((d, m.d_ff_shared), ("d_model", "ffn")),
            "ws2": ((m.d_ff_shared, d), ("ffn", "d_model")),
            "ws3": ((d, m.d_ff_shared), ("d_model", "ffn")),
            "ws_gate": ((d, 1), ("d_model", None)),
        })
    return spec


def moe_router_probs(x, p) -> jax.Array:
    """Softmax over every expert of float32 router logits (the product
    accumulated in float32, not a bf16 product cast after)."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"],
                        preferred_element_type=jnp.float32)
    return jax.nn.softmax(logits, axis=-1)


def moe_route(x, p, m):
    """The ``top_k`` experts of each token and their gates, renormalised
    to sum to one where the checkpoint does (``norm_topk_prob``)."""
    gate, eidx = lax.top_k(moe_router_probs(x, p), m.top_k)    # [B,S,K]
    if m.norm_topk_prob:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return gate, eidx


def _moe_capacity(S: int, m) -> int:
    c = int(math.ceil(S * m.top_k * m.capacity_factor / m.n_experts))
    return max(4, ((c + 3) // 4) * 4)


def _moe_capacity_shard_map(x, p, gate, eidx, cfg: ModelConfig,
                            ctx: ShardCtx):
    """Training's dispatch under a mesh: tokens routed within their own
    sequence (B stays on the data axes, so dispatch is communication-free)
    into ``capacity_factor``-sized expert buffers, a pick past its
    expert's capacity dropped; the expert-ffn dim on the TP axis."""
    from jax.sharding import PartitionSpec as P
    m = cfg.moe
    if m.n_held != m.n_experts:
        raise ValueError("the capacity dispatch computes every expert; "
                         f"this layer holds {m.n_held} of {m.n_experts}")
    B, S, d = x.shape
    E, K = m.n_experts, m.top_k
    a = act_fn(cfg.act)
    gate = gate.astype(x.dtype)
    C = _moe_capacity(S, m)
    ef = jnp.reshape(eidx, (B, S * K))                     # [B,T]
    gf = jnp.reshape(gate, (B, S * K))
    onehot = jax.nn.one_hot(ef, E, dtype=jnp.int32)        # [B,T,E]
    pos = jnp.cumsum(onehot, axis=1) - onehot              # pos within expert
    pos = jnp.sum(pos * onehot, axis=-1)                   # [B,T]
    keep = (pos < C).astype(x.dtype)
    xk = jnp.repeat(x, K, axis=1)                          # token s -> slots s*K+j
    pos_c = jnp.minimum(pos, C - 1)

    def scatter_one(buf, e_i, p_i, vals):
        return buf.at[e_i, p_i].add(vals)

    buf = jax.vmap(scatter_one)(
        jnp.zeros((B, E, C, d), x.dtype), ef, pos_c, xk * keep[..., None])

    def gather_one(y, e_i, p_i):
        return y[e_i, p_i]

    # combine-before-reduce: the expert-ffn output stays a PARTIAL sum
    # over the tp-sharded expert-ffn dim; gathering per-token slots first
    # means the psum moves [B,S,d] instead of the k·capacity× bigger
    # [B,E,C,d] (§Perf, dbrx train)
    def local(buf_l, w1, w3, w2, ef_l, pos_l, g_l):
        h = jnp.einsum("becd,edf->becf", buf_l, w1)
        h = a(h) * jnp.einsum("becd,edf->becf", buf_l, w3)
        ye = jnp.einsum("becf,efd->becd", h, w2)           # [b,E,C,d]
        yk = jax.vmap(gather_one)(ye, ef_l, pos_l) * g_l[..., None]
        out_p = jnp.sum(yk.reshape(yk.shape[0], -1, K, d), axis=2)
        return lax.psum(out_p, ctx.tp)

    dp, tp = ctx.dp, ctx.tp
    wspec = P(None, None, tp)
    return jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(dp, None, None, None), wspec, wspec, P(None, tp, None),
                  P(dp, None), P(dp, None), P(dp, None)),
        out_specs=P(dp, None, None), check_vma=False,
    )(buf, p["we1"], p["we3"], p["we2"], ef, pos_c, gf * keep)


def _moe_dropless(x, p, gate, eidx, cfg: ModelConfig, layer=None,
                  first=None, sum_d=None, sum_f=None):
    """Every pick that falls in the held block is computed, whatever the
    load: the batch's picks sorted by held expert into whole row tiles,
    the three expert matmuls as ragged grouped matmuls over the held
    groups (``kernels/moe_gemm.py``), and each output weighted by its gate
    and added back onto its token.  A pick of an expert this chip does
    not hold adds nothing here: under expert parallelism that part is
    another chip's.  With ``layer``, ``p``'s expert weights are the whole
    layer stack, of which the kernel reads layer ``layer`` in place.

    The held block is ``p``'s experts from ``first`` (the config's
    ``first_expert`` by default).  ``sum_d`` and ``sum_f`` complete the
    products whose contracted ``d_model`` or ``expert_ffn`` columns are
    split over devices (``_moe_dropless_mesh``)."""
    from repro.kernels import moe_gemm
    m = cfg.moe
    B, S, d = x.shape
    K, G = m.top_k, p["we1"].shape[-3]
    N = B * S
    first = m.first_expert if first is None else first
    sum_d = sum_d or (lambda t: t)
    sum_f = sum_f or (lambda t: t)
    local = eidx.reshape(N * K) - first
    group = jnp.where((local >= 0) & (local < G), local, G)
    tile_m = moe_gemm.row_tile(N * K)
    dest, sizes, rows = moe_gemm.group_layout(group, G, tile_m)
    # the token each buffer row holds (N, a zero row, for padding)
    src = jnp.full((rows,), N, jnp.int32).at[dest].set(
        jnp.arange(N * K, dtype=jnp.int32) // K, mode="drop")
    xt = jnp.concatenate([x.reshape(N, d), jnp.zeros((1, d), x.dtype)])
    buf = jnp.take(xt, src, axis=0)

    def gmm(lhs, w):
        if layer is None:
            return moe_gemm.ragged_matmul(lhs, w, sizes, tile_m, None)
        return moe_gemm.ragged_matmul_at(lhs, w, layer, sizes, tile_m)

    h = act_fn(cfg.act)(sum_d(gmm(buf, p["we1"])))
    h = h * sum_d(gmm(buf, p["we3"]))
    y = sum_f(gmm(h, p["we2"]))                            # [rows, d]
    # rows outside the groups are undefined: select, never multiply
    yk = jnp.take(y, jnp.minimum(dest, rows - 1), axis=0).astype(jnp.float32)
    yk = jnp.where((group < G)[:, None], yk * gate.reshape(N * K, 1), 0.0)
    return jnp.sum(yk.reshape(B, S, K, d), axis=2).astype(x.dtype)


def _moe_dropless_mesh(x, p, gate, eidx, cfg: ModelConfig, ctx: ShardCtx):
    """The dropless dispatch under a mesh, inside ``shard_map`` on each
    device's own shards of the expert weights, where they rest: a Pallas
    call gives the SPMD partitioner nothing to split, so handed the
    sharded weights it would gather them whole onto every device.

    Each device routes every token (its ``d_model`` columns) over the
    experts it holds; the products over split ``d_model`` and
    ``expert_ffn`` columns are summed across those devices, and the
    experts' outputs across the ``experts`` shards.  The output keeps the
    ``d_model`` split of the weights."""
    from jax.sharding import PartitionSpec as P
    ep, dm, ff = (tuple(ctx.spec(("experts", "d_model", "expert_ffn"),
                                 p["we1"].shape)) + (None,) * 3)[:3]

    def over(axes):
        return (lambda t: lax.psum(t, axes)) if axes else None

    def local(x_l, gate_l, eidx_l, w1, w3, w2):
        n = w1.shape[0]
        first = cfg.moe.first_expert + (lax.axis_index(ep) * n if ep else 0)
        out = _moe_dropless(x_l, {"we1": w1, "we3": w3, "we2": w2}, gate_l,
                            eidx_l, cfg, first=first, sum_d=over(dm),
                            sum_f=over(ff))
        return lax.psum(out, ep) if ep else out

    up, down = P(ep, dm, ff), P(ep, ff, dm)
    return jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(None, None, dm), P(), P(), up, up, down),
        out_specs=P(None, None, dm), check_vma=False,
    )(x, gate, eidx, p["we1"], p["we3"], p["we2"])


EXPERT_WEIGHTS = ("we1", "we2", "we3")


def moe_block(x, p, cfg: ModelConfig, ctx: ShardCtx, layer=None):
    """x: [B, S, d].  The routed experts this chip holds, plus the shared
    expert behind its sigmoid gate.  Routed dropless over the held
    block: on one device (serving) directly, under a mesh on each
    device's shards of the expert weights (``_moe_dropless_mesh``); a
    sharded train step with ``moe_impl == "shard_map"`` keeps its
    capacity dispatch.  With
    ``layer`` (dropless only), ``p``'s ``EXPERT_WEIGHTS`` are stacked over
    every layer and read at ``layer`` in place."""
    m = cfg.moe
    gate, eidx = moe_route(x, p, m)
    if ctx.moe_impl == "shard_map" and ctx.enabled:
        out = _moe_capacity_shard_map(x, p, gate, eidx, cfg, ctx)
    elif ctx.enabled:
        out = _moe_dropless_mesh(x, p, gate, eidx, cfg, ctx)
    else:
        out = _moe_dropless(x, p, gate, eidx, cfg, layer)

    if m.n_shared:
        a = act_fn(cfg.act)
        h = a(jnp.einsum("bsd,df->bsf", x, p["ws1"]))
        h = h * jnp.einsum("bsd,df->bsf", x, p["ws3"])
        sh = jnp.einsum("bsf,fd->bsd", h, p["ws2"])
        sgate = jax.nn.sigmoid(
            jnp.einsum("bsd,dg->bsg", x, p["ws_gate"]).astype(jnp.float32))
        out = out + sh * sgate.astype(x.dtype)
    return ctx.constrain(out, "batch", "seq", None)


def moe_aux_loss(x, p, cfg: ModelConfig) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style)."""
    m = cfg.moe
    probs = moe_router_probs(x, p)
    _, eidx = lax.top_k(probs, m.top_k)
    frac = jnp.mean(jax.nn.one_hot(eidx, m.n_experts, dtype=jnp.float32), axis=(0, 1, 2))
    imp = jnp.mean(probs, axis=(0, 1))
    return m.n_experts * jnp.sum(frac * imp)
