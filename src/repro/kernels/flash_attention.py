"""Causal flash attention for TPU: pl.pallas_call + explicit BlockSpec VMEM
tiling, online softmax, GQA-aware, causal block skipping.

TPU adaptation of the CUDA flash pattern: the (q-block × k-block) grid maps
to pallas grid dimensions with the k loop marked 'arbitrary' so the running
max / denominator / accumulator live in VMEM scratch across k steps; tiles
are (block_q × head_dim) / (block_k × head_dim) with head_dim on the
128-lane axis.  Validated against ref.attention_ref: interpreted on the
CPU, compiled on the chip.
"""
from __future__ import annotations

import functools
from typing import Optional
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, block_q: int, block_k: int, causal: bool,
               n_k: int):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # k block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = j * block_k <= i * block_q + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                          s.shape, 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                          s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(j == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...][:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, block_q: int = 128, block_k: int = 128,
                         causal: bool = True,
                         interpret: Optional[bool] = None):
    """q [BH, S, hd], k/v [BH, T, hd] (GQA handled by the wrapper)."""
    BH, S, hd = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    n_q, n_k = S // block_q, T // block_k
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_fa_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max
            pltpu.VMEM((block_q,), jnp.float32),       # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, softcap: float = 0.0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Model-site signature: q [B,S,H,hd], k/v [B,T,KV,hd] (GQA)."""
    del softcap  # the pallas path does not implement softcap (glm4 uses 0)
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, T, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, T, hd)
    o = flash_attention_bhsd(qf, kf, vf, block_q=block_q, block_k=block_k,
                             causal=causal, interpret=interpret)
    return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
