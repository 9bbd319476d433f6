"""RWKV6 WKV recurrence as a Pallas TPU kernel.

The state S [K, V] lives in VMEM scratch for the whole sequence — the chunk
loop is the innermost ('arbitrary') grid dimension, so there are no
HBM round-trips of the state between chunks (the XLA reference path carries
it through scan-carry buffers instead).  Within a chunk the recurrence runs
as an in-VMEM fori_loop; per-channel decays stay exact (no pairwise
factorization, DESIGN.md / models/ssm.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *,
                chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)       # [1, K] bonus row
    K = u.shape[1]
    # k_t and w_t act down the state's rows: turn each [1, K] row into a
    # [K, 1] column by a masked lane reduction (no transpose)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1))

    def col(row):
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def step(t, s):
        # token t is read from and written to the refs: slicing a loaded
        # value at a traced index does not lower on the TPU
        row = pl.ds(t, 1)
        r_t = r_ref[0, row, :].astype(jnp.float32)            # [1, K]
        k_t = k_ref[0, row, :].astype(jnp.float32)            # [1, K]
        v_t = v_ref[0, row, :].astype(jnp.float32)            # [1, V]
        w_t = jnp.exp(lw_ref[0, row, :].astype(jnp.float32))  # [1, K]
        kv_t = col(k_t) * v_t                                 # [K, V]
        # o_t = r_t @ (s + diag(u) kv_t)
        bonus = jnp.sum(r_t * u * k_t, axis=1, keepdims=True)  # [1, 1]
        o_t = jnp.dot(r_t, s, preferred_element_type=jnp.float32) \
            + bonus * v_t
        o_ref[0, row, :] = o_t.astype(o_ref.dtype)
        return col(w_t) * s + kv_t

    s_ref[...] = jax.lax.fori_loop(0, chunk, step, s_ref[...])


def wkv_pallas(r, k, v, lw, u, *, chunk: int = 64,
               interpret: Optional[bool] = None):
    """r/k/v/lw [B,S,H,K]; u [H,K] → (o [B,S,H,V], final state [B,H,K,V])."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    NC = S // chunk
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, K)
    # f32 in VMEM: one token is one sublane row, which a dynamic index
    # can address (packed bf16 rows cannot be sliced one at a time)
    rf, kf, vf, lwf = (fold(t).astype(jnp.float32) for t in (r, k, v, lw))
    uf = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    o = pl.pallas_call(
        kernel,
        grid=(B * H, NC),
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, V), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, V), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(rf, kf, vf, lwf, uf)
    o = o.reshape(B, H, S, V).transpose(0, 2, 1, 3).astype(r.dtype)
    # final state is recomputed cheaply outside the kernel when needed by
    # serving (decode keeps its own state); training only needs o.
    return o
