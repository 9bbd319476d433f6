"""Grouped (per-expert) matmul as a Pallas TPU kernel.

Classic tiled GEMM with a leading expert grid dimension: grid
(E, M/bm, N/bn, K/bk), fp32 accumulator in VMEM, MXU-aligned tiles.  Used
for the MoE expert FFN compute (site 'moe_gemm').
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import fit_block, resolve_interpret


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x, w, *, block_m: int = 128, block_n: int = 128,
                   block_k: int = 128, interpret: Optional[bool] = None):
    """x [E,M,K] @ w [E,K,N] → [E,M,N]."""
    E, M, K = x.shape
    N = w.shape[-1]
    # rows tile by 8, lanes by 128; K is x's lane axis and w's row axis
    bm, Mp = fit_block(block_m, M, 8)
    bn, Np = fit_block(block_n, N, 128)
    bk, Kp = fit_block(block_k, K, 128)
    if (Mp, Kp, Np) != (M, K, N):   # zero padding of K adds nothing
        x = jnp.pad(x, ((0, 0), (0, Mp - M), (0, Kp - K)))
        w = jnp.pad(w, ((0, 0), (0, Kp - K), (0, Np - N)))
    kernel = functools.partial(_gmm_kernel, n_k=Kp // bk)
    return pl.pallas_call(
        kernel,
        grid=(E, Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, ki: (e, i, ki)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, ki: (e, ki, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, ki: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x, w)[:, :M, :N]
