"""Shared Pallas builders for the benchmark suites: tiled matmul with
epilogue fusion, blocked reduction, 1-D map.  Each takes variant-style
block parameters; blocks are fitted to what the TPU lowering accepts,
padding an axis that no legal block divides.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import fit_block, resolve_interpret


def _lane_align(dtype) -> int:
    """Tiling of a 1-D block: 128 lanes of 32-bit words; packed narrower
    dtypes hold proportionally more elements."""
    return 128 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _pad_to(x, shape):
    """Zero-pad ``x`` at the end of each axis up to ``shape``."""
    if x.shape == tuple(shape):
        return x
    return jnp.pad(x, [(0, n - d) for d, n in zip(x.shape, shape)])


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k, epilogue, alpha, beta,
               c_ref=None):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _done():
        acc = acc_ref[...]
        if epilogue == "alpha_beta":
            acc = alpha * acc + beta * c_ref[...].astype(jnp.float32)
        elif epilogue == "relu":
            acc = jnp.maximum(acc, 0.0)
        o_ref[...] = acc.astype(o_ref.dtype)


def matmul_pallas(a, b, c=None, *, block_m=128, block_n=128, block_k=128,
                  epilogue: str = "none", alpha: float = 1.0,
                  beta: float = 1.0, interpret: Optional[bool] = None):
    """O = epilogue(A @ B [, C]) with an fp32 VMEM accumulator."""
    M, K = a.shape
    N = b.shape[1]
    # rows tile by 8, lanes by 128; K is a's lane axis and b's row axis
    bm, Mp = fit_block(block_m, M, 8)
    bn, Np = fit_block(block_n, N, 128)
    bk, Kp = fit_block(block_k, K, 128)
    n_k = Kp // bk
    kernel = functools.partial(_mm_kernel, n_k=n_k, epilogue=epilogue,
                               alpha=alpha, beta=beta)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
        pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
    ]
    # zero padding of K adds nothing to A @ B; padded rows and columns of
    # the output are sliced off
    args = [_pad_to(a, (Mp, Kp)), _pad_to(b, (Kp, Np))]
    if epilogue == "alpha_beta":
        def kernel2(a_ref, b_ref, c_ref, o_ref, acc_ref):
            _mm_kernel(a_ref, b_ref, o_ref, acc_ref, n_k=n_k,
                       epilogue=epilogue, alpha=alpha, beta=beta, c_ref=c_ref)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)))
        args.append(_pad_to(c, (Mp, Np)))
        body = kernel2
    else:
        body = kernel
    return pl.pallas_call(
        body,
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(*args)[:M, :N]


def _reduce_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # the (1, 1) f32 output block stays resident across the 'arbitrary'
    # grid and is the accumulator (the TPU lowering cannot index a
    # ()-shaped ref)
    o_ref[...] += jnp.sum(x_ref[...].astype(jnp.float32))


def reduce_sum_pallas(x, *, block: int = 4096,
                      interpret: Optional[bool] = None):
    n = x.shape[0]
    blk, n_pad = fit_block(block, n, _lane_align(x.dtype))
    out = pl.pallas_call(
        _reduce_kernel,
        grid=(n_pad // blk,),
        in_specs=[pl.BlockSpec((blk,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(_pad_to(x, (n_pad,)))    # zeros add nothing to the sum
    return out[0, 0].astype(x.dtype)


def _map_kernel(fn, *refs):
    *in_refs, o_ref = refs
    o_ref[...] = fn(*[r[...] for r in in_refs]).astype(o_ref.dtype)


def elementwise_pallas(fn, *arrays, block: int = 8192,
                       interpret: Optional[bool] = None):
    """1-D fused map kernel: o = fn(*arrays)."""
    n = arrays[0].shape[0]
    blk, n_pad = fit_block(block, n, _lane_align(arrays[0].dtype))
    body = functools.partial(_map_kernel, fn)
    return pl.pallas_call(
        body,
        grid=(n_pad // blk,),
        in_specs=[pl.BlockSpec((blk,), lambda i: (i,)) for _ in arrays],
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), arrays[0].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
    )(*[_pad_to(a, (n_pad,)) for a in arrays])[:n]
