"""Grouped (per-expert) matmuls as Pallas TPU kernels.

``grouped_matmul``: classic tiled GEMM with a leading expert grid
dimension, grid (E, M/bm, N/bn, K/bk), fp32 accumulator in VMEM,
MXU-aligned tiles; a fixed row count per expert (the MEP loop's
``moe_gemm`` case).

``ragged_matmul``: the served expert layer's kernel.  Routed rows sorted
by expert, each expert's rows a whole number of row tiles; the grid runs
only the tiles that hold rows, and each tile reads its own expert's
weight, so an expert that no token picked costs nothing.
``ragged_matmul_at`` reads one layer's weights out of the layer stack
where they lie, as the served layer scan needs.
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


# --------------------------------------------------------------------------
# Ragged grouped matmul: the served expert layer's kernel
# --------------------------------------------------------------------------
KERNEL_NAME = "moe_ragged_matmul"


def row_tile(rows: int) -> int:
    """Row tile of the ragged matmul for ``rows`` routed picks: 16 (the
    bf16 sublane tiling) where a group holds a few rows, as in decode;
    128, the MXU's height, where groups are hundreds of rows long."""
    return 16 if rows < 2048 else 128


def group_layout(group, n_groups: int, tile_m: int):
    """Where each routed pick goes in the ragged matmul's row buffer.

    ``group`` [P] is each pick's group (a held expert, ``0 .. n_groups-1``,
    or ``n_groups`` for a pick this chip does not hold).  Each group's
    rows start at a multiple of ``tile_m``, in group order, so a row tile
    belongs to one group.  Returns ``dest`` [P], the buffer row of each
    pick (``rows``, one past the buffer, for a pick not held), ``sizes``
    [n_groups], the groups' row counts padded to the tile, and ``rows``,
    the buffer's static length, enough for every pick in one group each
    with a partly filled last tile."""
    P = group.shape[0]
    rows = -(-P // tile_m) * tile_m + n_groups * tile_m
    onehot = (group[:, None] == jnp.arange(n_groups)[None, :]).astype(
        jnp.int32)                                        # [P, G]
    count = jnp.sum(onehot, axis=0)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    sizes = (count + tile_m - 1) // tile_m * tile_m
    start = jnp.cumsum(sizes) - sizes
    held = group < n_groups
    dest = jnp.where(held, start[jnp.minimum(group, n_groups - 1)] + rank,
                     rows)
    return dest, sizes, rows


def _ragged_kernel(tile_group_ref, layer_ref, x_ref, w_ref, o_ref, acc_ref,
                   *, n_k):
    del tile_group_ref, layer_ref   # read by the index maps only
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _weight_blocks(K: int, N: int, itemsize: int):
    """Blocks of one expert's [K, N] weight: N whole up to 1536 lanes
    (a block spanning the axis needs no padding, as a device's shard of
    an expert may), else 512; and K cut so one block is about 2 MiB,
    which two buffers of it hold well inside v5e's scoped VMEM."""
    bn, Np = (N, N) if N <= 1536 else fit_block(512, N, 128)
    bk, Kp = fit_block(max(128, (2 << 20) // (bn * itemsize)), K, 128)
    return bk, Kp, bn, Np


def _ragged_pallas(x, w, layer, sizes, tile_m: int,
                   interpret: Optional[bool]):
    """``w`` [L, G, K, N], of which the kernel reads layer ``layer``'s
    blocks where they lie: a slice ``w[layer]`` handed to the call would
    be copied out whole first, every group read whether it has rows or
    not."""
    M, K = x.shape
    _, G, _, N = w.shape
    bk, Kp, bn, Np = _weight_blocks(K, N, w.dtype.itemsize)
    if (Kp, Np) != (K, N):          # zero padding of K adds nothing
        x = jnp.pad(x, ((0, 0), (0, Kp - K)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, Kp - K), (0, Np - N)))
    tiles = sizes // tile_m
    # the group of each row tile; only the first ``sum(tiles)`` are run,
    # so a group with no rows has no tile and its weights are not read
    tile_group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                            total_repeat_length=M // tile_m)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    n_k = Kp // bk
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((M, Np), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Np // bn, jnp.sum(tiles), n_k),
            in_specs=[
                pl.BlockSpec((tile_m, bk), lambda n, t, k, tg, ly: (t, k)),
                pl.BlockSpec((None, None, bk, bn),
                             lambda n, t, k, tg, ly: (ly[0], tg[t], k, n)),
            ],
            out_specs=pl.BlockSpec((tile_m, bn),
                                   lambda n, t, k, tg, ly: (t, n)),
            scratch_shapes=[pltpu.VMEM((tile_m, bn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(tile_group, layer, x, w)
    return out[:, :N]


def ragged_matmul_at(x, w, layer, sizes, tile_m: int,
                     interpret: Optional[bool] = None):
    """``ragged_matmul`` with the weights of layer ``layer`` of a stack
    ``w`` [L, G, K, N], read in place (the served layer scan's form).
    Not differentiable."""
    return _ragged_pallas(x, w, layer, sizes, tile_m, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ragged_matmul(x, w, sizes, tile_m: int, interpret: Optional[bool] = None):
    """Row tile ``t`` of ``x`` [M, K] times the weight of its group:
    ``x[rows of g] @ w[g]`` for each group ``g`` of ``w`` [G, K, N].

    Groups are laid out as ``group_layout`` gives: group ``g`` holds
    ``sizes[g]`` rows (a multiple of ``tile_m``) after the groups before
    it.  Rows past ``sum(sizes)`` are left undefined.  On a TPU a Pallas
    kernel compiled for the chip; elsewhere the same kernel interpreted.
    The gradient is the reference's (``ref.ragged_matmul_ref``)."""
    return _ragged_pallas(x, w[None], 0, sizes, tile_m, interpret)


def _ragged_fwd(x, w, sizes, tile_m, interpret):
    return (_ragged_pallas(x, w[None], 0, sizes, tile_m, interpret),
            (x, w, sizes))


def _ragged_bwd(tile_m, interpret, res, g):
    from repro.kernels.ref import ragged_matmul_ref
    x, w, sizes = res
    _, vjp = jax.vjp(lambda x_, w_: ragged_matmul_ref(x_, w_, sizes), x, w)
    dx, dw = vjp(g)
    return dx, dw, None


ragged_matmul.defvjp(_ragged_fwd, _ragged_bwd)
