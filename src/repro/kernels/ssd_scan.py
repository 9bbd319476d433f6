"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Per (batch, head-block) lane the chunk loop is the innermost 'arbitrary'
grid dim with the state [Hb, P, N] resident in VMEM.  Each chunk is the
closed-form SSD block (pairwise scalar-decay matrix + two matmuls) — the
MXU-friendly restructuring of the CUDA selective-scan (DESIGN.md §3).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _ssd_kernel(u_ref, la_ref, b_ref, c_ref, y_ref, s_ref):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    Bm = b_ref[0].astype(f32)                 # [c, N]
    Cm = c_ref[0].astype(f32)                 # [c, N]
    Hb, c = u_ref.shape[1], u_ref.shape[2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    causal = rows >= cols
    eye = rows == cols
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)     # [t, s]

    def head(h, carry):
        # every product is 2-D: the TPU lowering has no batched matmul
        u = u_ref[0, h].astype(f32)           # [c, P]  (dt·x)
        # in-chunk prefix sum of the log decays (≤ 0) as a product with
        # a lower-triangular matrix of ones: the TPU lowering has no cumsum
        la = jnp.dot(causal.astype(f32), la_ref[0, h].astype(f32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=f32)             # [c, 1]
        la_row = jnp.sum(jnp.where(eye, la, 0.0), axis=0,
                         keepdims=True)                      # [1, c]
        dmat = jnp.where(causal, jnp.exp(la - la_row), 0.0)  # [t, s]
        y_intra = jnp.dot(cb * dmat, u, preferred_element_type=f32)
        s_prev = s_ref[h]                                    # [P, N]
        y_cross = jnp.exp(la) * jax.lax.dot_general(
            Cm, s_prev, (((1,), (1,)), ((), ())),
            preferred_element_type=f32)                      # [c, P]
        la_end = la[c - 1:c, :]                              # [1, 1]
        du = jnp.exp(la_end - la) * u                        # [s, P]
        upd = jnp.dot(du.T, Bm, preferred_element_type=f32)  # [P, N]
        s_ref[h] = jnp.exp(jnp.sum(la_end)) * s_prev + upd    # scalar decay
        y_ref[0, h] = (y_intra + y_cross).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, Hb, head, 0)


def ssd_pallas(xh, dt, a_log, B_t, C_t, *, chunk: int = 128,
               block_h: int = 8, interpret: Optional[bool] = None):
    """xh [B,S,H,P]; dt [B,S,H]; a_log [H]; B_t/C_t [B,S,N] → y [B,S,H,P].
    Matches ref.ssd_ref (output only; serving keeps its own state)."""
    Bb, S, H, P = xh.shape
    N = B_t.shape[-1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    NC = S // chunk
    # heads per grid step; the kernel walks them one at a time, and a
    # block of all heads outgrows VMEM at hymba's 50
    block_h = min(block_h, H)
    while H % block_h:
        block_h -= 1
    nH = H // block_h

    f32 = jnp.float32
    # heads lead, so a head's chunk is one [chunk, P] tile
    u = (dt.astype(f32)[..., None] * xh.astype(f32)).transpose(0, 2, 1, 3)
    la_step = -jnp.exp(a_log.astype(f32))[None, None] * dt.astype(f32)
    la_step = la_step.transpose(0, 2, 1)[..., None]          # [B,H,S,1]

    y = pl.pallas_call(
        _ssd_kernel,
        grid=(Bb, nH, NC),
        in_specs=[
            pl.BlockSpec((1, block_h, chunk, P),
                         lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, block_h, chunk, 1),
                         lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ci: (b, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_h, chunk, P),
                               lambda b, h, ci: (b, h, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, H, S, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((block_h, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(u, la_step, B_t, C_t)
    return y.transpose(0, 2, 1, 3)
