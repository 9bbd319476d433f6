"""What every Pallas kernel here asks of the backend: where it runs
(compiled for the chip, or interpreted) and which block shapes the TPU
lowering accepts.

Every ``pl.pallas_call`` in this package resolves its ``interpret`` flag
here, at trace time.  Kernels compile for the chip whenever JAX has a TPU
backend; the interpreter serves only where no TPU backend exists (the CPU
tests).  Asking for interpret mode on a TPU is an error, so a kernel the
chip's compiler refuses can never hide behind the interpreter.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` → interpret exactly when the default backend is not a TPU.
    ``False`` compiles for the chip (what a test that lowers for a
    described TPU topology on a CPU host asks for)."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode requested on a TPU backend: "
                         "kernels are compiled for the chip there")
    return bool(interpret)


def fit_block(block: int, dim: int, align: int) -> Tuple[int, int]:
    """Block length for an axis of length ``dim``, and the padded length
    that the block tiles.  The TPU lowering takes a block that spans the
    whole axis or is a multiple of the axis's tiling ``align`` (8 rows,
    128 lanes): the largest such multiple ≤ ``block`` that divides ``dim``
    is chosen, and where none divides, the axis is padded up to a
    multiple of the aligned block."""
    b = max(align, block - block % align)
    if b >= dim:
        return dim, dim
    for cand in range(b, 0, -align):
        if dim % cand == 0:
            return cand, dim
    return b, -(-dim // b) * b
