"""Production mesh construction.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state.  The single-pod mesh is 16×16 =
256 chips (one TPU v5e pod-slice); multi-pod adds a leading 'pod' axis
(2×16×16 = 512 chips) used as an extra data-parallel dimension whose
gradient all-reduce crosses DCN/ICI pod boundaries.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType

from repro.sharding.ctx import ShardCtx


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_ctx(mesh, preset: str = "default", **kw) -> ShardCtx:
    """Rule presets:
      default — 2D FSDP('data') × TP('model') with sequence-parallel
                activations (MoE + decode baseline)
      fsdp    — pure FSDP over all mesh axes, weights gathered per layer,
                no TP activation collectives (dense-train baseline)
      cp      — context parallel: batch on data, SEQUENCE on the model
                axis, weights FSDP over data, attention gathers only K/V
                (§Perf winner for GQA prefill)
      ep      — default + experts on the model axis (dbrx perf variant)
    """
    from repro.sharding.ctx import DEFAULT_RULES, EP_RULES, FSDP_RULES
    pod = ("pod",) if "pod" in mesh.axis_names else ()
    if preset == "fsdp":
        dp: Tuple[str, ...] = pod + ("data", "model")
        return ShardCtx(mesh=mesh, dp=dp, tp="model",
                        rules=dict(FSDP_RULES), seq_shard=False, **kw)
    if preset == "cp":
        all_axes = pod + ("data", "model")
        rules = dict(FSDP_RULES, seq="__tp__", d_model=all_axes)
        return ShardCtx(mesh=mesh, dp=pod + ("data",), tp="model",
                        rules=rules, attn_impl="cp",
                        fsdp_axes=all_axes, **kw)
    rules = dict(EP_RULES) if preset == "ep" else dict(DEFAULT_RULES)
    return ShardCtx(mesh=mesh, dp=pod + ("data",), tp="model",
                    rules=rules, **kw)


def make_smoke_mesh(n: int = 0):
    """Mesh over whatever local devices exist (tests use subprocesses with
    --xla_force_host_platform_device_count to get >1)."""
    n = n or len(jax.devices())
    model = 2 if n % 2 == 0 and n > 1 else 1
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware model (roofline constants)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (conservative: 1 link)
HBM_BYTES = 16 * 1024**3        # 16 GiB per chip
