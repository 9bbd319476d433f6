"""The registry kernels compiled for a described TPU v5e, at the widths of
the configs that use them.

No chip is needed: the TPU compiler compiles for a chip that is described
and not attached, and refuses what the chip's would (block shapes off the
(8, 128) tiling, primitives the Pallas TPU lowering lacks, VMEM
overflow).  Each test asserts that the kernel is in the compiled program
as a ``tpu_custom_call``, i.e. compiled rather than interpreted.

Only one process may load the TPU library, and it keeps it until exit, so
the topology is described in a module fixture (never at import, in a
``parametrize`` argument, a ``skipif`` or ``conftest.py``) and all such
compiles live in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import grouped_matmul
from repro.kernels.rwkv_wkv import wkv_pallas
from repro.kernels.ssd_scan import ssd_pallas
from repro.kernels.suites.pallas_lib import (elementwise_pallas,
                                             matmul_pallas,
                                             reduce_sum_pallas)
from repro.models.ssm import mamba_dims


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compile_for_chip(fn, shapes, one_chip):
    """Lower and compile ``fn`` for one described v5e chip; return the
    compiled program's text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32 = jnp.bfloat16, jnp.float32


def _attention_shapes(arch, seq=1024):
    c = get_config(arch)
    hd = c.resolved_head_dim
    return [((1, seq, c.n_heads, hd), BF16),
            ((1, seq, c.n_kv_heads, hd), BF16),
            ((1, seq, c.n_kv_heads, hd), BF16)]


@pytest.mark.parametrize("arch", ["stablelm-3b",   # MHA 32 heads, hd 80
                                  "glm4-9b"])      # GQA 32/2, hd 128
def test_flash_attention_compiles(one_chip, arch):
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    assert "tpu_custom_call" in compile_for_chip(
        fn, _attention_shapes(arch), one_chip)


def test_grouped_matmul_compiles_at_qwen2_moe_widths(one_chip):
    m = get_config("qwen2-moe-a2.7b")
    E, K, N = m.moe.n_experts, m.d_model, m.moe.d_ff_expert
    fn = functools.partial(grouped_matmul, interpret=False)
    assert "tpu_custom_call" in compile_for_chip(
        fn, [((E, 128, K), BF16), ((E, K, N), BF16)], one_chip)


def test_wkv_compiles_at_rwkv6_widths(one_chip):
    c = get_config("rwkv6-7b")
    K = c.ssm.head_dim
    H = c.d_model // K
    shp = ((1, 512, H, K), BF16)
    fn = functools.partial(wkv_pallas, interpret=False)
    assert "tpu_custom_call" in compile_for_chip(
        fn, [shp, shp, shp, shp, ((H, K), BF16)], one_chip)


def test_ssd_compiles_at_hymba_widths(one_chip):
    c = get_config("hymba-1.5b")
    _, H, P = mamba_dims(c)
    N, S = c.ssm.state_dim, 512
    fn = functools.partial(ssd_pallas, chunk=c.ssm.chunk, interpret=False)
    assert "tpu_custom_call" in compile_for_chip(
        fn, [((1, S, H, P), BF16), ((1, S, H), BF16), ((H,), BF16),
             ((1, S, N), BF16), ((1, S, N), BF16)], one_chip)


@pytest.mark.parametrize("epilogue", ["none", "alpha_beta"])
def test_matmul_compiles_off_the_tiling(one_chip, epilogue):
    # 1000 is no multiple of 128: matmul_pallas pads instead of choosing
    # a block of 125
    shapes = [((1000, 1000), F32)] * (3 if epilogue == "alpha_beta" else 2)

    def fn(a, b, c=None):
        return matmul_pallas(a, b, c, epilogue=epilogue, interpret=False)
    assert "tpu_custom_call" in compile_for_chip(fn, shapes, one_chip)


def test_reduce_sum_compiles(one_chip):
    fn = functools.partial(reduce_sum_pallas, interpret=False)
    assert "tpu_custom_call" in compile_for_chip(
        fn, [((100_000,), F32)], one_chip)


def test_elementwise_compiles_off_the_tiling(one_chip):
    def fn(x, y):
        return elementwise_pallas(lambda a, b: a + b, x, y, interpret=False)
    assert "tpu_custom_call" in compile_for_chip(
        fn, [((100_000,), BF16)] * 2, one_chip)
