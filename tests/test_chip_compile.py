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
from repro.kernels.moe_gemm import (KERNEL_NAME, grouped_matmul,
                                    ragged_matmul_at, row_tile)
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


# the served expert layer of the benchmark's qwen2-moe cell: 15 held
# experts of 2048 -> 1408 -> 2048 in each of 24 layers, one layer's read
# out of the stack; a decode step of 24 slots x top-4 picks, and more
# than the largest packed prefill, 32 rows x 256 x top-4
@pytest.mark.parametrize("picks", [24 * 4, 32 * 256 * 4])
@pytest.mark.parametrize("into", ["up", "down"])
def test_ragged_matmul_compiles_at_the_held_share(one_chip, picks, into):
    m = get_config("qwen2-moe-a2.7b")
    G, d, f = 15, m.d_model, m.moe.d_ff_expert
    K, N = (d, f) if into == "up" else (f, d)
    tile_m = row_tile(picks)
    rows = -(-picks // tile_m) * tile_m + G * tile_m
    fn = functools.partial(ragged_matmul_at, tile_m=tile_m, interpret=False)
    txt = compile_for_chip(
        fn, [((rows, K), BF16), ((m.n_layers, G, K, N), BF16),
             ((), jnp.int32), ((G,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in txt and KERNEL_NAME in txt


def test_served_moe_decode_reads_the_expert_stack_in_place(one_chip,
                                                          monkeypatch):
    """The decode step hands the expert kernel every layer's weights at
    once, so no layer's held experts are copied out before the kernel
    reads the picked ones."""
    import dataclasses

    from repro.kernels import moe_gemm
    from repro.models import get_model
    # the model's kernels resolve to interpret mode on this CPU host
    monkeypatch.setattr(moe_gemm, "resolve_interpret",
                        lambda interpret=None: False)
    c = get_config("qwen2-moe-a2.7b").reduced()
    c = dataclasses.replace(c, moe=dataclasses.replace(
        c.moe, held_experts=2, first_expert=1))
    model = get_model(c)
    B, T = 4, 64

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = abstract(model.abstract_params())
    cache = abstract(jax.eval_shape(lambda: model.init_cache(B, T)))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    txt = jax.jit(model.decode_step).lower(params, cache, tok,
                                           pos).compile().as_text()
    stack = params["layers"]["we1"].shape          # [L, held, d, f]
    calls = [ln for ln in txt.splitlines()
             if "tpu_custom_call" in ln and KERNEL_NAME in ln]
    assert len(calls) == 3, calls
    want = "bf16[" + ",".join(map(str, stack))
    assert all(want in ln or "bf16[" + ",".join(
        map(str, (stack[0], stack[1], stack[3], stack[2]))) in ln
        for ln in calls), calls


@pytest.mark.parametrize("preset", ["default", "ep"])
def test_moe_decode_on_four_chips_reads_expert_shards_in_place(
        topo, monkeypatch, preset):
    """A decode step of qwen2-moe-a2.7b's expert layer (published widths,
    two layers) sharded over a described 2x2 v5e: each of the three
    expert kernels reads its device's shard of the weights where it
    rests, and no collective gathers an expert weight whole (a Pallas
    call gives the SPMD partitioner nothing to split)."""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.kernels import moe_gemm
    from repro.launch.mesh import make_ctx
    from repro.models import get_model
    monkeypatch.setattr(moe_gemm, "resolve_interpret",
                        lambda interpret=None: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    ctx = make_ctx(mesh, preset=preset)
    c = dataclasses.replace(get_config("qwen2-moe-a2.7b"), n_layers=2)
    model = get_model(c, ctx)
    B, T = 8, 256

    def abstract(tree, axes):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, ctx.tree_shardings(axes, tree))

    params = abstract(model.abstract_params(), model.param_axes())
    rep = NamedSharding(mesh, P())
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: model.init_cache(B, T)))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=rep)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep)
    with jax.set_mesh(mesh):
        txt = jax.jit(model.decode_step).lower(params, cache, tok,
                                               pos).compile().as_text()
    E, d, f = c.moe.n_experts, c.d_model, c.moe.d_ff_expert
    e_l, d_l, f_l = ((E // 2, d // 2, f) if preset == "ep"
                     else (E, d // 2, f // 2))
    calls = [ln for ln in txt.splitlines()
             if "tpu_custom_call" in ln and KERNEL_NAME in ln]
    assert len(calls) == 3, calls
    shard = ("bf16[1,%d,%d,%d]" % (e_l, d_l, f_l),
             "bf16[1,%d,%d,%d]" % (e_l, f_l, d_l))
    assert all(any(s in ln for s in shard) for ln in calls), calls
    gathered = [tuple(int(n) for n in g.split(",") if n) for g in
                re.findall(r"= \w+\[([\d,]*)\][^=]*all-gather\(", txt)]
    whole = {(E, d, f), (E, f, d), (2, E, d, f), (2, E, f, d)}
    assert not whole & set(gathered), gathered


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
