"""Pallas kernel validation: shape/dtype sweeps in interpret mode against
the pure-jnp oracles in repro.kernels.ref, plus the model-internal chunked
algorithms vs the sequential references."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import (group_layout, grouped_matmul,
                                    ragged_matmul)
from repro.kernels.rwkv_wkv import wkv_pallas
from repro.kernels.ssd_scan import ssd_pallas
from repro.kernels.suites.pallas_lib import (elementwise_pallas,
                                             matmul_pallas,
                                             reduce_sum_pallas)
from repro.models.ssm import _ssd_chunked, _wkv_chunked

RNG = np.random.default_rng(42)


def randn(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


TOL = {jnp.float32: 2e-4, jnp.bfloat16: 5e-2}


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,H,KV,hd,bq,bk", [
    (128, 4, 2, 64, 64, 64),
    (256, 4, 4, 32, 128, 64),
    (64, 2, 1, 128, 64, 32),
    (128, 8, 2, 64, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, H, KV, hd, bq, bk, dtype):
    q = randn((2, S, H, hd), dtype)
    k = randn((2, S, KV, hd), dtype)
    v = randn((2, S, KV, hd), dtype)
    got = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


def test_flash_attention_noncausal():
    q, k, v = (randn((1, 128, 4, 32)) for _ in range(3))
    got = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,H,K,chunk", [
    (64, 2, 16, 16), (128, 4, 32, 32), (96, 2, 16, 32), (128, 2, 64, 64),
])
def test_wkv_pallas_sweep(S, H, K, chunk):
    r = randn((2, S, H, K), scale=0.5)
    k = randn((2, S, H, K), scale=0.5)
    v = randn((2, S, H, K), scale=0.5)
    lw = -jnp.abs(randn((2, S, H, K))) - 0.01
    u = randn((H, K), scale=0.5)
    got = wkv_pallas(r, k, v, lw, u, chunk=chunk)
    want, _ = ref.wkv_ref(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               rtol=1e-3, atol=1e-3)


def test_wkv_chunked_model_path_matches_ref():
    """The model's vectorized 3-phase chunked WKV is exact vs sequential."""
    r = randn((2, 96, 2, 16), scale=0.5)
    k = randn((2, 96, 2, 16), scale=0.5)
    v = randn((2, 96, 2, 16), scale=0.5)
    lw = -jnp.abs(randn((2, 96, 2, 16))) - 0.01
    u = randn((2 * 0 + 2, 16), scale=0.5)
    o, st = _wkv_chunked(r, k, v, lw, u, chunk=16, use_impl=False)
    want_o, want_st = ref.wkv_ref(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(want_st),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,H,P,N,chunk", [
    (64, 2, 16, 8, 16), (128, 4, 32, 16, 32), (128, 2, 64, 16, 64),
])
def test_ssd_pallas_sweep(S, H, P, N, chunk):
    xh = randn((2, S, H, P))
    dt = jnp.abs(randn((2, S, H), scale=0.3)) + 0.01
    a_log = randn((H,), scale=0.3)
    B_t, C_t = randn((2, S, N)), randn((2, S, N))
    got = ssd_pallas(xh, dt, a_log, B_t, C_t, chunk=chunk)
    want, _ = ref.ssd_ref(xh, dt, a_log, B_t, C_t)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ssd_chunked_model_path_matches_ref():
    xh = randn((2, 64, 4, 16))
    dt = jnp.abs(randn((2, 64, 4), scale=0.3)) + 0.01
    a_log = randn((4,), scale=0.3)
    B_t, C_t = randn((2, 64, 8)), randn((2, 64, 8))
    y, st = _ssd_chunked(xh, dt, a_log, B_t, C_t, chunk=16, use_impl=False)
    want_y, want_st = ref.ssd_ref(xh, dt, a_log, B_t, C_t)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(want_st),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("G,P,K,N,tile_m", [
    (5, 37, 64, 48, 16),          # decode-sized groups of a few rows
    (4, 300, 128, 256, 128),      # groups over several row tiles
    (3, 8, 256, 1408, 16),        # the expert width: one block of N
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_matmul_sweep(G, P, K, N, tile_m, dtype):
    """Each routed row times its own group's weight; a group with no rows
    (group 1 here) gets no row tile, and picks outside the held groups
    (id ``G``) get no row of the buffer."""
    group = jnp.asarray(RNG.choice([0] + list(range(2, G + 1)), size=P),
                        jnp.int32)
    dest, sizes, rows = group_layout(group, G, tile_m)
    assert int(sizes[1]) == 0 and (np.asarray(sizes) % tile_m == 0).all()
    held = np.asarray(group) < G
    assert sorted(np.asarray(dest)[held]) == sorted(set(
        np.asarray(dest)[held])) and (np.asarray(dest)[~held] == rows).all()
    x, w = randn((P, K), dtype), randn((G, K, N), dtype)
    buf = jnp.zeros((rows, K), dtype).at[dest].set(x, mode="drop")
    got = np.asarray(ragged_matmul(buf, w, sizes, tile_m, None), np.float32)
    want = np.einsum("pk,pkn->pn", np.asarray(x, np.float32), np.asarray(
        w, np.float32)[np.minimum(np.asarray(group), G - 1)])
    tol = TOL[dtype] * K ** 0.5
    np.testing.assert_allclose(got[np.asarray(dest)[held]], want[held],
                               rtol=tol, atol=tol)
    n = int(sizes.sum())
    np.testing.assert_allclose(
        got[:n], np.asarray(ref.ragged_matmul_ref(buf, w, sizes),
                            np.float32)[:n], rtol=tol, atol=tol)


def test_ragged_matmul_gradient_is_the_references():
    G, tile_m = 3, 16
    group = jnp.asarray(RNG.integers(0, G + 1, size=40), jnp.int32)
    dest, sizes, rows = group_layout(group, G, tile_m)
    buf = jnp.zeros((rows, 32)).at[dest].set(randn((40, 32)), mode="drop")
    w = randn((G, 32, 24))
    n = int(sizes.sum())

    def loss(f):
        return lambda b, w_: jnp.sum(jnp.sin(f(b, w_)[:n]))

    got = jax.grad(loss(lambda b, w_: ragged_matmul(b, w_, sizes, tile_m,
                                                    None)),
                   argnums=(0, 1))(buf, w)
    want = jax.grad(loss(lambda b, w_: ref.ragged_matmul_ref(b, w_, sizes)),
                    argnums=(0, 1))(buf, w)
    for g, h in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(h), rtol=1e-4,
                                   atol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("E,M,K,N,bm,bn,bk", [
    (4, 64, 32, 48, 32, 32, 16),
    (2, 128, 128, 128, 128, 64, 64),
    (8, 32, 16, 32, 64, 64, 64),       # blocks larger than dims → fitted
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(E, M, K, N, bm, bn, bk, dtype):
    x, w = randn((E, M, K), dtype), randn((E, K, N), dtype)
    got = grouped_matmul(x, w, block_m=bm, block_n=bn, block_k=bk)
    want = ref.grouped_matmul_ref(x, w)
    tol = TOL[dtype] * K ** 0.5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M,K,N,ep", [(64, 32, 48, "none"),
                                      (128, 128, 128, "alpha_beta"),
                                      (96, 64, 32, "relu")])
def test_matmul_pallas(M, K, N, ep):
    a, b = randn((M, K)), randn((K, N))
    c = randn((M, N))
    got = matmul_pallas(a, b, c if ep == "alpha_beta" else None,
                        block_m=32, block_n=32, block_k=32, epilogue=ep,
                        alpha=1.5, beta=1.2)
    want = a @ b
    if ep == "alpha_beta":
        want = 1.5 * want + 1.2 * c
    elif ep == "relu":
        want = jnp.maximum(want, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_reduce_and_elementwise_pallas():
    x = randn((8192,))
    np.testing.assert_allclose(float(reduce_sum_pallas(x, block=1024)),
                               float(jnp.sum(x)), rtol=1e-5, atol=1e-3)
    y = randn((8192,))
    np.testing.assert_allclose(
        np.asarray(elementwise_pallas(lambda a, b: a + b, x, y, block=2048)),
        np.asarray(x + y), rtol=1e-6, atol=1e-6)


def test_kernel_registry_integration():
    """Installing a pallas flash-attention variant changes the model's
    attention path but not its outputs."""
    import dataclasses
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import get_model

    cfg = dataclasses.replace(get_config("glm4-9b").reduced(),
                              param_dtype="float32")
    model = get_model(cfg, q_chunk=16)
    params = model.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)
    base, _, _ = model.forward(params, toks)

    def impl(q, k, v, causal=True, softcap=0.0):
        return flash_attention(q, k, v, causal=causal,
                               block_q=16, block_k=16)

    with ops.use_impl("attention", impl):
        swapped, _, _ = model.forward(params, toks)
    np.testing.assert_allclose(np.asarray(base), np.asarray(swapped),
                               rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
def test_interpret_mode_is_the_backends_choice(monkeypatch):
    from repro.kernels import backend
    assert backend.resolve_interpret() is True          # CPU: interpreted
    assert backend.resolve_interpret(False) is False    # compile for a chip
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
    assert backend.resolve_interpret() is False         # TPU: compiled
    with pytest.raises(ValueError):
        backend.resolve_interpret(True)                 # never interpreted


@pytest.mark.parametrize("block,dim,align,want", [
    (128, 64, 128, (64, 64)),        # block covers the axis: whole axis
    (32, 64, 8, (32, 64)),           # aligned divisor
    (32, 48, 128, (48, 48)),         # rounds up to 128 > 48: whole axis
    (512, 1408, 128, (128, 1408)),   # largest aligned divisor of 11 x 128
    (128, 1000, 128, (128, 1024)),   # none divides: pad
    (100, 1000, 8, (40, 1000)),      # 96..48 do not divide 1000; 40 does
])
def test_fit_block(block, dim, align, want):
    from repro.kernels.backend import fit_block
    assert fit_block(block, dim, align) == want


def test_matmul_pallas_pads_an_axis_no_block_divides():
    a, b = randn((200, 136)), randn((136, 260))
    got = matmul_pallas(a, b, block_m=64, block_n=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-4)
