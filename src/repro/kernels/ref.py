"""Pure-jnp oracles for the framework's hotspot kernels.

Deliberately naive: direct transcription of the math (sequential
recurrences, full score matrices).  Every Pallas kernel sweeps
shapes/dtypes against these in tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def attention_ref(q, k, v, *, causal: bool = True, softcap: float = 0.0):
    """q [B,S,H,hd], k/v [B,T,KV,hd] (GQA) → [B,S,H,hd]; full score matrix."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qh = q.reshape(B, S, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bskgh,btkh->bkgst", qh, k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(hd))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    if causal:
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, hd).astype(q.dtype)


def wkv_ref(r, k, v, lw, u):
    """Sequential RWKV6 recurrence.  r/k/v/lw [B,S,H,K]; u [H,K].
    o_t = r_t·(S_{t-1} + u⊙k_t⊗v_t);  S_t = diag(w_t)S_{t-1} + k_t⊗v_t."""
    B, S, H, K = r.shape
    f32 = jnp.float32
    r, k, v, lw = (t.astype(f32) for t in (r, k, v, lw))

    def step(state, xs):
        r_t, k_t, v_t, w_t = xs
        o_t = jnp.einsum("bhk,bhkv->bhv", r_t, state) \
            + jnp.einsum("bhk,bhk,bhv->bhv", r_t, u.astype(f32) * k_t, v_t)
        state = jnp.exp(w_t)[..., None] * state + k_t[..., None] * v_t[..., None, :]
        return state, o_t

    xs = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, lw))
    state, o = lax.scan(step, jnp.zeros((B, H, K, K), f32), xs)
    return o.transpose(1, 0, 2, 3), state


def ssd_ref(xh, dt, a_log, B_t, C_t):
    """Sequential Mamba-2 SSD.  xh [B,S,H,P]; dt [B,S,H]; a_log [H];
    B_t/C_t [B,S,N].  h_t = a_t h_{t-1} + (dt_t x_t)⊗B_t;  y_t = C_t·h_t."""
    Bb, S, H, P = xh.shape
    N = B_t.shape[-1]
    f32 = jnp.float32
    a = jnp.exp(-jnp.exp(a_log.astype(f32))[None, None] * dt.astype(f32))
    u = dt.astype(f32)[..., None] * xh.astype(f32)

    def step(h, xs):
        a_t, u_t, b_t, c_t = xs
        h = a_t[..., None, None] * h + jnp.einsum("bhp,bn->bhpn", u_t, b_t)
        y = jnp.einsum("bn,bhpn->bhp", c_t, h)
        return h, y

    xs = (a.transpose(1, 0, 2), u.transpose(1, 0, 2, 3),
          B_t.astype(f32).transpose(1, 0, 2), C_t.astype(f32).transpose(1, 0, 2))
    h, y = lax.scan(step, jnp.zeros((Bb, H, P, N), f32), xs)
    return y.transpose(1, 0, 2, 3).astype(xh.dtype), h


def grouped_matmul_ref(x, w):
    """x [E,M,K] @ w [E,K,N] → [E,M,N] (MoE expert GEMM)."""
    return jnp.einsum("emk,ekn->emn", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


def ragged_matmul_ref(x, w, sizes):
    """x [M,K] whose first ``sizes[0]`` rows take w[0], the next
    ``sizes[1]`` w[1], ...; rows past ``sum(sizes)`` give zeros."""
    return lax.ragged_dot(x, w, sizes.astype(jnp.int32),
                          preferred_element_type=jnp.float32).astype(x.dtype)
