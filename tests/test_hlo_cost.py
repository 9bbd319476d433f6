"""Validate the while-aware HLO cost walker against XLA's cost_analysis on
scan-free modules, and its trip-count multiplication on scanned ones."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.launch import hlo_cost


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_matches_cost_analysis_scan_free():
    def fn(a, b):
        return jnp.tanh(a @ b) @ b

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    compiled = _compiled_text(fn, a, a)
    got = hlo_cost.analyze(compiled.as_text())
    want = compiled.cost_analysis()["flops"]
    # dot flops dominate; elementwise accounting differs slightly
    assert abs(got.flops - want) / want < 0.05


def test_while_trip_count_multiplies():
    def fn(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), ()
        c, _ = lax.scan(body, x, None, length=13)
        return c

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = _compiled_text(fn, x, w)
    got = hlo_cost.analyze(compiled.as_text())
    per_iter = 2 * 64 * 128 * 128
    assert got.flops >= 13 * per_iter                    # walker multiplies
    assert compiled.cost_analysis()["flops"] \
        < 3 * per_iter                                   # XLA does not


def test_nested_while():
    def fn(x, w):
        def outer(c, _):
            def inner(d, _):
                return jnp.tanh(d @ w), ()
            d, _ = lax.scan(inner, c, None, length=4)
            return d, ()
        c, _ = lax.scan(outer, x, None, length=5)
        return c

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = _compiled_text(fn, x, w)
    got = hlo_cost.analyze(compiled.as_text())
    per_iter = 2 * 32 * 64 * 64
    assert got.flops >= 20 * per_iter * 0.95


def test_f32_bytes_override_halves_float_traffic():
    def fn(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = _compiled_text(fn, a, a)
    full = hlo_cost.analyze(compiled.as_text(), f32_bytes=4)
    half = hlo_cost.analyze(compiled.as_text(), f32_bytes=2)
    assert abs(half.hbm_bytes * 2 - full.hbm_bytes) / full.hbm_bytes < 0.01


def test_shape_bytes_parser():
    assert hlo_cost.shape_bytes("f32[4,8]{1,0}") == 128
    assert hlo_cost.shape_bytes("bf16[10]") == 20
    assert hlo_cost.shape_bytes("(f32[2,2], s32[3])") == 28
    assert hlo_cost.shape_bytes("pred[16,16,2,1,256,4096]{5,4,3,2,1,0}") \
        == 16 * 16 * 2 * 256 * 4096
    assert hlo_cost.shape_elems("f32[]") == 1


def test_dus_alias_bytes_model():
    """Scan-carry DUS must not count the whole buffer every iteration."""
    def fn(buf, upd):
        def body(b, i):
            return lax.dynamic_update_slice(b, upd, (i * 4, 0)), ()
        b, _ = lax.scan(body, buf, jnp.arange(16))
        return b

    buf = jax.ShapeDtypeStruct((4096, 256), jnp.float32)
    upd = jax.ShapeDtypeStruct((4, 256), jnp.float32)
    compiled = jax.jit(fn).lower(buf, upd).compile()
    got = hlo_cost.analyze(compiled.as_text())
    whole_buffer_every_iter = 16 * 4096 * 256 * 4
    assert got.hbm_bytes < whole_buffer_every_iter
