"""Numerical equivalence of the §Perf sharding variants, run on 8 fake
devices in subprocesses: context-parallel attention (incl. SSM/hybrid
families), shard_map MoE combine-before-reduce, and the sequence-sharded
flash-decode cache layout."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(body: str, devices: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax
        import jax.numpy as jnp
        import numpy as np
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import get_model
        from repro.launch.mesh import make_smoke_mesh, make_ctx
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.parametrize("arch", ["glm4-9b", "hymba-1.5b", "rwkv6-7b",
                                  "command-r-35b"])
def test_context_parallel_forward_matches(arch):
    run_subprocess(f"""
        cfg = dataclasses.replace(get_config("{arch}").reduced(),
                                  param_dtype="float32")
        mesh = make_smoke_mesh()
        m0 = get_model(cfg)
        params = m0.init_params(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab_size)
        want, _, _ = jax.jit(m0.forward)(params, toks)
        ctx = make_ctx(mesh, preset="cp")
        m1 = get_model(cfg, ctx)
        with jax.set_mesh(mesh):
            p_sh = jax.tree.map(jax.device_put, params,
                                ctx.tree_shardings(m1.param_axes(), params))
            got, _, _ = jax.jit(m1.forward)(
                p_sh, jax.device_put(toks, NamedSharding(mesh, P("data", None))))
        err = float(jnp.max(jnp.abs(np.asarray(got) - np.asarray(want))))
        assert err < 3e-3, err
        print("CP_OK", err)
    """)


def test_moe_shard_map_combine_matches_einsum():
    run_subprocess("""
        # the single-device model routes dropless; a capacity of every
        # pick an expert could get keeps the capacity dispatch from
        # dropping any, so the two agree
        cfg = get_config("dbrx-132b").reduced()
        cfg = dataclasses.replace(cfg, param_dtype="float32",
                                  moe=dataclasses.replace(
                                      cfg.moe, capacity_factor=float(
                                          cfg.moe.n_experts)
                                      / cfg.moe.top_k))
        mesh = make_smoke_mesh()
        m0 = get_model(cfg)
        params = m0.init_params(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab_size)
        want, _, _ = jax.jit(m0.forward)(params, toks)
        ctx = make_ctx(mesh, preset="default", moe_impl="shard_map",
                       seq_shard=False)
        m1 = get_model(cfg, ctx)
        with jax.set_mesh(mesh):
            p_sh = jax.tree.map(jax.device_put, params,
                                ctx.tree_shardings(m1.param_axes(), params))
            got, _, _ = jax.jit(m1.forward)(
                p_sh, jax.device_put(toks, NamedSharding(mesh, P("data", None))))
        err = float(jnp.max(jnp.abs(np.asarray(got) - np.asarray(want))))
        assert err < 3e-3, err
        print("MOE_SM_OK", err)
    """)


def test_tp_seq_decode_matches_local():
    """decode with the cache sequence dim sharded on the model axis
    (flash-decode LSE combine) equals local decode."""
    run_subprocess("""
        cfg = dataclasses.replace(get_config("glm4-9b").reduced(),
                                  param_dtype="float32")
        mesh = make_smoke_mesh()
        m0 = get_model(cfg)
        params = m0.init_params(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab_size)
        logits0, cache0 = m0.prefill(params, toks, max_len=32)
        tok = jnp.argmax(logits0[:, -1, :cfg.vocab_size],
                         axis=-1)[:, None].astype(jnp.int32)
        want, _ = m0.decode_step(params, cache0, tok, jnp.int32(16))

        from repro.sharding.ctx import DEFAULT_RULES
        ctx = make_ctx(mesh, preset="default").replace(
            rules=dict(DEFAULT_RULES, kv_seq="__tp__", kv_heads=None),
            decode_kv="tp_seq")
        m1 = get_model(cfg, ctx)
        with jax.set_mesh(mesh):
            p_sh = jax.tree.map(jax.device_put, params,
                                ctx.tree_shardings(m1.param_axes(), params))
            cache_sh = ctx.tree_shardings(m1.cache_axes(),
                                          m1.cache_shapes(4, 32))
            cache1 = jax.tree.map(jax.device_put, cache0, cache_sh)
            got, _ = jax.jit(m1.decode_step)(p_sh, cache1, tok, jnp.int32(16))
        err = float(jnp.max(jnp.abs(
            np.asarray(got[..., :cfg.vocab_size])
            - np.asarray(want[..., :cfg.vocab_size]))))
        assert err < 3e-3, err
        print("TPSEQ_DECODE_OK", err)
    """)


def test_kv_quant_decode_matches_exact():
    """int8 KV cache (per-position scales) keeps greedy decode identical
    and logits within quantization noise."""
    run_subprocess("""
        cfg = dataclasses.replace(get_config("codeqwen1.5-7b").reduced(),
                                  param_dtype="float32")
        m0 = get_model(cfg)
        m1 = get_model(cfg, kv_quant=True)
        params = m0.init_params(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                  cfg.vocab_size)
        l0, c0 = m0.prefill(params, toks[:, :16], max_len=24)
        l1, c1 = m1.prefill(params, toks[:, :16], max_len=24)
        for i in range(16, 24):
            g0, c0 = m0.decode_step(params, c0, toks[:, i:i+1], jnp.int32(i))
            g1, c1 = m1.decode_step(params, c1, toks[:, i:i+1], jnp.int32(i))
        err = float(jnp.max(jnp.abs(g0[..., :cfg.vocab_size]
                                    - g1[..., :cfg.vocab_size])))
        agree = bool(jnp.all(jnp.argmax(g0[..., :cfg.vocab_size], -1)
                             == jnp.argmax(g1[..., :cfg.vocab_size], -1)))
        assert err < 0.25 and agree, (err, agree)
        print("KV_QUANT_OK", err)
    """, devices=1)
