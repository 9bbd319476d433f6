"""Multi-device behaviour, run in subprocesses with
--xla_force_host_platform_device_count=8 so the main test process keeps
seeing 1 device (per the dry-run isolation rule)."""
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
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    run_subprocess("""
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import get_model
        from repro.launch.mesh import make_smoke_mesh
        from repro.sharding.ctx import ShardCtx
        from repro.train import AdamWConfig, init_state
        from repro.train.steps import make_train_step
        from repro.data import SyntheticLMData, make_global_batch

        cfg = dataclasses.replace(get_config("stablelm-3b").reduced(),
                                  param_dtype="float32")
        mesh = make_smoke_mesh()         # (4, 2) over 8 fake cpu devices
        ctx = ShardCtx(mesh=mesh, dp=("data",), tp="model")
        data = SyntheticLMData(cfg, 32, 8, seed=1)

        # single-device reference
        m0 = get_model(cfg)
        params = m0.init_params(jax.random.PRNGKey(0))
        s0 = jax.jit(make_train_step(m0, AdamWConfig(lr=1e-3)))
        p_ref, _, m_ref = s0(params, init_state(params), data.batch(0))

        # sharded
        m1 = get_model(cfg, ctx)
        axes = m1.param_axes()
        p_sh = ctx.tree_shardings(axes, params)
        params_sh = jax.tree.map(jax.device_put, params, p_sh)
        opt = init_state(params_sh)
        with jax.set_mesh(mesh):
            s1 = jax.jit(make_train_step(m1, AdamWConfig(lr=1e-3)))
            batch = make_global_batch(
                data, 0, NamedSharding(mesh, P("data", None)))
            p1, _, m1_ = s1(params_sh, opt, batch)
        assert abs(float(m_ref["loss"]) - float(m1_["loss"])) < 1e-3, (
            float(m_ref["loss"]), float(m1_["loss"]))
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
        print("SHARDED_MATCH_OK")
    """)


def test_flash_decode_sharded_matches_local():
    """The sequence-sharded combine equals local decode attention over the
    cache's [B, KV, hd, T] layout, the token's own k/v row joined: a full
    cache, and ragged cached lengths with one of them empty."""
    run_subprocess("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_smoke_mesh
        from repro.sharding.ctx import ShardCtx
        from repro.models.layers import attention_decode, flash_decode_sharded

        mesh = make_smoke_mesh()
        ctx = ShardCtx(mesh=mesh, dp=("data",), tp="model")
        rng = np.random.default_rng(0)
        B, T, H, KV, hd = 2, 64, 8, 2, 16
        f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        q, k, v = f(B, 1, H, hd), f(B, KV, hd, T), f(B, KV, hd, T)
        k_new, v_new = f(B, KV, hd, 1), f(B, KV, hd, 1)
        full = jnp.full((B,), T, jnp.int32)
        ragged = jnp.asarray([0, T - 3], jnp.int32)
        new = {"k_new": k_new, "v_new": v_new}
        with jax.set_mesh(mesh):
            k_sh = jax.device_put(k, NamedSharding(mesh, P(None, None, None,
                                                           "data")))
            v_sh = jax.device_put(v, NamedSharding(mesh, P(None, None, None,
                                                           "data")))
            for lens in (full, ragged):
                want = attention_decode(q, k, v, lens, **new)
                got = jax.jit(lambda q, k, v, l, new:
                              flash_decode_sharded(q, k, v, ctx, l, **new))(
                    q, k_sh, v_sh, lens, new)
                np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                           rtol=2e-4, atol=2e-4)
        # the empty slot attends to its own row alone
        np.testing.assert_allclose(
            np.asarray(want[0, 0].reshape(KV, H // KV, hd)),
            np.broadcast_to(np.asarray(v_new[0, :, None, :, 0]),
                            (KV, H // KV, hd)), rtol=1e-6)
        print("FLASH_DECODE_OK")
    """)


def test_compressed_psum_shard_map():
    run_subprocess("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_smoke_mesh
        from repro.runtime.compress import compressed_psum

        mesh = make_smoke_mesh()
        n_data = mesh.shape["data"]
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((n_data * 4, 32)), jnp.float32)

        def f(xl):
            out, res = compressed_psum(xl, "data")
            return out

        with jax.set_mesh(mesh):
            got = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("data", None),
                out_specs=P("data", None)))(x)
        want = jnp.tile(jnp.sum(x.reshape(n_data, 4, 32), axis=0),
                        (n_data, 1))
        rel = float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want))))
        assert rel < 0.05, rel    # one int8 quantization of error
        print("COMPRESSED_PSUM_OK", rel)
    """)


def test_gather_fsdp_produces_allgather_not_allreduce():
    """The explicit FSDP weight gather must turn contraction-dim-sharded
    matmuls into weight all-gathers instead of activation all-reduces."""
    run_subprocess("""
        import re
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_smoke_mesh
        from repro.sharding.ctx import ShardCtx

        mesh = make_smoke_mesh()
        ctx = ShardCtx(mesh=mesh, dp=("data",), tp="model")

        def step(w, x):
            wg = ctx.gather_fsdp(w, ("d_model", "ffn"))
            return jnp.sum(jnp.tanh(x @ wg))

        w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
        x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
        with jax.set_mesh(mesh):
            c = jax.jit(jax.grad(step), in_shardings=(
                NamedSharding(mesh, P("data", "model")),
                NamedSharding(mesh, P("data", None)))).lower(w, x).compile()
        txt = c.as_text()
        assert " all-gather" in txt or "all-gather(" in txt
        # gradient flows back as reduce-scatter (FSDP semantics)
        print("GATHER_FSDP_OK")
    """)


def test_moe_dispatch_sharded_matches_single_device():
    run_subprocess("""
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import get_model
        from repro.launch.mesh import make_smoke_mesh
        from repro.sharding.ctx import ShardCtx

        cfg = dataclasses.replace(get_config("qwen2-moe-a2.7b").reduced(),
                                  param_dtype="float32")
        mesh = make_smoke_mesh()
        ctx = ShardCtx(mesh=mesh, dp=("data",), tp="model")
        m0 = get_model(cfg)
        m1 = get_model(cfg, ctx)
        params = m0.init_params(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab_size)
        want, _, _ = jax.jit(m0.forward)(params, toks)
        with jax.set_mesh(mesh):
            got, _, _ = jax.jit(m1.forward)(
                jax.tree.map(jax.device_put, params,
                             ctx.tree_shardings(m1.param_axes(), params)),
                jax.device_put(toks, NamedSharding(mesh, P("data", None))))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-3, atol=3e-3)
        print("MOE_SHARDED_OK")
    """)


@pytest.mark.parametrize("preset", ["default", "ep", "fsdp"])
def test_moe_dropless_under_a_mesh_matches_single_device(preset):
    """Under a mesh the dropless expert layer runs on each device's own
    shards of the expert weights (``layers._moe_dropless_mesh``): a
    forward pass and a decode step on a held share match the
    single-device model.  That the shards stay in place is checked where
    the kernel is a chip's custom call (``test_chip_compile.py``)."""
    out = run_subprocess(f"""
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import get_model
        from repro.launch.mesh import make_ctx, make_smoke_mesh

        cfg = get_config("qwen2-moe-a2.7b").reduced()
        cfg = dataclasses.replace(cfg, param_dtype="float32",
                                  moe=dataclasses.replace(
                                      cfg.moe, held_experts=2,
                                      first_expert=1))
        mesh = make_smoke_mesh()            # (4, 2): data x model
        ctx = make_ctx(mesh, preset="{preset}")
        m0, m1 = get_model(cfg), get_model(cfg, ctx)
        params = m0.init_params(jax.random.PRNGKey(0))
        sh = ctx.tree_shardings(m1.param_axes(), params)
        params_sh = jax.tree.map(jax.device_put, params, sh)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                  cfg.vocab_size)
        want, _, _ = jax.jit(m0.forward)(params, toks)
        B, T = 8, 32
        cache = m0.init_cache(B, T)
        tok = toks[:, :1]
        pos = jnp.arange(B, dtype=jnp.int32)
        want_d, _ = jax.jit(m0.decode_step)(params, cache, tok, pos)
        with jax.set_mesh(mesh):
            got, _, _ = jax.jit(m1.forward)(
                params_sh,
                jax.device_put(toks, NamedSharding(mesh, P("data", None))))
            got_d, _ = jax.jit(m1.decode_step)(params_sh, cache, tok, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-3, atol=3e-3)
        np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                                   rtol=3e-3, atol=3e-3)
        print("MOE_MESH_OK")
    """)
    assert "MOE_MESH_OK" in out
