"""The chip smoke's phases, rehearsed on the CPU: ``stablelm-3b``'s
``reduced()`` preset served end to end with Pallas interpreted, the
kernels at the reduced widths of the configs that use them, and the
entry point's refusal of a machine without a TPU."""
import importlib.util
import os

import jax
import pytest

from repro.configs import get_config
from repro.kernels import ops

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def compiles(smoke):
    log = smoke.CompileLog()
    yield log
    log.close()


def test_kernel_phase_on_reduced_widths(smoke):
    cfgs = {a: get_config(a).reduced() for a in smoke.KERNEL_ARCHS}
    lines = []
    assert smoke.kernel_phase(cfgs, seq=64, seed=0, log=lines.append) == 5
    assert len(lines) == 5


def test_serving_and_reintegration_phases_on_reduced_preset(smoke,
                                                            compiles):
    cfg = get_config(smoke.SERVE_ARCH).reduced()
    lines = []
    gen0 = ops.generation("attention")
    server, prompts, tokens = smoke.serving_phase(
        cfg, seed=0, slots=4, max_len=64, buckets=(16, 32), max_new=8,
        compiles=compiles, log=lines.append)
    # on the CPU the two paths agree token for token
    assert tokens == smoke.generate_reference(server.model, server.params,
                                              prompts, 8)
    assert any("tokens equal to generate(): 64/64" in ln for ln in lines)
    assert len(prompts) == 8
    assert {server.bucket_of(len(p)) for p in prompts} == {16, 32}
    # the decode, its token input, and a prefill for each bucket at 1, 2
    # and 4 rows
    assert server.aot_compiles == len(server._exec) == 8

    agree = smoke.reintegration_phase(server, prompts, tokens, max_new=8,
                                      log=lines.append)
    assert 0 <= agree <= 64
    assert server.swap_epochs == 1 and server.aot_compiles == 16
    # the install was popped again: the registry is as it was
    assert ops.generation("attention") == gen0
    assert any("tokens equal to the jnp path" in ln for ln in lines)


def test_main_refuses_a_machine_without_a_tpu(smoke, capsys, monkeypatch):
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: None)
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""                      # no result line
    assert "needs a TPU" in err


@pytest.mark.parametrize("env_dir", [None, "/some/cache"])
def test_compile_cache_placement(monkeypatch, env_dir):
    from repro.launch import compile_cache
    set_calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_calls.append((k, v)))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert set_calls == []            # JAX's own default applies
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert set_calls == [("jax_compilation_cache_dir", want)]
