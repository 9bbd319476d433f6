"""The plain reference computes what the program's model computes: in
float32 weights, the program's prefill logits equal the reference's."""
import numpy as np
import pytest

from bench import weights
from bench.harness import build_model_config
from bench.references import dense
from bench.tests import tiny


@pytest.mark.parametrize("qkv_bias,partial", [(True, 0.5), (False, 0.25)])
def test_reference_matches_the_program_in_float32(qkv_bias, partial):
    import jax
    import jax.numpy as jnp
    from repro.models import get_model
    m = dict(tiny.TINY_MODEL, param_dtype="float32", qkv_bias=qkv_bias,
             partial_rotary=partial)
    params = weights.make(dense.shapes(m), m, seed=5)
    model = get_model(build_model_config(m, "glm4-9b"))
    weights.check_layout(params, model.abstract_params())
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        for n in (1, 17, 40):
            want, _ = jax.jit(model.prefill)(params,
                                             jnp.asarray(toks[None, :n]))
            got = dense.logits(params, m, toks[:n].astype(np.int32), n - 1)
            np.testing.assert_allclose(
                got[0], np.asarray(want[0, -1, :m["vocab_size"]]),
                rtol=1e-4, atol=1e-4)


def test_int8_control_departs_from_the_reference():
    m = dict(tiny.TINY_MODEL)
    params = weights.make(dense.shapes(m), m, seed=6)
    toks = np.random.default_rng(1).integers(
        0, m["vocab_size"], 64).astype(np.int32)
    ref = dense.logits(params, m, toks, 0)
    low = dense.logits(params, m, toks, 0, int8=True)
    err = np.abs(low - ref).max()
    assert 1e-3 < err < 0.5 * np.abs(ref).max()
