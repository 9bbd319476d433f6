"""Continuous-batching equivalence properties.

Every request served through the bucketed/ragged ``BatchedServer`` must
decode exactly the greedy tokens the fixed-batch ``generate()`` path
produces for the same prompt — across ragged prompt lengths, mid-batch
EOS, slot churn, and a hot-swap epoch mid-traffic.  Also covers the AOT
executable cache (built at startup, rebuilt on registry epoch) and the
bucket-tagged telemetry feeding the autotuner.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_case
from repro.kernels import ops
from serving_stub import StubModel, make_server, stub_generate


@pytest.fixture(autouse=True)
def _clean_registry():
    ops.clear_all()
    ops.telemetry.reset()
    yield
    ops.clear_all()
    ops.telemetry.reset()


def ragged_prompts(n, seed=1, lo=3, hi=20):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 32, int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


def check_equivalence(srv, pairs):
    """pairs: [(request, (prompt, max_new))] — every served request must
    match the fixed-batch greedy reference byte-for-byte."""
    for r, (p, mn) in pairs:
        ref = stub_generate(p, mn, eos_id=srv.eos_id)
        assert r.done, f"request {r.rid} never finished"
        assert r.tokens == ref, (
            f"request {r.rid} (len {len(p)}, max_new {mn}) diverged:\n"
            f"  served {r.tokens}\n  reference {ref}")


def test_ragged_lengths_match_fixed_batch_reference():
    srv = make_server(slots=3, max_len=64)
    jobs = [(p, 5) for p in ragged_prompts(8, seed=2)]
    pairs = [(srv.submit(p, max_new=mn), (p, mn)) for p, mn in jobs]
    srv.run()
    check_equivalence(srv, pairs)
    # ragged prompts landed in more than one prefill bucket
    assert len({r.bucket for r, _ in pairs}) > 1


def test_mid_batch_eos_and_slot_churn():
    # learn a realistic EOS: the token request 0 decodes second
    probe = make_server(slots=1, max_len=64)
    r = probe.submit(ragged_prompts(1, seed=3)[0], max_new=6)
    probe.run()
    eos = r.tokens[1]

    srv = make_server(slots=2, max_len=64, eos_id=eos)
    jobs = [(p, mn) for p, mn in zip(ragged_prompts(9, seed=3),
                                     [6, 2, 9, 1, 4, 7, 3, 5, 8])]
    pairs = [(srv.submit(p, max_new=mn), (p, mn)) for p, mn in jobs]
    srv.run()
    check_equivalence(srv, pairs)
    # the EOS actually fired mid-traffic for at least one request
    assert any(r.tokens[-1] == eos and len(r.tokens) < mn
               for r, (_, mn) in pairs)


def test_hot_swap_epoch_mid_traffic_preserves_outputs():
    srv = make_server(slots=2, max_len=64)
    jobs = [(p, 6) for p in ragged_prompts(6, seed=4)]
    pairs = [(srv.submit(p, max_new=mn), (p, mn)) for p, mn in jobs]
    srv.step()
    srv.step()                     # requests in flight, partially decoded
    case = get_case("attention_prefill")
    ops.install("attention",
                case.build(dict(case.baseline_variant, chunked=True),
                           impl="jnp"))
    srv.run()                      # swap picked up at a step boundary
    assert srv.swap_epochs == 1
    # equivalence holds across the swap (chunked impl is numerically
    # identical); reference path sees the swapped registry too
    check_equivalence(srv, pairs)


def test_aot_executables_built_and_rebuilt_on_epoch():
    srv = make_server(slots=2, max_len=64)
    # startup traced: 1 decode + one prefill per (bucket, pow2 rows<=2)
    built = srv.aot_compiles
    assert built >= 1 + len(srv.buckets)
    p = ragged_prompts(1, seed=5)[0]
    srv.submit(p, max_new=3)
    srv.run()
    assert srv.aot_compiles == built        # served from the AOT cache
    case = get_case("attention_prefill")
    ops.install("attention", case.build(dict(case.baseline_variant),
                                        impl="jnp"))
    srv.submit(p, max_new=3)
    srv.run()
    assert srv.swap_epochs == 1
    assert srv.aot_compiles >= 2 * built    # epoch flushed + rebuilt


def test_aot_off_still_serves_identically():
    jobs = [(p, 4) for p in ragged_prompts(5, seed=6)]
    srv = make_server(slots=2, max_len=64, aot=False)
    assert srv.aot_compiles == 0
    pairs = [(srv.submit(p, max_new=mn), (p, mn)) for p, mn in jobs]
    srv.run()
    check_equivalence(srv, pairs)


def test_compile_error_propagates_instead_of_a_lazy_jit(monkeypatch):
    """A kernel the compiler refuses must stop the server from starting,
    not be served by a jit that compiles (or fails) on first call."""
    from repro.serve import BatchedServer
    from serving_stub import StubModel
    model = StubModel()
    params = model.init_params(jax.random.PRNGKey(0))
    real_jit = jax.jit

    class Refused(RuntimeError):
        pass

    class Unlowerable:
        def __init__(self, fn):
            self.jitted = real_jit(fn)

        def __call__(self, *args):
            return self.jitted(*args)

        def lower(self, *args):
            raise Refused("the compiler refused this program")

    monkeypatch.setattr(jax, "jit", Unlowerable)
    with pytest.raises(Refused):
        BatchedServer(model, params, slots=2, max_len=64)


def test_bucket_telemetry_reaches_autotuner():
    tel = ops.Telemetry()
    srv = make_server(slots=2, max_len=64, telemetry=tel)
    short = [p[:4] for p in ragged_prompts(3, seed=7)]   # bucket 8 (floor)
    long = [np.resize(p, 14).astype(np.int32)            # bucket 16
            for p in ragged_prompts(3, seed=8)]
    reqs = [srv.submit(p, max_new=3) for p in short + long]
    srv.run()
    assert all(r.done for r in reqs)
    by_bucket = tel.site_buckets("attention")
    assert set(by_bucket) == {8, 16}
    # hottest-first ordering and per-bucket scale snapping
    assert list(by_bucket) == sorted(by_bucket,
                                     key=by_bucket.get, reverse=True)
    assert tel.weighted_scale("attention", bucket=8) <= \
        tel.weighted_scale("attention", bucket=16)


def test_recurrent_family_uses_exact_length_packing():
    srv = make_server(slots=2, max_len=64)
    assert srv.padded_packing            # dense stub → padded buckets
    model = StubModel()

    class _SSMCfg:
        family = "ssm"
        vocab_size = 32

    model.cfg = _SSMCfg()
    params = model.init_params(jax.random.PRNGKey(0))
    from repro.serve import BatchedServer
    ssm_srv = BatchedServer(model, params, slots=2, max_len=64)
    assert not ssm_srv.padded_packing    # recurrent state: no pad rows
    p = ragged_prompts(1, seed=9)[0]
    assert ssm_srv.bucket_of(len(p)) == len(p)


def test_recurrent_real_model_ragged_equivalence():
    """Real ssm-family model: exact-length packed admission + ragged
    decode must still match generate() token for token (recurrent state
    is per-row, so vector positions are exact)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import get_model
    from repro.serve import BatchedServer, generate

    cfg = dataclasses.replace(get_config("rwkv6-7b").reduced(),
                              param_dtype="float32")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    srv = BatchedServer(model, params, slots=2, max_len=64)
    assert not srv.padded_packing
    rng = np.random.default_rng(0)
    chunk = cfg.ssm.chunk
    prompts = [rng.integers(0, cfg.vocab_size, n * chunk).astype(np.int32)
               for n in (1, 2, 1, 3)]
    reqs = [srv.submit(p, max_new=4) for p in prompts]
    srv.run()
    assert all(r.done for r in reqs)
    for r, p in zip(reqs, prompts):
        ref = generate(model, params, jnp.asarray(p[None, :]), max_new=4)[0]
        assert r.tokens == [int(t) for t in ref[:len(r.tokens)]], \
            f"rid {r.rid} diverged"

