"""``BatchedServer`` runs one decode ahead: step k dispatches decode k
before it reads decode k-1's tokens back.  On small dense, mixture-of-
experts and hybrid models, every request gets exactly the tokens that the
same executables give when called one blocking step at a time, through
slot reuse, a request that ends at its prefill token, a request that
fills the cache, admissions while a decode is in flight, a registry swap
mid-stream and an EOS read one step late; the counters say how often the
loop ran ahead and how many tokens it dropped, and serving compiles
nothing that the server did not build."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import get_model
from repro.serve import BatchedServer

FAMILIES = {"dense": "stablelm-3b", "moe": "qwen2-moe-a2.7b",
            "hybrid": "hymba-1.5b"}
SLOTS, MAX_LEN, BUCKETS = 3, 32, (8, 32)
# (prompt length, max_new, step before which it is submitted); lengths
# are whole chunks of the hybrid's scan (8) or shorter than one
JOBS = [(5, 6, 0), (8, 1, 0), (7, 9, 0), (24, 12, 0), (3, 5, 2),
        (16, 4, 3), (5, 7, 5), (8, 3, 6), (7, 4, 6)]
FILLS_CACHE = 3            # JOBS[3]: 24 + 9 tokens reach MAX_LEN
SWAP_AT = 4                # a registry mutation before this step
# at most SLOTS requests at a time, so that an EOS read one step late
# delays no admission and both orders run the same shapes
EOS_JOBS = [(5, 8, 0), (7, 9, 0), (8, 1, 0), (16, 6, 1)]
COMPILED = "/jax/core/compile/backend_compile_duration"


class Blocking(BatchedServer):
    """The same executables called one blocking step at a time: each
    prefill's and each decode's tokens are read before the next call, and
    the decode's token input is built on the host."""

    def step(self):
        self._refresh_impls()
        for first, rows in self._admit():
            first = np.asarray(first)
            for r, (s, req) in enumerate(rows):
                self._take(req, int(first[r]), s)
        live = [s for s in range(self.slots) if self._decodes(s)]
        if not live:
            return 0
        toks = np.zeros((self.slots, 1), np.int32)
        pos = np.zeros(self.slots, np.int32)
        for s in live:
            toks[s, 0] = self.active[s].tokens[-1]
            pos[s] = self.pos[s]
        nxt, self.cache = self._get_decode()(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(pos))
        nxt = np.asarray(nxt)
        for s in live:
            self.pos[s] += 1
            self._take(self.active[s], int(nxt[s]), s)
        return len(live)


@pytest.fixture(autouse=True)
def _clean_registry():
    ops.clear_all()
    yield
    ops.clear_all()


def prompt(vocab, job):
    n = job[0]
    return np.random.default_rng(1000 * n + job[1]).integers(
        0, vocab, n).astype(np.int32)


@dataclasses.dataclass
class Served:
    reqs: list
    decode_steps: list             # the step of each decode call
    ahead_at_submit: list          # a decode was in flight at submission
    ahead_at_swap: bool            # ... and at the registry mutation
    compiles: int                  # XLA compiles while serving
    built: int                     # the server's AOT builds while serving


def serve(srv, jobs, swap_at=None):
    """Serve ``jobs`` step by step; count decode calls and compiles."""
    vocab = srv.model.cfg.vocab_size
    clock = {"step": 0}
    decode_steps, ahead_at_submit, reqs = [], [], []
    ahead_at_swap = False
    build = srv._get_decode

    def counted():
        ex = build()

        def call(*args):
            decode_steps.append(clock["step"])
            return ex(*args)
        return call
    srv._get_decode = counted
    compiles = []

    def on_compile(event, secs, **kw):
        if event == COMPILED:
            compiles.append(secs)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    built = srv.aot_compiles
    try:
        last = max(at for _, _, at in jobs)
        while True:
            step = clock["step"]
            for job in jobs:
                if job[2] == step:
                    ahead_at_submit.append(srv._ahead is not None)
                    reqs.append(srv.submit(prompt(vocab, job),
                                           max_new=job[1]))
            if step == swap_at:
                ahead_at_swap = srv._ahead is not None
                ops.install("attention", lambda *a, **k: None)
                ops.rollback("attention")
            if step > last and not srv.queue and srv._ahead is None and \
                    all(a is None for a in srv.active):
                break
            srv.step()
            clock["step"] += 1
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        del srv._get_decode
    return Served(reqs, decode_steps, ahead_at_submit, ahead_at_swap,
                  len(compiles), srv.aot_compiles - built)


@pytest.fixture(scope="module")
def runs():
    """Per family, built once: the one-step-ahead server and the blocking
    one over the same jobs, and over ``EOS_JOBS`` with an EOS that the
    blocking server decodes mid-stream."""
    done = {}

    def get(family):
        if family not in done:
            cfg = dataclasses.replace(get_config(FAMILIES[family]).reduced(),
                                      param_dtype="float32")
            model = get_model(cfg)
            params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
            kw = dict(slots=SLOTS, max_len=MAX_LEN, buckets=BUCKETS,
                      telemetry=ops.Telemetry())
            ahead, blocking = BatchedServer(model, params, **kw), \
                Blocking(model, params, **kw)
            out = {"ahead": serve(ahead, JOBS, swap_at=SWAP_AT),
                   "blocking": serve(blocking, JOBS),
                   "swaps": ahead.swap_epochs}
            # the EOS: the third token of the first request, served freely
            eos = serve(blocking, EOS_JOBS).reqs[0].tokens[2]
            blocking.eos_id = ahead.eos_id = eos
            out["eos_blocking"] = serve(blocking, EOS_JOBS)
            out["eos_ahead"] = serve(ahead, EOS_JOBS)
            out.update(eos=eos, dropped=ahead.dropped_tokens,
                       decodes_ahead=ahead.decodes_ahead,
                       padded=ahead.padded_packing,
                       blocking_dropped=blocking.dropped_tokens)
            done[family] = out
        return done[family]
    return get


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_tokens_match_the_blocking_order(runs, family):
    r = runs(family)
    got, want = r["ahead"].reqs, r["blocking"].reqs
    assert [q.tokens for q in got] == [q.tokens for q in want]
    assert all(q.done for q in got)
    # each ends at its budget: max_new, or where it fills the cache
    for q, (n, max_new, _) in zip(got, JOBS):
        assert len(q.tokens) == min(max_new, MAX_LEN - n + 1)
    assert len(got[1].tokens) == 1 and len(got[FILLS_CACHE].tokens) == 9
    # later requests, some into reused slots, came while a decode was
    # on the device, and the swap rebuilt the executables under one
    assert r["ahead"].ahead_at_submit[-5:] == [True] * 5
    assert r["ahead"].ahead_at_swap and r["swaps"] == 1


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_an_eos_read_late_drops_the_one_token_past_it(runs, family):
    r = runs(family)
    got, want = r["eos_ahead"].reqs, r["eos_blocking"].reqs
    assert [q.tokens for q in got] == [q.tokens for q in want]
    # every request stops at its first EOS and appends nothing past it
    for q, (n, max_new, _) in zip(got, EOS_JOBS):
        assert q.done
        if r["eos"] in q.tokens:
            assert q.tokens.index(r["eos"]) == len(q.tokens) - 1
    # a request that met EOS before its budget had one more token
    # decoded, which was dropped; the blocking order drops none
    early = sum(1 for q, (n, max_new, _) in zip(got, EOS_JOBS)
                if q.tokens[-1] == r["eos"] and
                len(q.tokens) < min(max_new, MAX_LEN - n + 1))
    assert early >= 1
    assert r["dropped"] == early
    assert r["blocking_dropped"] == 0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_decodes_run_ahead_on_a_steady_stream(runs, family):
    r = runs(family)
    served = [r["ahead"], r["eos_ahead"]]
    # a decode runs ahead when the step before dispatched one too
    want = sum(sum(1 for k in s.decode_steps if k - 1 in s.decode_steps)
               for s in served)
    assert r["decodes_ahead"] == want
    # one decode a step, from the first admission until the stream ends:
    # only each stream's first decode has none before it
    for s in served:
        assert s.decode_steps == list(range(s.decode_steps[0],
                                            s.decode_steps[-1] + 1))
    assert want == sum(len(s.decode_steps) - 1 for s in served)
    # the blocking order decodes in the same steps
    assert r["blocking"].decode_steps == r["ahead"].decode_steps


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_serving_compiles_only_what_the_server_builds(runs, family):
    r = runs(family)
    # every compile is an executable the server built: none is an eager
    # operation on the step's path
    for s in (r["ahead"], r["eos_ahead"]):
        assert s.compiles == s.built
    # with padded buckets every one was built when the server was made or
    # rebuilt at the swap; the second stream, with no swap, builds none
    if r["padded"]:
        assert r["eos_ahead"].built == 0
