"""``BatchedServer``'s profiler spans, read back from a real trace: every
phase of a step nests inside its ``serve.step`` in order, each executable
call lies inside the span that names it, and tracing changes no served
token.  The server runs one step ahead: the decode dispatched in step k
is waited for, and its tokens appended, in step k+1."""
import glob
import os
import re
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get_config
from repro.core import get_case
from repro.kernels import ops
from repro.models import get_model
from repro.serve import BatchedServer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from bench.harness import (RunRecord, Served, StepClock,  # noqa: E402
                           TimedTokens)

SLOTS, MAX_LEN, BUCKETS = 4, 64, (16, 32)
# (prompt length, max_new, step before which it is submitted)
JOBS = [(5, 6, 0), (20, 3, 0), (12, 9, 0), (30, 4, 0), (7, 1, 0),
        (25, 5, 0), (9, 7, 2), (31, 2, 2), (14, 5, 5)]
SWAP_AT = 3                # a registry install before this step: a rebuild
IN_STEP = {"serve.prefill", "serve.prefill_wait", "serve.decode",
           "serve.decode_wait", "serve.bookkeeping"}


@pytest.fixture(autouse=True)
def _clean_registry():
    ops.clear_all()
    yield
    ops.clear_all()


@pytest.fixture(scope="module")
def model_params():
    model = get_model(get_config("stablelm-3b").reduced())
    return model, jax.jit(model.init_params)(jax.random.PRNGKey(0))


def serve(model, params):
    """Serve ``JOBS`` as the benchmark drives the server, stamping each
    token with its step and opening a ``call.prefill`` or ``call.decode``
    span around each executable call; returns the server, the requests as
    ``Served`` and the step clock."""
    server = BatchedServer(model, params, slots=SLOTS, max_len=MAX_LEN,
                           buckets=BUCKETS, telemetry=ops.Telemetry())

    def spy(name, build):
        def get(*key):
            ex = build(*key)

            def call(*args):
                with TraceAnnotation(name):
                    return ex(*args)
            return call
        return get
    server._get_prefill = spy("call.prefill", server._get_prefill)
    server._get_decode = spy("call.decode", server._get_decode)

    rng = np.random.default_rng(7)
    clock, served = StepClock(), []
    while True:
        step = clock.step + 1
        for n, max_new, at in JOBS:
            if at == step:
                prompt = rng.integers(0, 512, n).astype(np.int32)
                req = server.submit(prompt, max_new=max_new)
                req.tokens = TimedTokens(clock)
                served.append(Served(due=0.0, submitted=0.0, prompt=prompt,
                                     max_new=max_new, req=req))
        if step == SWAP_AT:
            case = get_case("attention_prefill")
            ops.install("attention", case.build(
                dict(case.baseline_variant, chunked=True), impl="jnp"))
        if step > max(at for _, _, at in JOBS) and not server.queue and \
                all(a is None for a in server.active):
            return server, served, clock
        clock.step += 1
        server.step()


def spans(log_dir):
    """Every ``serve.*`` and ``call.*`` event of the trace: (name, start,
    end), by start, outer spans first."""
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events
                       if e.name.startswith(("serve.", "call.")))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def within(events, outer):
    """The events that lie inside ``outer``'s time."""
    return [x for x in events if outer[1] <= x[1] and x[2] <= outer[2]
            and x is not outer]


@pytest.fixture(scope="module")
def traced(model_params, tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("serve_trace"))
    ops.clear_all()
    jax.profiler.start_trace(log_dir)
    try:
        run = serve(*model_params)
    finally:
        jax.profiler.stop_trace()
    ops.clear_all()
    return run, spans(log_dir)


def test_every_phase_nests_in_its_step_in_order(traced):
    (server, _, clock), events = traced
    steps = [e for e in events if e[0] == "serve.step"]
    assert len(steps) == clock.step + 1 > SWAP_AT
    assert sum(e[0] == "serve.submit" for e in events) == len(JOBS)
    assert not any(e[0] == "serve.submit" for s in steps
                   for e in within(events, s))

    rebuilds = [e for e in events if e[0] == "serve.rebuild"]
    assert len(rebuilds) == server.swap_epochs == 1
    assert rebuilds[0] in within(events, steps[SWAP_AT])

    seen = 0
    for step in steps:
        inside = [x for x in within(events, step)
                  if x[0] in IN_STEP | {"serve.rebuild"}]
        seen += len(inside)
        order = "".join({"serve.rebuild": "r", "serve.prefill": "p",
                         "serve.prefill_wait": "w", "serve.decode": "d",
                         "serve.decode_wait": "v",
                         "serve.bookkeeping": "b"}[x[0]] for x in inside)
        # dispatch every prefill and the decode, then wait for the last
        # decode and this step's prefills, then the bookkeeping
        m = re.fullmatch("r?(p*)d?(v?)(w?)(b?)", order)
        assert m, order
        prefill, wait_decode, wait_prefill, book = m.groups()
        assert bool(prefill) == bool(wait_prefill), order
        assert bool(book) == bool(wait_decode or wait_prefill), order
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
    # no phase of a step lies outside one
    assert seen == sum(x[0] in IN_STEP | {"serve.rebuild"} for x in events)


def test_each_executable_call_lies_in_the_span_that_names_it(traced):
    _, events = traced
    for kind in ("prefill", "decode"):
        spans_ = [e for e in events if e[0] == "serve." + kind]
        calls = [e for e in events if e[0] == "call." + kind]
        # one call per span, inside it, and none inside a wait
        assert [[c for c in within(events, s) if c[0] == "call." + kind]
                for s in spans_] == [[c] for c in calls]
        assert not any(c[0].startswith("call.") for e in events
                       if e[0].endswith("_wait") for c in within(events, e))
    assert sum(e[0] == "call.prefill" for e in events) > 2


def test_decode_spans_are_the_benchmarks_decode_steps(traced):
    (_, served, clock), events = traced
    rec = RunRecord(model={}, peaks=None, t0=0.0, window_s=0.0,
                    requests=served, step_starts=[],
                    window_steps=(0, clock.step + 1), compiles_in_window=0,
                    trace=None)
    steps = [e for e in events if e[0] == "serve.step"]

    def having(phase):
        return [k for k, s in enumerate(steps)
                if any(x[0] == phase for x in within(events, s))]
    # the decode of step k has its wait and its tokens in step k+1
    decoded = sorted(rec.window_decode_steps())
    assert [k + 1 for k in having("serve.decode")] == decoded
    assert having("serve.decode_wait") == decoded and len(decoded) > 5
    assert set(decoded) <= set(having("serve.bookkeeping"))


def test_tracing_changes_no_served_token(traced, model_params):
    (_, traced_served, _), _ = traced
    _, served, _ = serve(*model_params)
    assert [list(s.tokens) for s in served] == \
        [list(s.tokens) for s in traced_served]
    assert all(s.req.done for s in served)
