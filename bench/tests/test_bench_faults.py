"""The comparison fails a run whose timed path is broken underneath: a
token altered where the server produces it, a decode step that returns
its cache unchanged, a request that is never answered."""
import time

import pytest

from bench import harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


def test_altered_decode_token_is_not_correct(bench, monkeypatch):
    from repro.serve.decode import BatchedServer
    vocab = tiny.TINY_MODEL["vocab_size"]
    make_decode = BatchedServer._get_decode

    def broken(self):
        ex = make_decode(self)

        def call(*args):
            nxt, cache = ex(*args)
            return (nxt + 1) % vocab, cache
        return call

    monkeypatch.setattr(BatchedServer, "_get_decode", broken)
    out = harness.run_cell(bench, tiny.WORKLOAD, seed=11, seconds=1.5,
                           trace=False, t_process=time.perf_counter(),
                           log=lambda s: None)
    assert out["correct"] is False
    widest = out["check"]["widest_gap"]
    assert widest["value"] > widest["limit"]


def test_decode_that_keeps_its_cache_is_not_correct(bench, monkeypatch):
    """A decode step that returns the cache it was given: the keys and
    values of decoded tokens are never stored."""
    from repro.serve.decode import BatchedServer
    make_decode = BatchedServer._get_decode

    def stale(self):
        ex = make_decode(self)

        def call(params, cache, toks, pos):
            nxt, _ = ex(params, cache, toks, pos)
            return nxt, cache
        return call

    monkeypatch.setattr(BatchedServer, "_get_decode", stale)
    out = harness.run_cell(bench, tiny.WORKLOAD, seed=13, seconds=1.5,
                           trace=False, t_process=time.perf_counter(),
                           log=lambda s: None)
    assert out["correct"] is False
    assert out["check"]["widest_gap"]["value"] > \
        out["check"]["widest_gap"]["limit"]


def test_dropped_request_is_not_correct(bench, monkeypatch):
    """A request whose answer never comes fails the run."""
    from repro.serve.decode import BatchedServer
    submit = BatchedServer.submit
    seen = []

    def lossy(self, prompt, max_new=16):
        req = submit(self, prompt, max_new)
        if max_new > 2:                       # not one of the warm-up's
            seen.append(req)
            if len(seen) == 3:
                self.queue.remove(req)
        return req

    monkeypatch.setattr(BatchedServer, "submit", lossy)
    monkeypatch.setattr(harness, "DRAIN_LIMIT_S", 2.0)
    out = harness.run_cell(bench, tiny.WORKLOAD, seed=12, seconds=1.5,
                           trace=False, t_process=time.perf_counter(),
                           log=lambda s: None)
    assert out["correct"] is False
    assert out["failed"] == 1
    assert out["check"]["unfinished"] == {"value": 1, "limit": 0}
