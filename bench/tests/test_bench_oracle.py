"""The sample the reference checks: the longest request, every request
live in the busiest step, then seeded others up to the stated size."""
import numpy as np
import pytest

from bench import oracle


def test_busiest_step_holds_every_request_live_in_it():
    spans = [(0, 3), (2, 9), (4, 6), (5, 8), (10, 12)]
    assert oracle.busiest(spans) == [1, 2, 3]       # step 5: three live
    assert oracle.busiest([]) == []


@pytest.mark.parametrize("seed", [1, 2**33 + 5])
def test_sample_holds_the_longest_and_the_busiest_step(seed):
    n = 40
    lengths = list(range(100, 100 + n))
    served = [8] * n
    spans = [(i, i + 1) for i in range(n)]
    spans[3] = spans[7] = spans[11] = (100, 120)    # live together
    picked = oracle.sample(lengths, served, spans, seed, min_tokens=64,
                           min_requests=10)
    assert picked[0] == n - 1                       # the longest
    assert {3, 7, 11} <= set(picked)
    assert len(picked) == len(set(picked)) == 10
    assert picked == oracle.sample(lengths, served, spans, seed,
                                   min_tokens=64, min_requests=10)


def test_check_fails_a_wrong_token_in_one_slot_of_many():
    """A token altered in one of the slots live at once is in the sample,
    whatever the seed draws."""
    vocab, n = 16, 30
    rng = np.random.default_rng(0)
    logits = {i: rng.normal(size=(4, vocab)) for i in range(n)}
    outs = [list(np.argmax(logits[i], axis=1)) for i in range(n)]
    spans = [(10 * i, 10 * i + 3) for i in range(n)]
    spans[5] = spans[6] = spans[9] = (500, 520)
    outs[9][2] = (outs[9][2] + 1) % vocab
    prompts = [np.zeros(3 + (i == 0), np.int32) for i in range(n)]

    def request_gaps(seq, start, out):
        i = next(j for j in range(n) if outs[j] == list(out))
        return oracle.gaps(logits[i], out)

    for seed in range(5):
        got = oracle.check(prompts, outs, spans, [4] * n, 0, vocab,
                           request_gaps, {"widest_gap": 1e-6}, seed,
                           min_tokens=8, min_requests=2)
        assert got["correct"] is False
        assert got["readings"]["requests_compared"] >= 4
