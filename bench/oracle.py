"""What decides ``correct`` for a served cell.

Once the window has closed, a sample of the requests the window served,
drawn from the seed and always holding the longest and every request that
was live in the step with the most live slots, is run through the
plain float32 reference over its prompt and its served tokens.  At each
served position the reference's logits give a gap: its best logit minus
the logit of the token the server produced there.  The server returns
tokens only (its argmax is fused into its executables), so gaps are all
it can be judged by; a gap is zero wherever the served token is the
reference's own choice.  The numbers compared, each against its limit in
the cell's file (``cells/<workload>.json``, key ``limits``):

``widest_gap``     the largest gap over the sample;
``mean_gap``       the mean gap over every served token of the sample;
``unfinished``     requests due in the window with no complete answer by
                   the end of the drain (limit 0);
``malformed``      finished requests whose token count differs from what
                   they asked for, or with a token outside the vocabulary
                   (limit 0).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np


def busiest(spans: Sequence[Tuple[int, int]]) -> List[int]:
    """Indices of the requests live in the first step with the most live
    requests; ``spans[i]`` is request ``i``'s first and last step."""
    if not spans:
        return []
    live = np.zeros(max(last for _, last in spans) + 2, np.int64)
    for first, last in spans:
        live[first] += 1
        live[last + 1] -= 1
    step = int(np.argmax(np.cumsum(live)))
    return [i for i, (first, last) in enumerate(spans)
            if first <= step <= last]


def sample(lengths: Sequence[int], served: Sequence[int],
           spans: Sequence[Tuple[int, int]], seed: int, *,
           min_tokens: int, min_requests: int) -> List[int]:
    """Indices of the requests to check: the longest (prompt plus served
    tokens), every request live in the busiest step (so the sample spans
    the slots in use at once), then others in a seeded order until it
    holds ``min_tokens`` served tokens and ``min_requests`` requests."""
    longest = max(range(len(lengths)), key=lambda i: lengths[i])
    picked = [longest] + [i for i in busiest(spans) if i != longest]
    tokens = sum(served[i] for i in picked)
    for i in np.random.default_rng(seed % 2**64 ^ 0x5EED).permutation(
            len(lengths)):
        if tokens >= min_tokens and len(picked) >= min_requests:
            break
        if int(i) not in picked:
            picked.append(int(i))
            tokens += served[i]
    return picked


def gaps(ref_logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """Reference best logit minus the reference logit of each served token
    (``ref_logits[j]`` is the distribution token ``j`` was drawn from)."""
    lg = np.asarray(ref_logits, np.float64)
    t = np.asarray(tokens, np.int64)
    return lg.max(axis=1) - lg[np.arange(len(t)), t]


def control_gaps(ref_logits: np.ndarray,
                 low_logits: np.ndarray) -> np.ndarray:
    """The control's gaps: at each position, the gap of the token that the
    lower-precision computation puts first."""
    return gaps(ref_logits, np.argmax(low_logits, axis=1))


def readings(all_gaps: Sequence[np.ndarray]) -> Dict[str, float]:
    g = np.concatenate([np.asarray(x, np.float64) for x in all_gaps])
    return {"widest_gap": float(g.max()), "mean_gap": float(g.mean()),
            "tokens_compared": int(g.size),
            "tokens_off_argmax": int(np.count_nonzero(g > 0))}


def check(prompts: Sequence[np.ndarray], outputs: Sequence[Sequence[int]],
          spans: Sequence[Tuple[int, int]], max_new: Sequence[int],
          unfinished: int, vocab: int,
          request_gaps: Callable[[np.ndarray, int, np.ndarray], np.ndarray],
          limits: Dict[str, float], seed: int, *, min_tokens: int,
          min_requests: int) -> Dict[str, Any]:
    """Compare the served answers with the reference.  ``outputs`` are the
    finished requests' tokens and ``spans`` the first and last server step
    of each; ``request_gaps(sequence, start, served)``
    gives the gap at each served position (``gaps`` of the reference's
    logits, or of the control's choices).  Returns the numbers, their
    limits and the verdict."""
    malformed = sum(1 for out, n in zip(outputs, max_new)
                    if len(out) != n or any(not 0 <= t < vocab for t in out))
    nums: Dict[str, float] = {"unfinished": unfinished,
                              "malformed": malformed}
    if outputs and not malformed:
        idx = sample([len(p) + len(o) for p, o in zip(prompts, outputs)],
                     [len(o) for o in outputs], spans, seed,
                     min_tokens=min_tokens, min_requests=min_requests)
        per_request = []
        for i in idx:
            p, out = prompts[i], np.asarray(outputs[i], np.int32)
            seq = np.concatenate([p, out[:-1]]).astype(np.int32)
            per_request.append(request_gaps(seq, len(p) - 1, out))
        nums.update(readings(per_request))
        nums["requests_compared"] = len(idx)
    lim = {"unfinished": 0, "malformed": 0, **limits}
    compared = {k: (nums.get(k), lim[k]) for k in lim}
    ok = all(v is not None and v <= limit for v, limit in compared.values())
    return {"correct": ok, "compared": compared, "readings": nums}


def format_lines(result: Dict[str, Any]) -> List[str]:
    """One line per number compared: name, number, limit."""
    return [f"check {k}: {v} (limit {limit})"
            for k, (v, limit) in result["compared"].items()]
