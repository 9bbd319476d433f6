"""The program's own spans on the device trace's clock: what the host was
doing while the device sat idle, and the scheduler's host time per step.

``BatchedServer`` (``serve/decode.py``) opens a ``TraceAnnotation`` named
``serve.*`` around ``submit`` and around each phase of ``step``; they
nest on the serving thread inside the benchmark's own ``step`` span.
``load`` keeps them beside the benchmark's spans, and every function here
reads a ``tracereduce.Trace`` so loaded.
"""
from __future__ import annotations

import bisect
import statistics
from typing import Dict, List, Optional, Sequence

from bench import tracereduce
from bench.tracereduce import Interval, Trace

SERVE_SPANS = ("serve.step", "serve.rebuild", "serve.submit",
               "serve.prefill", "serve.prefill_wait", "serve.decode",
               "serve.decode_wait", "serve.bookkeeping")
WAITS = ("serve.prefill_wait", "serve.decode_wait")
LABELS = ("step", "generator", "await_arrival") + SERVE_SPANS


def load(path: str) -> Trace:
    return tracereduce.load(path, tracereduce.HOST_SPANS + SERVE_SPANS)


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """The intersection of two merged interval lists, merged."""
    i = j = 0
    out: List[Interval] = []
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def innermost(spans: Sequence[tracereduce.Event]) -> List[tracereduce.Event]:
    """Cut the time that nested spans cover into pieces, each named by the
    innermost span open over it."""
    out: List[tracereduce.Event] = []
    stack: List[tuple] = []               # (name, end) of the open spans
    t = 0.0

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][1] <= limit:
            name, end = stack.pop()
            if end > t:
                out.append((name, t, end))
                t = end
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close_until(s)
        if stack and s > t:
            out.append((stack[-1][0], t, s))
        stack.append((name, e))
        t = max(t, s)
    close_until(float("inf"))
    return out


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """``tracereduce.idle_gaps``'s gaps, each named by its rule over the
    ``innermost`` pieces of ``LABELS`` rather than the whole spans: a gap
    inside ``serve.decode`` reads ``serve.decode``, not ``step``."""
    pieces = innermost([x for x in trace.spans if x[0] in LABELS])
    return tracereduce.idle_gaps(Trace(trace.modules, trace.ops, pieces),
                                 k, labels=LABELS)


def step_host_ms(trace: Trace) -> Optional[float]:
    """Median over the ``serve.step`` spans of the time the host spent
    in the step less the time it waited there for the device's results
    (``serve.prefill_wait``, ``serve.decode_wait``), in ms."""
    steps = sorted((s, e) for n, s, e in trace.spans if n == "serve.step")
    if not steps:
        return None
    waits = tracereduce.union((s, e) for n, s, e in trace.spans
                              if n in WAITS)
    starts = [s for s, _ in waits]
    host = []
    for s, e in steps:
        inside = waits[bisect.bisect_left(starts, s):
                       bisect.bisect_right(starts, e)]
        host.append(e - s - tracereduce.overlap([(s, e)], inside))
    return statistics.median(host) / 1e6


def idle_in_step(trace: Trace, span: str = "serving") -> Optional[float]:
    """Share of ``span`` during which no operation ran on the device
    while the host was inside ``serve.step``, averaged over devices; at
    most ``tracereduce.idle_share`` of the same span.  None where the
    trace has no such span or no step."""
    work = tracereduce.span_intervals(trace, span)
    total = tracereduce.length(work)
    steps = tracereduce.span_intervals(trace, "serve.step")
    if total <= 0 or not steps:
        return None
    both = intersect(work, steps)
    inside = tracereduce.length(both)
    devs = trace.devices
    return sum(inside - tracereduce.overlap(tracereduce.busy(trace, d),
                                            both)
               for d in devs) / len(devs) / total


def idle_by_span(trace: Trace, span: str = "serving") -> Dict[str, float]:
    """Seconds of ``span`` in which the first device ran no operation,
    each charged to the innermost of ``LABELS`` the host was in (``none``
    where it was in none); they add up to the idle time of ``span``."""
    work = tracereduce.span_intervals(trace, span)
    busy = tracereduce.busy(trace, trace.devices[0])
    pieces: Dict[str, List[Interval]] = {}
    for name, s, e in innermost([x for x in trace.spans if x[0] in LABELS]):
        pieces.setdefault(name, []).append((s, e))
    out, charged = {}, 0.0
    for name, segs in pieces.items():
        part = intersect(work, tracereduce.union(segs))
        out[name] = (tracereduce.length(part)
                     - tracereduce.overlap(busy, part)) / 1e9
        charged += out[name]
    idle = (tracereduce.length(work) - tracereduce.overlap(busy, work)) / 1e9
    out["none"] = idle - charged
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
