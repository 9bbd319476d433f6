"""Reduce a profiler trace (``.xplane.pb``) to device time, busy and idle
intervals and the host spans the benchmark opened around them.

Device planes are named ``/device:TPU:<n>``.  On each, the line
``XLA Modules`` holds one event per executable run (the program's jitted
function names, such as ``jit_decode_and_pick(...)``) and ``XLA Ops`` one
per operation inside them.  The benchmark's own host spans (``step``,
``generator``, ``await_arrival``, ``serving``) are events of the host
plane ``/host:CPU``.  All events share one clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]          # start, end (ns)
Event = Tuple[str, float, float]        # name, start, end (ns)

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES, OPS = "XLA Modules", "XLA Ops"
HOST_SPANS = ("step", "generator", "await_arrival", "serving")
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Trace:
    modules: Dict[str, List[Event]]     # device plane -> module runs
    ops: Dict[str, List[Event]]         # device plane -> operations
    spans: List[Event]                  # the benchmark's host spans

    @property
    def devices(self) -> List[str]:
        return sorted(set(self.modules) | set(self.ops))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, span_names: Sequence[str] = HOST_SPANS) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                dest = {MODULES: modules, OPS: ops}.get(line.name)
                if dest is not None:
                    dest[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in span_names)
    spans.sort(key=lambda e: e[1])
    return Trace(modules=modules, ops=ops, spans=spans)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def busy(trace: Trace, device: str) -> List[Interval]:
    """Merged intervals in which an operation ran on ``device``."""
    events = trace.ops.get(device) or trace.modules.get(device, [])
    return union((s, e) for _, s, e in events)


def busy_s(trace: Trace) -> float:
    """Busy seconds, averaged over the traced devices."""
    devs = trace.devices
    return sum(length(busy(trace, d)) for d in devs) / len(devs) / 1e9


def module_seconds(trace: Trace, name_part: str) -> float:
    """Device seconds of the executables whose name holds ``name_part``,
    summed over runs and devices."""
    return sum(e - s for evs in trace.modules.values()
               for name, s, e in evs if name_part in name) / 1e9


def span_intervals(trace: Trace, name: str) -> List[Interval]:
    return union((s, e) for n, s, e in trace.spans if n == name)


def idle_share(trace: Trace, span: str = "serving") -> float:
    """Share of the time inside ``span`` during which no operation ran,
    averaged over devices; raises if the span never opened."""
    work = span_intervals(trace, span)
    total = length(work)
    if total <= 0:
        raise ValueError(f"no {span!r} span in the trace")
    devs = trace.devices
    return sum(1.0 - overlap(busy(trace, d), work) / total
               for d in devs) / len(devs)


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_decode_and_pick(1613...)`` -> ``decode_and_pick``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` operations with the most device time (seconds), named
    ``<executable>/<operation>`` as the trace names them.  Loops
    (``while``), which hold other operations, are left out: their time
    is their body's."""
    tot: Dict[str, float] = {}
    for dev, evs in trace.ops.items():
        mods = sorted(trace.modules.get(dev, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, e in evs:
            short = op_name(name)
            if short.startswith(CONTAINERS):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][2]:
                short = module_name(mods[i][0]) + "/" + short
            tot[short] = tot.get(short, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, k: int = 10,
              labels: Sequence[str] = ("step", "generator",
                                       "await_arrival")) -> List[List]:
    """The ``k`` longest gaps between device operations, each named by
    the host span that overlaps it most (``none`` where no span does)."""
    dev = trace.devices[0]
    merged = busy(trace, dev)
    host = [(n, s, e) for n, s, e in trace.spans if n in labels]
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _)
            in zip(merged[:-1], merged[1:]) if b_start > a_end]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        best, best_ov = "none", 0.0
        for n, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > best_ov:
                best, best_ov = n, ov
        out.append([best, (e - s) / 1e9])
    return out
