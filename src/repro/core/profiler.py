"""Timing + platform abstraction.

Eq. 3 of the paper: each candidate is run R times, the R measurements are
sorted, the lowest and highest k are discarded, and the rest averaged
(trimmed mean) to suppress system noise.  The measurement loop itself
lives in ``repro.core.measure``: R is the *cap*, and the adaptive engine
stops early once the trimmed mean's CI half-width converges (or the
candidate provably loses to the incumbent); ``wallclock`` below is the
legacy fixed-R entry point.

Two platforms mirror the paper's NVIDIA/DCU pair (DESIGN.md §3):

* ``CPUPlatform``       — wall-clocks the jit-compiled jnp lowering of a
  variant on the host CPU (a *measured* feedback signal), on the CPU
  device even in a process whose default device is a TPU.
* ``TPUModelPlatform``  — analytic TPU v5e roofline over the case's
  flops/traffic model (+ optionally the while-aware HLO walker), since no
  TPU exists in this container.  Timing = max(compute, memory) + a fixed
  per-launch overhead.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core.kernelcase import KernelCase, Variant
from repro.launch import mesh as hw


@dataclass
class TimingResult:
    trimmed_mean_s: float
    times_s: List[float]
    r: int                        # reps actually collected
    k: int                        # trim actually applied (effective k)
    ci_half_width_s: float = 0.0  # normal-CI half-width of the trimmed mean
    r_cap: int = 0                # eq. 3 cap in force (0 → legacy/unknown)
    raced_out: bool = False       # aborted: lower bound lost to incumbent
    deterministic: bool = False   # analytic timer, single rep is exact

    @property
    def raw_mean_s(self) -> float:
        return float(np.mean(self.times_s))

    @property
    def ci_rel(self) -> float:
        """CI half-width relative to the trimmed mean."""
        return self.ci_half_width_s / self.trimmed_mean_s \
            if self.trimmed_mean_s else 0.0

    @property
    def lower_bound_s(self) -> float:
        """Optimistic lower bound: the best observed rep minus the CI
        half-width — what incumbent racing compares against."""
        return min(self.times_s) - self.ci_half_width_s


def trimmed_mean(times: Sequence[float], k: int) -> float:
    """Eq. 3: drop lowest/highest k of R sorted measurements (R > 2k)."""
    r = len(times)
    if r <= 2 * k:
        raise ValueError(f"R={r} must exceed 2k={2 * k}")
    s = sorted(times)
    kept = s[k:r - k] if k else s
    return float(np.mean(kept))


def wallclock(fn: Callable, inputs, *, r: int, k: int,
              warmup: int = 1) -> TimingResult:
    """Fixed-R eq. 3 wall-clock (legacy entry point).  The measurement
    loop itself lives in ``repro.core.measure``; this wrapper pins the
    engine to the non-adaptive path so existing callers keep the exact
    R-rep behaviour.  Each warmup call blocks on its own output (a
    deferred compile must not leak into the first timed rep), and
    ``warmup=0`` is supported."""
    from repro.core.measure import MeasureConfig, measure_fn
    return measure_fn(fn, inputs, r=r, k=k,
                      cfg=MeasureConfig(adaptive=False, race=False,
                                        warmup=warmup))


# --------------------------------------------------------------------------
class _LRUCache:
    """Thread-safe bounded LRU keyed by variant; recently-timed entries
    stay, the least-recently-timed are evicted."""

    def __init__(self, maxsize: int):
        self.maxsize = max(1, maxsize)
        self._od: "OrderedDict[Any, Any]" = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            if key not in self._od:
                return None
            self._od.move_to_end(key)
            return self._od[key]

    def put(self, key, val) -> None:
        with self._lock:
            self._od[key] = val
            self._od.move_to_end(key)
            while len(self._od) > self.maxsize:
                self._od.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._od


# name → zero-arg factory; lets an out-of-process worker reconstruct the
# scheduler's platform from the name string in an eval spec
_PLATFORM_FACTORIES: Dict[str, Callable[[], "Platform"]] = {}


def register_platform(name: str,
                      factory: Callable[[], "Platform"]) -> None:
    """Register a platform factory under ``name`` so eval specs can refer
    to platforms by string (workers call ``platform_from_name``).
    Re-registering a name replaces the factory (tests, custom tunings)."""
    _PLATFORM_FACTORIES[name] = factory


def platform_from_name(name: str) -> "Platform":
    """Reconstruct a platform from its spec string (wire form)."""
    try:
        factory = _PLATFORM_FACTORIES[name]
    except KeyError:
        raise KeyError(f"unknown platform {name!r}; registered: "
                       f"{sorted(_PLATFORM_FACTORIES)}") from None
    return factory()


class Platform:
    name: str = "abstract"
    # True → timing is analytic/deterministic: candidates can be timed
    # from concurrent workers with no coordination at all.  Measured
    # (wall-clock) platforms stay False, which routes their timing
    # through the measurement engine's timing lease — only the short
    # wall-clock slices serialize (process-wide mutex + cross-process
    # flock arbiter), so measured campaigns still fan out across
    # threads and worker processes.
    concurrency_safe: bool = False

    def time_variant(self, case: KernelCase, variant: Variant, scale: int,
                     inputs, *, r: int, k: int,
                     budget: Optional["MeasureConfig"] = None,
                     incumbent_s: Optional[float] = None) -> TimingResult:
        """Eq. 3 timing.  ``r`` is the rep cap, ``k`` the trim count;
        ``budget`` (a ``repro.core.measure.MeasureConfig``) enables the
        adaptive engine's CI-based early stop and carries the timing
        lease, and ``incumbent_s`` arms incumbent racing."""
        raise NotImplementedError

    def profile_feedback(self, case: KernelCase, variant: Variant,
                         scale: int) -> Dict[str, float]:
        """Profiler counters handed to the proposer (paper: cache hit rate,
        occupancy; here: arithmetic intensity, VMEM footprint, ...)."""
        fl = case.flops(scale)
        tb = case.generic_traffic(variant, scale)
        return {
            "flops": fl,
            "traffic_bytes": tb,
            "arithmetic_intensity": fl / max(tb, 1.0),
        }


class CPUPlatform(Platform):
    name = "cpu"
    concurrency_safe = False     # measured wall-clock

    def __init__(self, max_cache: Optional[int] = None):
        if max_cache is None:
            max_cache = int(os.environ.get("REPRO_CPU_CACHE_MAX", "64"))
        self._cache = _LRUCache(max_cache)

    def _compiled(self, case: KernelCase, variant: Variant):
        # builds jit their own stages: an unfused variant is a chain of
        # separately-jitted passes (the CUDA multi-kernel-launch analogue),
        # so the platform must NOT wrap another jit around it.
        key = (case.name, tuple(sorted(variant.items())))
        fn = self._cache.get(key)
        if fn is None:
            fn = case.build(variant, impl="jnp")
            self._cache.put(key, fn)
        return fn

    def time_variant(self, case, variant, scale, inputs, *, r, k,
                     budget=None, incumbent_s=None):
        from repro.core.measure import measure_fn
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            fn = self._compiled(case, variant)
            return measure_fn(fn, jax.device_put(inputs, cpu), r=r, k=k,
                              cfg=budget, incumbent_s=incumbent_s)


class TPUModelPlatform(Platform):
    """Analytic v5e roofline: t = max(flops/197T, traffic/819G) + overhead.

    The per-variant traffic model is where tiling choices matter: a GEMM
    with block (bm, bn, bk) re-reads A grid_n times and B grid_m times, so
    bigger MXU-aligned blocks reduce the memory term — the same signal a
    real profile would give the LLM.
    """
    name = "tpu-v5e-model"
    concurrency_safe = True      # analytic, no shared timing state
    LAUNCH_OVERHEAD_S = 2e-6

    def __init__(self, peak_flops: float = hw.PEAK_FLOPS_BF16,
                 hbm_bw: float = hw.HBM_BW):
        self.peak_flops = peak_flops
        self.hbm_bw = hbm_bw

    def time_variant(self, case, variant, scale, inputs, *, r, k,
                     budget=None, incumbent_s=None):
        fl = case.flops(scale)
        tb = case.generic_traffic(variant, scale)
        # dtype strategy: fp32 accumulate with bf16 storage halves traffic
        if variant.get("compute_dtype") == "bf16":
            tb *= 0.5
            fl_t = fl / self.peak_flops
        else:
            fl_t = fl / (self.peak_flops / 2)      # fp32 MXU rate is halved
        mem_t = tb / self.hbm_bw
        # misaligned tiles waste MXU lanes
        util = variant_mxu_utilization(variant)
        t = (max(fl_t / util, mem_t) + self.LAUNCH_OVERHEAD_S
             + case.variant_latency(variant, scale))
        # the model is a pure function of (variant, scale): one rep IS
        # the distribution — no synthetic [t]*R padding, zero CI width,
        # flagged deterministic so consumers can tell it apart from a
        # measured single rep
        return TimingResult(t, [t], 1, 0, ci_half_width_s=0.0,
                            r_cap=max(1, int(r)), deterministic=True)

    def profile_feedback(self, case, variant, scale):
        fb = super().profile_feedback(case, variant, scale)
        fb["mxu_utilization"] = variant_mxu_utilization(variant)
        fb["vmem_bytes"] = variant_vmem_bytes(variant)
        lat = case.variant_latency(variant, scale)
        roof = max(case.flops(scale) / self.peak_flops,
                   case.generic_traffic(variant, scale) / self.hbm_bw)
        fb["latency_s"] = lat
        fb["latency_fraction"] = lat / max(lat + roof, 1e-12)
        return fb


register_platform(CPUPlatform.name, CPUPlatform)
register_platform(TPUModelPlatform.name, TPUModelPlatform)


def variant_mxu_utilization(variant: Variant) -> float:
    """Fraction of the 128×128 MXU (and 8×128 VPU lanes) a tile fills."""
    util = 1.0
    for key in ("block_m", "block_n", "block_k", "block"):
        b = variant.get(key)
        if b is None:
            continue
        if b % 128 == 0:
            continue
        if b % 8 == 0:
            util = min(util, max(b % 128, 8) / 128 if b < 128 else 0.9)
        else:
            util = min(util, 0.5)
    return max(util, 0.05)


def variant_vmem_bytes(variant: Variant) -> int:
    """Working-set estimate for the BlockSpec tiles (used by AER's VMEM
    overflow repair; v5e VMEM ≈ 128 MiB)."""
    bm = variant.get("block_m", 128)
    bn = variant.get("block_n", 128)
    bk = variant.get("block_k", 128)
    dt = 2 if variant.get("compute_dtype") == "bf16" else 4
    return int((bm * bk + bk * bn + bm * bn) * dt)


VMEM_BYTES = 128 * 1024 * 1024
