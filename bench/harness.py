"""One served cell, run once: set-up, an open-loop window, the drain, the
comparison with the reference, and the metrics.

The window drives ``BatchedServer.submit`` and ``BatchedServer.step``
(``serve/decode.py``) from one thread.  Each request is submitted at its
due time whether or not earlier ones have finished, and ``step()`` runs
whenever the server has work.  Every output token is stamped on the host
clock when the server appends it to its request.  Times are measured
from the request's due time, so a stall is charged to every request it
delays.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import oracle, stats, tracereduce, traffic, weights
from bench.spec import Bench, Cell

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
DRAIN_LIMIT_S = 120.0


class CompileLog:
    """XLA compiles (a persistent-cache hit counts as one, with its
    retrieval time) and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self._jax = jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, secs: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += secs

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1

    def close(self) -> None:
        mon = self._jax.monitoring
        mon.unregister_event_duration_listener(self._on_time)
        mon.unregister_event_listener(self._on_event)


class StepClock:
    """The index of the server step in progress (-1 before the first)."""
    step = -1


class TimedTokens(list):
    """A request's output tokens, each stamped with the host time and the
    server step at which the server appended it."""

    def __init__(self, clock: StepClock):
        super().__init__()
        self.clock = clock
        self.times: List[float] = []
        self.steps: List[int] = []

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        self.steps.append(self.clock.step)
        super().append(tok)


@dataclasses.dataclass
class Served:
    """One request of the window, as the benchmark saw it."""
    due: float             # host time it was due
    submitted: float       # host time it was submitted
    prompt: np.ndarray
    max_new: int
    req: Any               # the server's Request

    @property
    def tokens(self) -> TimedTokens:
        return self.req.tokens


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader (``metrics/<name>.py``) reads."""
    model: Dict[str, Any]          # the configuration's ``model`` block
    peaks: Optional[Dict[str, Any]]
    t0: float                      # host time the window opened
    window_s: float
    requests: List[Served]
    step_starts: List[float]       # host time each step started
    window_steps: Tuple[int, int]  # [first, last) step index in the window
    compiles_in_window: int
    trace: Optional[tracereduce.Trace]
    family: Any = None             # references/<reference>.py: the counts

    def in_window(self, step: int) -> bool:
        return self.window_steps[0] <= step < self.window_steps[1]

    def window_prefills(self) -> List[int]:
        """Prompt lengths prefilled by steps of the window."""
        return [len(r.prompt) for r in self.requests
                if r.tokens.steps and self.in_window(r.tokens.steps[0])]

    def window_decode_steps(self) -> Dict[int, List[int]]:
        """Step -> keys each live slot's query attended in that step's
        decode (prompt length plus the tokens before it)."""
        out: Dict[int, List[int]] = {}
        for r in self.requests:
            for j, s in enumerate(r.tokens.steps[1:], start=1):
                if self.in_window(s):
                    out.setdefault(s, []).append(len(r.prompt) + j)
        return out


def _replace(base, given: Dict[str, Any], what: str):
    """``base`` (a dataclass) with the fields ``given`` states; a key it
    has no field for is an error."""
    unknown = set(given) - {f.name for f in dataclasses.fields(base)}
    if unknown:
        raise KeyError(f"{what} keys unknown to {type(base).__name__}: "
                       f"{sorted(unknown)}")
    return dataclasses.replace(base, **given)


def build_model_config(model: Dict[str, Any], arch: str):
    """The program's ``ModelConfig``: its registry entry for ``arch`` with
    every field the configuration file states.  A nested object (``moe``,
    ``ssm``, ``encoder``) states fields of the registry entry's spec of
    that name, which must have one."""
    from repro.configs import get_config
    base = get_config(arch)
    model = dict(model)
    for name, block in model.items():
        if isinstance(block, dict):
            spec = getattr(base, name, None)
            if not dataclasses.is_dataclass(spec):
                raise KeyError(f"model.{name}: {arch}'s registry entry has "
                               f"no {name} spec")
            model[name] = _replace(spec, block, f"model.{name}")
    return _replace(base, model, "model")


def _field(block: Dict[str, Any], dotted: str):
    for part in dotted.split("."):
        block = block[part]
    return block


def check_keymap(config: Dict[str, Any]) -> None:
    """The ``model`` block must state the same numbers as the source's
    keys it names in ``keymap``; a dotted field (``moe.top_k``) names a
    field of a nested spec block."""
    for field, key in config.get("keymap", {}).items():
        want, got = config["config"][key], _field(config["model"], field)
        if want != got:
            raise ValueError(f"{config['name']}: model.{field} = {got} but "
                             f"config.{key} = {want}")


def _span(trace: bool):
    if not trace:
        return lambda name: contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


class Window:
    """The open loop: submits at due times, steps while there is work."""

    def __init__(self, server, arrivals: List[traffic.Arrival], clock:
                 StepClock, *, trace: bool):
        self.server = server
        self.arrivals = arrivals
        self.clock = clock
        self.span = _span(trace)
        self.served: List[Served] = []
        self.step_starts: List[float] = []
        self.t0 = 0.0
        self._serving = None

    def _has_work(self) -> bool:
        return bool(self.server.queue) or any(
            a is not None for a in self.server.active)

    def _step(self) -> None:
        if self._serving is None:
            self._serving = self.span("serving")
            self._serving.__enter__()
        with self.span("step"):
            self.step_starts.append(time.perf_counter())
            self.clock.step += 1
            self.server.step()
        if not self._has_work():
            self._close_serving()

    def _close_serving(self) -> None:
        if self._serving is not None:
            self._serving.__exit__(None, None, None)
            self._serving = None

    def run(self, t0: float, seconds: float) -> None:
        end, arr, i = t0 + seconds, self.arrivals, 0
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if i < len(arr) and t0 + arr[i].due_s <= now:
                with self.span("generator"):
                    while i < len(arr) and t0 + arr[i].due_s <= now:
                        a = arr[i]
                        req = self.server.submit(a.prompt, max_new=a.max_new)
                        req.tokens = TimedTokens(self.clock)
                        self.served.append(Served(
                            due=t0 + a.due_s, submitted=time.perf_counter(),
                            prompt=a.prompt, max_new=a.max_new, req=req))
                        i += 1
            if self._has_work():
                self._step()
            else:
                wake = min(t0 + arr[i].due_s if i < len(arr) else end, end)
                with self.span("await_arrival"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
        self._close_serving()

    def drain(self, limit_s: float) -> None:
        deadline = time.perf_counter() + limit_s
        while (not all(s.req.done for s in self.served)
               and time.perf_counter() < deadline):
            self._step()
        self._close_serving()


def warm_up(server, cell: Cell) -> Dict[str, float]:
    """Run every executable the cell's traffic can reach once: each
    prefill bucket at each packed row count, and the decode step.
    Returns the seconds each took, keyed ``<bucket>x<rows>``."""
    s = cell.settings
    rows, n = [], 1
    while n <= s["slots"]:
        rows.append(n)
        n *= 2
    rng = np.random.default_rng(0)
    vocab = cell.config["model"]["vocab_size"]
    took = {}
    for bucket in s["buckets"]:
        length = min(bucket, s["max_len"] - 2)
        for n in rows:
            t = time.perf_counter()
            for _ in range(n):
                server.submit(rng.integers(0, vocab, size=length,
                                           dtype=np.int32), max_new=2)
            server.run(max_steps=100)
            took[f"{bucket}x{n}"] = round(time.perf_counter() - t, 3)
    return took


def make_server(cell: Cell, params):
    """The program's server over ``params``, which must have the layout
    the program's own initialisation would give (checked on shapes)."""
    from repro.kernels import ops
    from repro.models import get_model
    from repro.serve import BatchedServer
    model = get_model(build_model_config(cell.config["model"],
                                         cell.config["arch"]))
    weights.check_layout(params, model.abstract_params())
    s = cell.settings
    return BatchedServer(model, params, slots=s["slots"],
                         max_len=s["max_len"], buckets=tuple(s["buckets"]),
                         telemetry=ops.Telemetry())


def reference_check(cell: Cell, params, served: List[Served], seed: int, *,
                    control: bool) -> Dict[str, Any]:
    """The served tokens against the family's float32 reference; with
    ``control``, the int8 reference's choices in the server's place, on
    the same prompts and served tokens."""
    ref = cell.family
    m = cell.config["model"]
    done = [s for s in served if s.req.done]
    chk = cell.settings["check"]

    def request_gaps(seq, start, out):
        want = ref.logits(params, m, seq, start)
        if control:
            return oracle.control_gaps(
                want, ref.logits(params, m, seq, start, int8=True))
        return oracle.gaps(want, out)

    return oracle.check(
        [s.prompt for s in done], [list(s.tokens) for s in done],
        [(s.tokens.steps[0], s.tokens.steps[-1]) for s in done],
        [s.max_new for s in done], unfinished=len(served) - len(done),
        vocab=m["vocab_size"], request_gaps=request_gaps,
        limits=chk["limits"], seed=seed, min_tokens=chk["min_tokens"],
        min_requests=chk["min_requests"])


def generator_lag(window: Window) -> Dict[str, float]:
    """How late requests were submitted after their due time (a request
    due while a step runs waits for it), and the slowest step."""
    lag = [s.submitted - s.due for s in window.served]
    steps = np.diff(window.step_starts) if len(window.step_starts) > 1 \
        else np.zeros(1)
    return {"lag_p50_ms": 1e3 * stats.percentile(lag, 50),
            "lag_p99_ms": 1e3 * stats.percentile(lag, 99),
            "lag_max_ms": 1e3 * max(lag),
            "slowest_step_ms": 1e3 * float(steps.max()),
            "slowest_step_at_s": float(window.step_starts[int(steps.argmax())]
                                       - window.t0)}


def start_server(cell: Cell, seed: int, compiles: CompileLog
                 ) -> Tuple[Any, Any, Dict[str, Any]]:
    """Weights from ``seed`` by the family's table, the server with its
    executables, and the warm-up.  Returns (params, server, the set-up's
    parts)."""
    import jax
    m = cell.config["model"]
    t = time.perf_counter()
    params = jax.block_until_ready(weights.make(cell.family.shapes(m), m,
                                                seed))
    t_weights = time.perf_counter()
    server = make_server(cell, params)
    t_exec = time.perf_counter()
    parts = {"weights_s": t_weights - t, "executables_s": t_exec - t_weights,
             "executables": server.aot_compiles, "compiles": compiles.count,
             "cache_hits": compiles.cache_hits}
    took = warm_up(server, cell)
    parts["warmup_s"] = time.perf_counter() - t_exec
    parts["warmup_runs_s"] = took
    return params, server, parts


@dataclasses.dataclass
class WindowRun:
    window: Window
    t0: float
    t_end: float
    steps: Tuple[int, int]         # [first, last) step index of the window
    compiles_in_window: int
    log_dir: Optional[str]


def serve_window(server, cell: Cell, *, seed: int, seconds: float,
                 rate: float, trace: bool, compiles: CompileLog,
                 drain_s: float = DRAIN_LIMIT_S) -> WindowRun:
    """The cell's traffic for ``seconds`` at ``rate``, then the drain.
    With ``trace`` the profiler records the window (not the drain)."""
    import jax
    arrivals = traffic.schedule(cell.traffic, rate=rate, seconds=seconds,
                                seed=seed,
                                vocab=cell.config["model"]["vocab_size"])
    clock = StepClock()
    window = Window(server, arrivals, clock, trace=trace)
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(log_dir, profiler_options=_profile_options())
    c_before = compiles.count
    t0 = window.t0 = time.perf_counter()
    window.run(t0, seconds)
    t_end = time.perf_counter()
    run = WindowRun(window=window, t0=t0, t_end=t_end,
                    steps=(0, clock.step + 1),
                    compiles_in_window=compiles.count - c_before,
                    log_dir=log_dir)
    if trace:
        jax.profiler.stop_trace()
    window.drain(drain_s)
    return run


def _profile_options():
    """Device and host activity with the benchmark's spans; no Python
    function tracing, which would slow the host."""
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run_cell(bench: Bench, workload: str, *, seed: int, seconds: float,
             trace: bool, t_process: float, control: bool = False,
             log=print) -> Dict[str, Any]:
    """Run ``workload`` once and return its result line's object.  The
    caller has checked the device; ``log`` takes the earlier lines."""
    import jax
    cell = bench.cell(workload)
    check_keymap(cell.config)
    m = cell.config["model"]
    dev = jax.devices()[0]
    peaks = bench.peaks(dev.device_kind) if dev.platform == "tpu" else None
    compiles = CompileLog()
    t_import = time.perf_counter()
    params, server, parts = start_server(cell, seed, compiles)
    run = serve_window(server, cell, seed=seed, seconds=seconds,
                       rate=cell.settings["rate_rps"], trace=trace,
                       compiles=compiles)
    compiles.close()
    setup = {"setup_s": run.t0 - t_process, "import_s": t_import - t_process,
             **parts}
    log("setup: " + " ".join(f"{k}={v}".replace(" ", "")
                             for k, v in setup.items()))
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    served = run.window.served
    n_window = len(served)
    log("generator: " + " ".join(
        f"{k}={v}" for k, v in generator_lag(run.window).items())
        + f" requests={n_window} window_s={run.t_end - run.t0}"
        f" compiles_in_window={run.compiles_in_window}")

    # the program's state goes before the reference runs
    run.window.server = None
    del server
    gc.collect()
    check = reference_check(cell, params, served, seed, control=control)
    log("readings: " + " ".join(f"{k}={v}" for k, v in
                                check["readings"].items()))
    del params
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out: Dict[str, Any] = {"correct": check["correct"],
                           "attempted": n_window,
                           "failed": n_window - sum(s.req.done
                                                    for s in served)}
    if not trace:
        finished = [s for s in served if s.tokens.times]
        e2e = stats.end_to_end([s.tokens.times for s in finished])
        e2e["setup_s"] = setup["setup_s"]
        units = {x["name"]: x["unit"] for x in cell.end_to_end}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in e2e.items() if k in units}
    else:
        tr = tracereduce.load(tracereduce.find_xplane(run.log_dir))
        shutil.rmtree(run.log_dir, ignore_errors=True)
        rec = RunRecord(model=m, peaks=peaks, t0=run.t0,
                        window_s=run.t_end - run.t0, requests=served,
                        step_starts=run.window.step_starts,
                        window_steps=run.steps,
                        compiles_in_window=run.compiles_in_window,
                        trace=tr if tr.devices else None, family=cell.family)
        metrics = {}
        for x in cell.per_layer:
            v = bench.metric_reader(x["name"])(rec)
            if v is not None:
                metrics[x["name"]] = {"value": v, "unit": x["unit"]}
        out["metrics"] = metrics
        if rec.trace is not None:
            device["busy_s"] = tracereduce.busy_s(rec.trace)
            out["breakdown"] = {
                "device_ops": tracereduce.top_ops(rec.trace),
                "idle_gaps": tracereduce.idle_gaps(rec.trace)}
        device["window_s"] = run.t_end - run.t0
    out["device"] = device
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in check["compared"].items()}
    out["_check_lines"] = oracle.format_lines(check)
    return out


def cache_dir(root: str) -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else a fixed directory inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
