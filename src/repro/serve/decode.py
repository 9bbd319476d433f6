"""Continuous-batching serving engine: ragged decode, bucketed packed
prefill, per-bucket AOT executables.

``BatchedServer`` keeps a fixed pool of KV-cache *slots* and streams
greedy decode continuously:

* **Ragged decode** — a per-slot position vector is threaded through
  ``model.decode_step``, so every slot advances independently: admitting
  a short prompt next to a long one, or a sequence finishing mid-batch,
  never stalls or length-aligns the rest of the batch.
* **Bucketed packed prefill** — admitted prompts are grouped into
  power-of-two length buckets, right-padded to their bucket, and
  prefilled as one packed batch per bucket (one device call per bucket
  per admission wave, not one per request).  Under causal attention the
  pad tail cannot influence earlier positions and pad K/V beyond the true
  length is masked out at decode, so packed prefill is exactly equivalent
  to per-request prefill.  Recurrent-state families (ssm / hybrid carry
  cumulative scan state, which padding would corrupt) fall back to
  exact-length buckets: still packed, never padded.  Their chunked-scan
  prompt-length constraints (``cfg.ssm.chunk`` divisibility for long
  prompts) are the model's own, shared with ``generate()``.
* **Per-bucket AOT executables** — every (bucket, packed-rows) prefill
  shape plus the decode step is ``jax.jit(...).lower(...).compile()``d at
  startup, so steady-state traffic never hits a mid-request trace.  The
  swap-epoch contract is preserved: a registry mutation
  (``ops.registry_epoch``) invalidates all executables at the next step
  boundary and they are rebuilt against the newly active impls.
* **Per-bucket telemetry** — every prefill/decode event is tagged with
  the request's bucket, so each (site, bucket) pair is a distinct
  telemetry site and ``serve.autotune`` campaigns per traffic bucket at
  that bucket's observed scale.
* **One step ahead** — step call k dispatches its admission prefills
  and decode k before it reads decode k−1's tokens and the prefills'
  first tokens back, so the host's read-back and bookkeeping run while
  decode k runs on the device.  Decode k's token input stays on the
  device: decode k−1's picks with each admitted prompt's first token
  spliced in by its prefill.  Positions and budget finishes are known on
  the host at dispatch; only an EOS is learned one step late, and the
  one token its slot decodes past it is dropped.
* **Profiler spans** — ``submit`` and every phase of ``step`` open a
  ``jax.profiler.TraceAnnotation`` named ``serve.*``, so a profiler
  trace shows on the device's clock what the host did in each idle gap.
  They record only while a profiler runs and cost under a microsecond
  each otherwise.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels import ops


def generate(model, params, prompts: jnp.ndarray, *, max_new: int = 16,
             frames: Optional[jnp.ndarray] = None,
             eos_id: Optional[int] = None) -> np.ndarray:
    """Greedy generation for a fixed batch.  prompts: [B, S] int32.

    With ``eos_id``, a sequence stops at its first EOS: every later
    column is masked to ``eos_id`` (pad-with-eos), and the loop exits
    early once all rows have finished.
    """
    B, S = prompts.shape
    max_len = S + max_new
    if model.cfg.family == "encdec":
        logits, cache = model.prefill(params, prompts, frames,
                                      max_len=max_len)
    else:
        logits, cache = model.prefill(params, prompts, max_len=max_len)
    step = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1, :model.cfg.vocab_size],
                     axis=-1).astype(jnp.int32)[:, None]
    done = (tok[:, 0] == eos_id) if eos_id is not None \
        else jnp.zeros((B,), bool)
    out = [tok]
    for i in range(max_new - 1):
        if eos_id is not None and bool(done.all()):
            break
        logits, cache = step(params, cache, tok, jnp.int32(S + i))
        nxt = jnp.argmax(logits[:, -1, :model.cfg.vocab_size],
                         axis=-1).astype(jnp.int32)[:, None]
        if eos_id is not None:
            nxt = jnp.where(done[:, None], jnp.int32(eos_id), nxt)
            done = done | (nxt[:, 0] == eos_id)
        tok = nxt
        out.append(tok)
    res = np.asarray(jnp.concatenate(out, axis=1))
    if res.shape[1] < max_new:        # early EOS exit: pad-with-eos
        pad = np.full((B, max_new - res.shape[1]), eos_id, res.dtype)
        res = np.concatenate([res, pad], axis=1)
    return res


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    bucket: int = 0               # prefill length bucket admitted under


def _pow2_buckets(max_len: int, lo: int = 8) -> Tuple[int, ...]:
    """Power-of-two prompt-length buckets up to ``max_len``."""
    out, b = [], lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


class BatchedServer:
    """Continuous-batching greedy server over a fixed slot count."""

    def __init__(self, model, params, *, slots: int = 4, max_len: int = 128,
                 eos_id: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 aot: bool = True,
                 telemetry_site: str = "attention",
                 telemetry: Optional[ops.Telemetry] = None):
        assert model.cfg.family != "encdec", "use generate() for enc-dec"
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        # padding a packed batch is only exact when positions beyond a
        # row's true length cannot leak into it: causal attention masks
        # them, but cumulative recurrent state (ssm / hybrid) would absorb
        # the pads — those families pack exact-length groups instead
        self.padded_packing = model.cfg.family not in ("ssm", "hybrid")
        if self.padded_packing:
            self.buckets: Tuple[int, ...] = tuple(sorted(
                buckets)) if buckets else _pow2_buckets(max_len)
        else:
            self.buckets = ()     # exact-length buckets, discovered live
        self.aot = aot
        self.site = telemetry_site
        self.telemetry = telemetry if telemetry is not None else ops.telemetry
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * slots
        self.finished: List[Request] = []
        # per-slot cache length, advanced when a decode is dispatched
        self.pos = np.zeros(slots, np.int32)
        # a buffer of its own for each entry (eager zeros of one shape
        # may share one), since the prefill donates each
        self.cache = jax.tree.map(jnp.copy, model.init_cache(slots, max_len))
        self.swap_epochs = 0                      # hot-swap re-traces so far
        self.aot_compiles = 0                     # executables built so far
        # decodes dispatched while the previous decode's tokens were
        # still on the device
        self.decodes_ahead = 0
        # tokens decoded for a request already finished when they were
        # read: the one step past an EOS learned late
        self.dropped_tokens = 0
        # the token each slot feeds its next decode, on the device
        self._feed = jax.device_put(np.zeros(slots, np.int32))
        # the decode whose tokens are not read yet: its picks and the
        # (slot, request) of each row it decoded
        self._ahead: Optional[Tuple[jax.Array,
                                    List[Tuple[int, Request]]]] = None
        self._rid = itertools.count()
        self._epoch = ops.registry_epoch()
        self._exec: Dict[Tuple, object] = {}      # (kind, ...) -> executable
        self._trace_steps()

    # ------------------------------------------------------- executables --
    def _trace_steps(self) -> None:
        """(Re)build the executable set against the current registry state.
        Fresh lowerings re-consult the registry, so a newly-installed impl
        takes effect here and only here."""
        self._exec.clear()
        self._get_decode()
        self._get_decode_input()
        if self.aot and self.padded_packing:
            n = 1
            while n <= _next_pow2(self.slots):
                for bucket in self.buckets:
                    self._get_prefill(bucket, n)
                n *= 2

    def _cache_avals(self):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.cache)

    def _aot(self, jitted, *args):
        """AOT-compile ``jitted`` for ``args`` (arrays or avals); a compile
        error propagates (a kernel the chip's compiler refuses must not be
        served by some other path).  With ``aot=False`` the jit object is
        returned as-is and compiles lazily on first call."""
        if not self.aot:
            return jitted
        ex = jitted.lower(*args).compile()
        self.aot_compiles += 1
        return ex

    def _get_decode(self):
        key = ("decode",)
        ex = self._exec.get(key)
        if ex is None:
            model, vocab = self.model, self.model.cfg.vocab_size

            def decode_and_pick(params, cache, toks, pos):
                # greedy argmax fused into the executable: one device
                # call per step, no eager logit slicing on the host
                logits, cache = model.decode_step(params, cache, toks, pos)
                return (jnp.argmax(logits[:, -1, :vocab],
                                   axis=-1).astype(jnp.int32), cache)

            ex = self._aot(
                jax.jit(decode_and_pick), self.params, self._cache_avals(),
                jax.ShapeDtypeStruct((self.slots, 1), jnp.int32),
                jax.ShapeDtypeStruct((self.slots,), jnp.int32))
            self._exec[key] = ex
        return ex

    def _get_decode_input(self):
        """``[slots]`` tokens on the device -> the decode's ``[slots, 1]``
        token input, without a round trip through the host."""
        key = ("decode_input",)
        ex = self._exec.get(key)
        if ex is None:
            def decode_input(feed):
                return feed[:, None]

            ex = self._aot(jax.jit(decode_input), jax.ShapeDtypeStruct(
                (self.slots,), jnp.int32))
            self._exec[key] = ex
        return ex

    def _get_prefill(self, bucket: int, n: int):
        key = ("prefill", bucket, n)
        ex = self._exec.get(key)
        if ex is None:
            model, max_len = self.model, self.max_len
            vocab = model.cfg.vocab_size

            def packed_prefill(params, toks, lens, cache, si, feed):
                # prefill + greedy pick + slot splice fused into one
                # executable: row r lands in cache slot si[r], and its
                # first token becomes that slot's next decode input; pad
                # rows carry an out-of-range index and are dropped
                logits, cache1 = model.prefill(params, toks,
                                               max_len=max_len,
                                               lengths=lens)
                first = jnp.argmax(logits[:, -1, :vocab],
                                   axis=-1).astype(jnp.int32)

                def put(big, one):
                    return big.at[:, si].set(one.astype(big.dtype),
                                             mode="drop")
                return (first, feed.at[si].set(first, mode="drop"),
                        jax.tree.map(put, cache, cache1))

            # the cache is donated: the rows land in place, so the chip
            # holds one cache beside the prefill's own buffers, not two.
            # The feed is not: it is the last decode's picks, which the
            # host reads after this call
            vec = jax.ShapeDtypeStruct((self.slots,), jnp.int32)
            ex = self._aot(
                jax.jit(packed_prefill, donate_argnums=(3,)), self.params,
                jax.ShapeDtypeStruct((n, bucket), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
                self._cache_avals(),
                jax.ShapeDtypeStruct((n,), jnp.int32), vec)
            self._exec[key] = ex
        return ex

    def _refresh_impls(self) -> None:
        """Swap epoch: if the ops registry changed since the last trace,
        rebuild every executable at this step boundary.  In-flight
        requests keep their cache rows and continue undisturbed."""
        epoch = ops.registry_epoch()
        if epoch != self._epoch:
            self._epoch = epoch
            self.swap_epochs += 1
            with TraceAnnotation("serve.rebuild"):
                self._trace_steps()

    # --------------------------------------------------------- admission --
    def bucket_of(self, prompt_len: int) -> int:
        """The prefill bucket a prompt of this length is admitted under."""
        if not self.padded_packing:
            return prompt_len                    # exact-length packing
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(f"prompt length {prompt_len} exceeds the largest "
                         f"bucket {self.buckets[-1]} (max_len={self.max_len})")

    def submit(self, prompt: np.ndarray, max_new: int = 16) -> Request:
        with TraceAnnotation("serve.submit"):
            req = Request(rid=next(self._rid), prompt=prompt,
                          max_new=max_new, bucket=self.bucket_of(len(prompt)))
            self.queue.append(req)
        return req

    def _budget(self, req: Request) -> int:
        """Tokens ``req`` is served unless it meets EOS first: its
        ``max_new``, or fewer where its answer would overrun the cache."""
        return min(req.max_new, self.max_len - len(req.prompt) + 1)

    def _decodes(self, s: int) -> bool:
        """Slot ``s`` holds a request with a decode left to dispatch.  A
        slot whose request has all its tokens dispatched is free, even
        while its last token is still on the device: a prefill into it
        runs after that decode, whose cache it takes."""
        req = self.active[s]
        return req is not None and \
            int(self.pos[s]) - len(req.prompt) + 1 < self._budget(req)

    def _finish(self, req: Request, slot: Optional[int]) -> None:
        req.done = True
        self.finished.append(req)
        if slot is not None and self.active[slot] is req:
            self.active[slot] = None          # slot recycled at next admit
            self.pos[slot] = 0

    def _admit(self) -> List[Tuple[jax.Array, List[Tuple[Optional[int],
                                                         Request]]]]:
        """Drain the queue into free slots, one packed prefill call per
        bucket per wave.  Returns each call's first tokens, still on the
        device, with the (slot, request) of each row; a request that ends
        at its first token takes no slot."""
        calls = []
        while self.queue:
            free = [s for s in range(self.slots) if not self._decodes(s)]
            if not free:
                break
            wave, rest = self.queue[:len(free)], self.queue[len(free):]
            self.queue = rest
            groups: Dict[int, List[Request]] = {}
            for req in wave:                  # FIFO within each bucket
                groups.setdefault(req.bucket, []).append(req)
            fi = 0
            done_at_prefill = False
            for bucket, reqs in groups.items():
                n_pad = _next_pow2(len(reqs))  # bounded executable count
                with TraceAnnotation("serve.prefill"):
                    toks = np.zeros((n_pad, bucket), np.int32)
                    lens = np.ones((n_pad,), np.int32)
                    # pad rows, and rows of requests that end at their
                    # prefill token, point past the pool: the splice in
                    # the executable drops them
                    si = np.full((n_pad,), self.slots, np.int32)
                    rows: List[Tuple[Optional[int], Request]] = []
                    for r, req in enumerate(reqs):
                        toks[r, :len(req.prompt)] = req.prompt
                        lens[r] = len(req.prompt)
                        if self._budget(req) <= 1:
                            rows.append((None, req))
                            done_at_prefill = True
                            continue
                        s = si[r] = free[fi]
                        fi += 1
                        rows.append((s, req))
                        self.active[s] = req
                        self.pos[s] = len(req.prompt)
                    first, self._feed, self.cache = self._get_prefill(
                        bucket, n_pad)(
                        self.params, jnp.asarray(toks), jnp.asarray(lens),
                        self.cache, jnp.asarray(si), self._feed)
                    # start the read-back now: it waits for this call,
                    # not for the decode queued behind it
                    first.copy_to_host_async()
                calls.append((first, rows))
            if not done_at_prefill:
                break                         # every slot given is taken
            # some requests took no slot: loop to admit more while the
            # queue has work
        return calls

    def _take(self, req: Request, tok: int, slot: Optional[int]) -> None:
        """Append a token read back from the device to ``req`` and finish
        it at EOS or at its budget; drop it where ``req`` has finished
        already (its EOS was read one step late)."""
        if req.done:
            self.dropped_tokens += 1
            return
        req.tokens.append(tok)
        if ((self.eos_id is not None and tok == self.eos_id)
                or len(req.tokens) >= self._budget(req)):
            self._finish(req, slot)           # EOS / budget / cache full

    # ------------------------------------------------------------- steps --
    def step(self) -> int:
        """One serving step, one step ahead: admit (packed prefill per
        bucket) and dispatch one ragged decode over every slot with a
        token left to decode, then read back the previous step's decode
        and this step's prefills and append their tokens while the new
        decode runs.  Returns the amount of work done — requests
        admitted, tokens dispatched and tokens read — so ``0`` means the
        server is idle (queue empty, no live slots, nothing in flight)."""
        with TraceAnnotation("serve.step"):
            self._refresh_impls()
            prefills = self._admit()
            live = [s for s in range(self.slots) if self._decodes(s)]
            ahead, self._ahead = self._ahead, None
            if live:
                with TraceAnnotation("serve.decode"):
                    # per-slot positions: a slot with nothing to decode
                    # decodes a dummy token at pos 0 (its row is fully
                    # overwritten at the next admission)
                    pos = np.zeros(self.slots, np.int32)
                    pos[live] = self.pos[live]
                    nxt, self.cache = self._get_decode()(
                        self.params, self.cache,
                        self._get_decode_input()(self._feed),
                        jnp.asarray(pos))
                    nxt.copy_to_host_async()
                    self._feed = nxt
                    self.pos[live] += 1
                    self.decodes_ahead += ahead is not None
                    self._ahead = (nxt, [(s, self.active[s]) for s in live])
            if ahead is not None:
                with TraceAnnotation("serve.decode_wait"):
                    picked = np.asarray(ahead[0])
            firsts = []
            if prefills:
                with TraceAnnotation("serve.prefill_wait"):
                    firsts = [np.asarray(first) for first, _ in prefills]
            if ahead is None and not prefills:
                return len(live)
            with TraceAnnotation("serve.bookkeeping"):
                if ahead is not None:
                    for s, req in ahead[1]:
                        tok = int(picked[s])
                        if not req.done:
                            # context length this token was decoded at
                            # (traffic weighting)
                            self.telemetry.observe(
                                self.site,
                                scale=len(req.prompt) + len(req.tokens),
                                tokens=1, kind="decode", bucket=req.bucket)
                        self._take(req, tok, s)
                for first, (_, rows) in zip(firsts, prefills):
                    for r, (s, req) in enumerate(rows):
                        self.telemetry.observe(
                            self.site, scale=len(req.prompt),
                            tokens=len(req.prompt), kind="prefill",
                            bucket=req.bucket)
                        self._take(req, int(first[r]), s)
            return (sum(len(rows) for _, rows in prefills) + len(live)
                    + (len(ahead[1]) if ahead is not None else 0))

    def run(self, max_steps: int = 1000) -> List[Request]:
        """Drive steps until the queue, the slots and the decode in flight
        are all drained (a step that only admits-and-finishes-at-prefill
        keeps going while the queue has work)."""
        for _ in range(max_steps):
            if not self.queue and self._ahead is None and all(
                    a is None for a in self.active):
                break
            self.step()
        return self.finished

