"""Continuous-batching LM serving engine.

Production shape of the loop on the jitted prefill/serve_step pair from
``repro.models.lm``, rebuilt on the shared ``SlotScheduler``:

  * **continuous batching** (``run``): a fixed slot table decodes every
    step at full width while each slot sits at its OWN depth (vector
    ``pos`` in ``serve_step``); the moment a request delivers its last
    token the slot is refilled from the queue mid-flight -- no lockstep
    ``steps = max(max_new_tokens)`` drain.  Each admitted request is
    prefilled alone, right-padded to the smallest of a fixed set of length
    **buckets** that holds it (pads masked out of attention and SSM state
    by ``lm_prefill(prompt_lens=...)``), and its cache scattered into the
    live batch cache in the same program.  So prefill runs one program per
    bucket, the decode step one program, and ``warmup`` compiles them all:
    a run compiles nothing whatever its mix of prompt lengths.
  * **lockstep baseline** (``run_lockstep``): the historical chunked
    generation loop, kept as the benchmark baseline: a chunk's prompts are
    RIGHT-padded to one bucket with per-slot ``prompt_lens`` and per-slot
    decode positions.

Correctness contracts held by both paths (regression-tested):
  * a request's output is identical whether served alone or batched with
    longer prompts / longer generations;
  * every REAL request is returned, including ``max_new_tokens=0`` (empty
    output) -- idle slots are marked by the scheduler's explicit occupancy,
    never by a sentinel token count;
  * ``stats`` separates ``prefill_seconds`` from ``decode_seconds`` and
    counts delivered tokens only.

Each pass of ``run``'s loop is split into phases that partition its wall
time: ``no_work`` (no slot active: sleep until the next arrival),
``prefill`` (the admitted requests' prefills, cache inserts and first
tokens), ``decode`` (the decode step's enqueue and its wait), ``fetch``
(the argmax readback) and ``collect`` (admission, appends, finished
requests, refill).  Each is an ``lm_serve.<phase>`` span and an
``lm_serve.<phase>_seconds`` counter (``serving/phases.py``); counters
``lm_serve.prefill_tokens``, ``.prefill_pad_tokens`` and ``.decode_tokens``
count real prompt tokens, bucket padding and tokens delivered by decode
steps.

The jitted step functions live at MODULE level, keyed on the static
``ArchConfig`` (a frozen dataclass), so every engine instance -- and every
test constructing one -- shares one compile cache, the ``_fused_step``
idiom from ``train/source.py``.  The prefill (which scatters its prompt's
cache into the live one) and the decode step donate the live cache, so it
is updated in place: the decode step carries it through its layer scan and
writes only each slot's new K/V row and each layer's SSM/conv state.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import lm
from repro.obs import jaxprof
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving.phases import PhaseClock
from repro.serving.scheduler import SlotScheduler

PHASES = ("no_work", "prefill", "decode", "fetch", "collect")


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (P,) int32
    max_new_tokens: int = 16
    arrival: float = 0.0        # open-loop arrival time (s, run-relative)
    output: Optional[np.ndarray] = None
    latency: Optional[float] = None     # completion - arrival (s)
    # ``run`` keeps in ``logits`` the float32 logits rows the first
    # ``keep_logits`` tokens were taken from: the prefill's last row, then
    # one row a decode step
    keep_logits: int = 0
    logits: Optional[list] = None


def default_buckets(max_seq: int) -> tuple:
    """Powers of two from 16 below ``max_seq``, then ``max_seq``."""
    out, b = [], 16
    while b < max_seq:
        out.append(b)
        b *= 2
    return tuple(out) + (max_seq,)


# ---------------------------------------------------------------------------
# module-level compile-cached step functions (shared across engine instances)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "max_seq"),
         donate_argnames=("cache",))
def _prefill(params, cfg: ArchConfig, cache, tokens, prompt_lens, slots,
             max_seq: int):
    """Prefill a right-padded group of prompts (batch g) and scatter its
    cache into the live batch cache at slot indices ``slots`` (g,), leaf
    layout (L, B, ...).  Returns (last-token logits, greedy tokens, cache).
    One program, so the group's own cache is a temporary of it and never
    outlives it."""
    logits, new = lm.lm_prefill(params, cfg, {"tokens": tokens}, max_seq,
                                cache_dtype=jnp.float32,
                                prompt_lens=prompt_lens)
    cache = jax.tree_util.tree_map(
        lambda c, n: c.at[:, slots].set(n.astype(c.dtype)), cache, new)
    return logits, jnp.argmax(logits, -1), cache


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _decode_step(params, cfg: ArchConfig, cache, tokens, pos):
    return lm.serve_step(params, cfg, cache, tokens, pos)


@jax.jit
def _logits_row(logits, slot):
    """One slot's row of a decode step's logits, so that a request keeping
    its logits reads back its own row and not the whole batch's."""
    return logits[slot]


class ServeEngine:
    def __init__(self, params, cfg: ArchConfig, batch_slots: int = 4,
                 max_seq: int = 128,
                 prefill_buckets: Optional[Sequence[int]] = None):
        if cfg.encoder_layers:
            raise ValueError("encoder-decoder serving goes through the "
                             "decode dry-run, not ServeEngine")
        self.params, self.cfg = params, cfg
        self.batch, self.max_seq = batch_slots, max_seq
        self.buckets = tuple(sorted(set(
            prefill_buckets or default_buckets(max_seq))))
        if self.buckets[-1] > max_seq:
            raise ValueError(f"prefill bucket {self.buckets[-1]} exceeds "
                             f"max_seq={max_seq}")
        self.stats = {"tokens": 0, "prefill_tokens": 0, "seconds": 0.0,
                      "prefill_seconds": 0.0, "decode_seconds": 0.0,
                      "decode_steps": 0, "delivered_slot_steps": 0}
        self._t_run_start: Optional[float] = None   # perf stamp of run start

    # -- shared helpers -----------------------------------------------------

    def bucket(self, n: int) -> int:
        """The padded length a prompt of ``n`` tokens is prefilled at."""
        return next(b for b in self.buckets if b >= n)

    def _validate(self, requests: List[Request]) -> None:
        for r in requests:
            if len(r.prompt) + r.max_new_tokens > self.max_seq:
                raise ValueError(
                    f"prompt ({len(r.prompt)}) + max_new_tokens "
                    f"({r.max_new_tokens}) exceeds max_seq={self.max_seq}")
            if len(r.prompt) == 0:
                raise ValueError("empty prompt")
            if len(r.prompt) > self.buckets[-1]:
                raise ValueError(f"prompt ({len(r.prompt)}) exceeds the "
                                 f"largest prefill bucket {self.buckets[-1]}")

    def _account(self, prefill_s: float = 0.0, decode_s: float = 0.0) -> None:
        self.stats["prefill_seconds"] += prefill_s
        self.stats["decode_seconds"] += decode_s
        self.stats["seconds"] += prefill_s + decode_s

    def _new_cache(self):
        return lm.init_cache(self.cfg, self.batch, self.max_seq, jnp.float32)

    def warmup(self) -> None:
        """Serve one request of each bucket's length (room left for three
        tokens), half of them keeping their logits, so that every program
        ``run`` dispatches and every readback it makes has run, on the
        states it leaves too: a later run compiles nothing.
        Registers the prefill and decode programs with the recompile
        watcher, which ``run`` then checks."""
        self.run([Request(np.zeros(min(b, self.max_seq - 3), np.int32), 3,
                          keep_logits=3 if i % 2 else 0)
                  for i, b in enumerate(self.buckets)])
        watcher = jaxprof.get_watcher()
        for name, fn in (("serve.prefill", _prefill),
                         ("serve.decode_step", _decode_step)):
            watcher.watch(name, fn)

    def _finish(self, req: Request, tokens, now: float, done: list) -> None:
        req.output = np.asarray(tokens, np.int32)[: req.max_new_tokens]
        req.latency = now - req.arrival
        self.stats["tokens"] += int(req.output.shape[0])
        done.append(req)
        reg = obs_metrics.get_registry()
        reg.counter("serve.requests").add(1)
        reg.histogram("serve.request_latency_seconds").observe(req.latency)
        seated = getattr(req, "_seated", None)
        if seated is not None:
            reg.histogram("serve.queue_wait_seconds").observe(
                seated - req.arrival)
        tracer = obs_trace.get_tracer()
        if tracer is not None and self._t_run_start is not None:
            # request lifetime span on the tracer timeline: arrival (queued)
            # through completion; queue wait separates scheduling delay from
            # prefill+decode service time
            tracer.complete(
                "serve.request", tracer.rel(self._t_run_start + req.arrival),
                req.latency, cat="serve", tokens=int(req.output.shape[0]),
                prompt=int(len(req.prompt)),
                queue_wait_s=None if seated is None
                else round(seated - req.arrival, 6))

    # -- continuous batching ------------------------------------------------

    def run(self, requests: List[Request], greedy: bool = True):
        """Serve with continuous batching; returns every request, completed,
        in completion order.  Requests with ``arrival > 0`` queue until the
        run clock (seconds since ``run`` started) passes their arrival.
        After ``warmup``, any program the run compiles is flagged by the
        recompile watcher (``jax.recompiles``)."""
        if not greedy:
            raise NotImplementedError("ServeEngine decodes greedily")
        self._validate(requests)
        sched = SlotScheduler(self.batch)
        sched.submit_all(requests)
        b = self.batch
        cache = self._new_cache()
        pos = np.zeros(b, np.int32)          # per-slot decode depth
        cur = np.zeros(b, np.int32)          # per-slot last emitted token
        outs: List[list] = [[] for _ in range(b)]
        remaining = np.zeros(b, np.int64)
        done: List[Request] = []
        t_start = time.perf_counter()
        clock = lambda: time.perf_counter() - t_start
        self._t_run_start = t_start
        phase = PhaseClock("lm_serve", PHASES, t_start)
        reg = obs_metrics.get_registry()
        occ_hist = reg.histogram("serve.slot_occupancy")
        counts = {k: reg.counter("lm_serve." + k) for k in
                  ("prefill_tokens", "prefill_pad_tokens", "decode_tokens")}
        tracer = obs_trace.get_tracer()

        while not sched.done:
            with phase("collect"):
                now = clock()
                # admit until no free slot / no ripe request; zero-token
                # requests complete immediately (returned with an empty
                # output) and their slot is refilled in the same round
                seated = []
                while True:
                    adm = sched.admit(now)
                    if not adm:
                        break
                    recycled = False
                    for slot, req in adm:
                        req._seated = now
                        if req.max_new_tokens <= 0:
                            self._finish(req, [], clock(), done)
                            sched.complete(slot)
                            recycled = True
                        else:
                            seated.append((slot, req))
                    if not recycled:
                        break

            if seated:
                t0 = time.perf_counter()
                with phase("prefill"):
                    firsts = []
                    for slot, req in seated:     # alone, at its bucket
                        plen = len(req.prompt)
                        toks = np.zeros((1, self.bucket(plen)), np.int32)
                        toks[0, :plen] = req.prompt
                        logits, first, cache = _prefill(
                            self.params, self.cfg, cache, jnp.asarray(toks),
                            jnp.asarray([plen], jnp.int32),
                            jnp.asarray([slot], jnp.int32), self.max_seq)
                        req.logits = [] if req.keep_logits > 0 else None
                        firsts.append((first, logits
                                       if req.logits is not None else None))
                        counts["prefill_tokens"].add(plen)
                        counts["prefill_pad_tokens"].add(
                            self.bucket(plen) - plen)
                        self.stats["prefill_tokens"] += plen
                    firsts = jax.device_get(firsts)
                self._account(prefill_s=time.perf_counter() - t0)
                with phase("collect"):
                    for (slot, req), (first, row) in zip(seated, firsts):
                        if row is not None:
                            req.logits.append(row[0])
                        outs[slot] = [int(first[0])]
                        pos[slot], cur[slot] = len(req.prompt), first[0]
                        remaining[slot] = req.max_new_tokens - 1
                        if remaining[slot] == 0:     # max_new_tokens == 1
                            self._finish(req, outs[slot], clock(), done)
                            sched.complete(slot)

            active = sched.active_items()
            if not active:
                with phase("no_work"):
                    nxt_arr = sched.next_arrival()
                    if nxt_arr is not None and nxt_arr > clock():
                        time.sleep(min(nxt_arr - clock(), 0.005))
                continue

            # ONE full-width decode step; every slot advances at its own pos
            t0 = time.perf_counter()
            kept = [slot for slot, req in active if req.logits is not None
                    and len(req.logits) < req.keep_logits]
            with phase("decode"):
                logits, cache = _decode_step(self.params, self.cfg, cache,
                                             jnp.asarray(cur),
                                             jnp.asarray(pos))
                nxt = jnp.argmax(logits, -1)
                jax.block_until_ready(nxt)
            with phase("fetch"):
                nxt, rows = jax.device_get((nxt, [
                    _logits_row(logits, jnp.int32(slot)) for slot in kept]))
                del logits
            self._account(decode_s=time.perf_counter() - t0)
            with phase("collect"):
                self.stats["decode_steps"] += 1
                self.stats["delivered_slot_steps"] += len(active)
                counts["decode_tokens"].add(len(active))
                occ_hist.observe(len(active) / b)
                if tracer is not None:
                    tracer.counter("serve.slots", active=len(active), total=b)
                now = clock()
                cur = np.array(nxt, np.int32)
                for slot, row in zip(kept, rows):
                    sched.occupant(slot).logits.append(row)
                for slot, req in active:
                    pos[slot] += 1
                    outs[slot].append(int(cur[slot]))
                    remaining[slot] -= 1
                    if remaining[slot] == 0:
                        self._finish(req, outs[slot], now, done)
                        sched.complete(slot)
        jaxprof.get_watcher().check()   # flags programs compiled in the run
        return done

    # -- lockstep baseline --------------------------------------------------

    def run_lockstep(self, requests: List[Request], greedy: bool = True):
        """The historical chunked loop (benchmark baseline): slot batches of
        ``self.batch`` requests, each chunk right-padded to one bucket,
        prefilled in one dispatch and decoded for ``max(max_new_tokens)`` lockstep steps.
        Freed slots idle until the whole chunk drains -- that wasted work is
        exactly what ``run`` recycles.  Outputs match ``run``."""
        if not greedy:
            raise NotImplementedError("ServeEngine decodes greedily")
        self._validate(requests)
        done: List[Request] = []
        t_start = time.perf_counter()
        self._t_run_start = t_start
        for i in range(0, len(requests), self.batch):
            chunk = requests[i:i + self.batch]
            nreal = len(chunk)
            plen = self.bucket(max(len(r.prompt) for r in chunk))
            toks = np.zeros((self.batch, plen), np.int32)
            lens = np.zeros(self.batch, np.int32)
            for j in range(self.batch):
                r = chunk[min(j, nreal - 1)]     # pad SLOTS clone a real row;
                toks[j, :len(r.prompt)] = r.prompt   # active flags mark them
                lens[j] = len(r.prompt)
            active = [j for j in range(nreal) if chunk[j].max_new_tokens > 0]

            t0 = time.perf_counter()
            _, cur, cache = _prefill(
                self.params, self.cfg, self._new_cache(), jnp.asarray(toks),
                jnp.asarray(lens), jnp.arange(self.batch), self.max_seq)
            cur = np.asarray(cur, np.int32)
            self._account(prefill_s=time.perf_counter() - t0)
            self.stats["prefill_tokens"] += int(lens[:nreal].sum())

            outs = [[] for _ in range(self.batch)]
            for j in active:
                outs[j].append(int(cur[j]))
            pos = lens.copy()
            steps = max((chunk[j].max_new_tokens for j in active), default=0)
            t0 = time.perf_counter()
            for _ in range(max(steps - 1, 0)):
                logits, cache = _decode_step(
                    self.params, self.cfg, cache, jnp.asarray(cur),
                    jnp.asarray(np.minimum(pos, self.max_seq - 1)))
                cur = np.asarray(jnp.argmax(logits, -1), np.int32)
                pos += 1
                self.stats["decode_steps"] += 1
                for j in active:
                    if len(outs[j]) < chunk[j].max_new_tokens:
                        outs[j].append(int(cur[j]))
                        self.stats["delivered_slot_steps"] += 1
            self._account(decode_s=time.perf_counter() - t0)
            now = time.perf_counter() - t_start
            # EVERY real request is returned -- zero-token ones with an
            # empty output; padding slots are never requests at all
            for j, r in enumerate(chunk):
                self._finish(r, outs[j], now, done)
        return done

    # -- derived stats ------------------------------------------------------

    @property
    def tokens_per_second(self) -> float:
        """Delivered decode tokens per DECODE second (prefill excluded --
        the old accounting folded prefill wall-clock into this rate)."""
        return self.stats["tokens"] / max(self.stats["decode_seconds"], 1e-9)

    @property
    def prefill_tokens_per_second(self) -> float:
        return (self.stats["prefill_tokens"]
                / max(self.stats["prefill_seconds"], 1e-9))

    @property
    def slot_utilization(self) -> float:
        """Fraction of decode slot-steps that delivered a requested token."""
        total = self.stats["decode_steps"] * self.batch
        return self.stats["delivered_slot_steps"] / max(total, 1)
