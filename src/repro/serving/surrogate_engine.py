"""Surrogate serving: continuous batching over a device-resident model fleet.

The paper's deliverable is the *served* surrogate, and §III makes the
seed-ensemble variability band the trust signal -- so the band IS the
product: every query is answered by ALL N ensemble members in one vmapped
dispatch and returns the per-timestep member mean plus the +/-sigma band
width (``hi - lo`` of ``core.variability.VariabilityBand`` over members,
asserted consistent in tests).

A query is a conditioning->rollout: a simulation parameter vector plus the
normalized times to roll the surrogate over (``models.surrogate`` maps
``[params, t]`` to the six output fields).  The engine packs the CURRENT
timestep of every active slot into one ``(B, cond_dim)`` batch and runs the
stacked ``(M, ...)`` member params through a single jitted vmapped
``apply_surrogate`` -- the ``BatchSource``/module-level compile-cache
pattern from ``train/source.py``: the fleet step is a module-level jit
keyed on the static ``SurrogateConfig``, the stacked params stay device
resident across the whole serve loop, and only the tiny cond batch is
uploaded per step.

Continuous batching comes from the shared ``SlotScheduler``: rollouts of
mixed lengths retire independently and freed slots are refilled mid-flight,
vs the ``run_lockstep`` baseline that drains ``max(T)`` steps per chunk.

Each pass of ``run``'s loop is split into phases that partition its wall
time: ``no_work`` (no slot active: sleep until the next arrival),
``dispatch`` (cond upload and fleet-step enqueue), ``device_wait`` (until
the step's mean and band are ready), ``fetch`` (their device-to-host copy)
and ``collect`` (admission, cond rows, per-slot appends, finished rollouts'
stacking, slot refill).  Each is a ``surrogate_serve.<phase>`` span --
``dispatch``, ``device_wait`` and ``fetch`` inside one
``surrogate_serve.fleet_step`` -- and a cumulative
``surrogate_serve.<phase>_seconds`` counter in the global registry, always
on.  The device idles in every phase but ``device_wait``.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.models.surrogate import SurrogateConfig, apply_surrogate
from repro.obs import jaxprof
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving.phases import PhaseClock
from repro.serving.scheduler import SlotScheduler


@dataclasses.dataclass
class SurrogateQuery:
    params_vec: np.ndarray      # (PARAM_DIM,) simulation input parameters
    times: np.ndarray           # (T,) normalized rollout times in [0, 1]
    arrival: float = 0.0        # open-loop arrival time (s, run-relative)
    mean: Optional[np.ndarray] = None    # (T, H, W, F) member mean
    width: Optional[np.ndarray] = None   # (T, H, W, F) band width (hi - lo)
    latency: Optional[float] = None

    @property
    def steps(self) -> int:
        return int(np.asarray(self.times).shape[0])


PHASES = ("no_work", "dispatch", "device_wait", "fetch", "collect")


@partial(jax.jit, static_argnames=("cfg", "sigmas"))
def _fleet_step(member_params, cond, cfg: SurrogateConfig, sigmas: float):
    """ONE dispatch: every ensemble member predicts every slot's current
    condition.  member_params: stacked (M, ...) pytree; cond: (B, cond_dim).
    Returns (mean (B, H, W, F), band width = hi - lo = 2*sigmas*std)."""
    preds = jax.vmap(lambda p: apply_surrogate(p, cfg, cond))(member_params)
    mean = jnp.mean(preds, axis=0)
    width = 2.0 * sigmas * jnp.std(preds, axis=0)
    return mean, width


class SurrogateServeEngine:
    """Fixed-slot ensemble serving of a trained (or stacked) surrogate fleet.

    ``member_params``: a stacked pytree with leading member axis M -- e.g.
    ``core.ensemble.EnsembleResult.params`` straight from the vmapped
    trainer, or ``init_ensemble`` output.  Uploaded once; resident for the
    engine's lifetime.
    """

    def __init__(self, member_params, cfg: SurrogateConfig,
                 batch_slots: int = 8, sigmas: float = 2.0):
        self.members = jax.tree_util.tree_map(jnp.asarray, member_params)
        leaves = jax.tree_util.tree_leaves(self.members)
        if not leaves or leaves[0].ndim < 1:
            raise ValueError("member_params must be a stacked (M, ...) pytree")
        self.num_members = int(leaves[0].shape[0])
        self.cfg = cfg
        self.batch = batch_slots
        self.sigmas = float(sigmas)
        self.stats = {"queries": 0, "field_evals": 0, "steps": 0,
                      "seconds": 0.0}
        self._t_run_start: Optional[float] = None   # perf stamp of run start

    # -- internals ----------------------------------------------------------

    def _dispatch(self, cond_np: np.ndarray):
        return _fleet_step(self.members, jnp.asarray(cond_np), self.cfg,
                           self.sigmas)

    def _step(self, cond_np: np.ndarray):
        mean, width = self._dispatch(cond_np)
        return np.asarray(mean), np.asarray(width)

    def _finish(self, q: SurrogateQuery, means: list, widths: list,
                now: float, done: list) -> None:
        shape = (0, self.cfg.height, self.cfg.width, self.cfg.fields)
        q.mean = (np.stack(means) if means
                  else np.zeros(shape, np.float32))
        q.width = (np.stack(widths) if widths
                   else np.zeros(shape, np.float32))
        q.latency = now - q.arrival
        self.stats["queries"] += 1
        done.append(q)
        reg = obs_metrics.get_registry()
        reg.counter("surrogate_serve.queries").add(1)
        reg.histogram("surrogate_serve.query_latency_seconds").observe(
            q.latency)
        tracer = obs_trace.get_tracer()
        if tracer is not None and self._t_run_start is not None:
            seated = getattr(q, "_seated", None)
            tracer.complete(
                "surrogate_serve.query",
                tracer.rel(self._t_run_start + q.arrival), q.latency,
                cat="serve", steps=q.steps,
                queue_wait_s=None if seated is None
                else round(seated - q.arrival, 6))

    def _cond_row(self, q: SurrogateQuery, k: int) -> np.ndarray:
        return np.concatenate([np.asarray(q.params_vec, np.float32),
                               np.float32(q.times[k])[None]])

    # -- continuous batching ------------------------------------------------

    def run(self, queries: List[SurrogateQuery]):
        """Serve rollouts with mid-flight slot refill; returns every query,
        completed, in completion order."""
        sched = SlotScheduler(self.batch)
        sched.submit_all(queries)
        b = self.batch
        cond_dim = self.cfg.cond_dim
        cond = np.zeros((b, cond_dim), np.float32)
        step_idx = np.zeros(b, np.int64)
        means: List[list] = [[] for _ in range(b)]
        widths: List[list] = [[] for _ in range(b)]
        done: List[SurrogateQuery] = []
        t_start = time.perf_counter()
        clock = lambda: time.perf_counter() - t_start
        self._t_run_start = t_start
        phase = PhaseClock("surrogate_serve", PHASES, t_start)
        reg = obs_metrics.get_registry()
        occ_hist = reg.histogram("surrogate_serve.slot_occupancy")
        tracer = obs_trace.get_tracer()
        # fleet step shape is fixed (B, cond_dim): growth after the first
        # step's compile (rebased away below) is a genuine recompile
        watcher = jaxprof.get_watcher()
        watcher.watch("surrogate_serve.fleet_step", _fleet_step)
        first_step = True

        while not sched.done:
            with phase("collect"):
                now = clock()
                while True:
                    adm = sched.admit(now)
                    if not adm:
                        break
                    recycled = False
                    for slot, q in adm:
                        q._seated = now
                        if q.steps == 0:     # empty rollout: return as-is
                            self._finish(q, [], [], clock(), done)
                            sched.complete(slot)
                            recycled = True
                        else:
                            step_idx[slot] = 0
                            means[slot], widths[slot] = [], []
                            cond[slot] = self._cond_row(q, 0)
                    if not recycled:
                        break
                active = sched.active_items()

            if not active:
                with phase("no_work"):
                    nxt_arr = sched.next_arrival()
                    if nxt_arr is not None and nxt_arr > clock():
                        time.sleep(min(nxt_arr - clock(), 0.005))
                continue

            t0 = time.perf_counter()
            with obs_trace.span("surrogate_serve.fleet_step", cat="serve",
                                active=len(active), members=self.num_members):
                with phase("dispatch"):
                    mean, width = self._dispatch(cond)
                with phase("device_wait"):
                    jax.block_until_ready((mean, width))
                with phase("fetch"):
                    mean_b, width_b = np.asarray(mean), np.asarray(width)
                    # free the device buffers before the next step's
                    # outputs are allocated, as ``_step`` does
                    del mean, width
            self.stats["seconds"] += time.perf_counter() - t0
            self.stats["steps"] += 1
            self.stats["field_evals"] += len(active)
            with phase("collect"):
                occ_hist.observe(len(active) / b)
                if first_step:
                    first_step = False
                    watcher.rebase()        # first-step compile is expected
                if tracer is not None:
                    tracer.counter("surrogate_serve.slots",
                                   active=len(active), total=b)
                now = clock()
                for slot, q in active:
                    means[slot].append(mean_b[slot])
                    widths[slot].append(width_b[slot])
                    k = int(step_idx[slot]) + 1
                    if k >= q.steps:
                        self._finish(q, means[slot], widths[slot], now, done)
                        sched.complete(slot)
                    else:
                        step_idx[slot] = k
                        cond[slot] = self._cond_row(q, k)
        watcher.check()         # flags mid-run fleet-step recompiles
        return done

    # -- lockstep baseline --------------------------------------------------

    def run_lockstep(self, queries: List[SurrogateQuery]):
        """Chunked baseline: slot batches of ``self.batch`` queries, each
        chunk rolled for ``max(T)`` steps; short rollouts idle (their slot
        re-evaluates the last timestep and the result is dropped)."""
        done: List[SurrogateQuery] = []
        t_start = time.perf_counter()
        self._t_run_start = t_start
        for i in range(0, len(queries), self.batch):
            chunk = queries[i:i + self.batch]
            steps = max((q.steps for q in chunk), default=0)
            cond = np.zeros((self.batch, self.cfg.cond_dim), np.float32)
            acc = [([], []) for _ in chunk]
            for s in range(steps):
                for j, q in enumerate(chunk):
                    if q.steps:             # zero-step queries have no times
                        cond[j] = self._cond_row(q, min(s, q.steps - 1))
                t0 = time.perf_counter()
                mean_b, width_b = self._step(cond)
                self.stats["seconds"] += time.perf_counter() - t0
                self.stats["steps"] += 1
                for j, q in enumerate(chunk):
                    if s < q.steps:
                        acc[j][0].append(mean_b[j])
                        acc[j][1].append(width_b[j])
                        self.stats["field_evals"] += 1
            now = time.perf_counter() - t_start
            for j, q in enumerate(chunk):
                self._finish(q, acc[j][0], acc[j][1], now, done)
        return done

    # -- derived stats ------------------------------------------------------

    @property
    def queries_per_second(self) -> float:
        return self.stats["queries"] / max(self.stats["seconds"], 1e-9)

    @property
    def slot_utilization(self) -> float:
        total = self.stats["steps"] * self.batch
        return self.stats["field_evals"] / max(total, 1)
