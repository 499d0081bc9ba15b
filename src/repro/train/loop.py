"""Surrogate training loop: store/loader-driven epochs, prefetch overlap,
device-resident fused decode, bit-exact checkpoint/restart.

The data source is anything implementing the ``ArrayStore`` protocol (raw
in-memory fields, ``CompressedArrayStore`` online per-batch decompression --
the paper's workflow 2 -- a ``ShardedCompressedStore``, or a
``DeviceResidentCompressedStore``), or a legacy ``idx -> batch`` callable.
The ``BatchSource`` seam (repro.train.source) picks the backend per store:

  * host-streaming: batches are ordered by a ``ShardedLoader`` (or a
    ``ShardAwareLoader`` matched to a sharded store's layout) and fetched on
    a ``PrefetchLoader`` worker thread so host-side read + decode overlaps
    the jitted train step;
  * device-resident: the compressed payload already lives in device memory,
    so each step ships only the (B,) index vector and gather + decode +
    model update compile into ONE fused jitted step -- zero host bytes per
    batch (``prefetch`` is ignored; there is nothing left to overlap).

Exact-resume guarantee (both backends): every epoch's permutation is derived
from ``(seed, epoch)`` alone, and the loader state (epoch, step_in_epoch,
seed) is written into each checkpoint manifest.  A run killed mid-epoch and
restarted therefore consumes the exact batches, in the exact order, at the
exact global steps an uninterrupted run would have -- final params are
bit-identical, and the resumed call's loss history matches the fresh run's
post-resume entries bit-for-bit (asserted in tests/test_resume.py).  This is
the precondition for the paper's §III variability bands: restart noise would
otherwise pollute the run-to-run spread that serves as the compression
yardstick.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import jaxprof
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
# Re-exported building blocks (historical import location; the
# implementations live in repro.train.source alongside the BatchSource seam).
from repro.train.source import (batch_stream, make_batch_source,
                                make_fused_step, make_getter, make_loader)
from repro.data.loader import ShardedLoader
from repro.models.surrogate import SurrogateConfig, apply_surrogate, init_surrogate, l1_loss
from repro.train import checkpoint as ckpt
from repro.train.optimizer import AdamConfig, adam_init, adam_update


@dataclasses.dataclass
class TrainConfig:
    epochs: int = 40
    batch_size: int = 64
    lr: float = 1e-4
    seed: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every_steps: int = 200
    ckpt_keep: int = 3
    lossy_ckpt_bits: Optional[int] = None
    # any registered Codec instance (repro.compression.get_codec(...)); takes
    # precedence over lossy_ckpt_bits.  A fixed-accuracy codec with no
    # default tolerance triggers per-leaf certification at each save: the
    # tolerance comes from Algorithm 1 run on the parameter tensors with the
    # optimizer's own per-step displacement as the error bound.
    ckpt_codec: Optional[object] = None
    log_every: int = 50
    prefetch: int = 2               # queue depth; 0 = synchronous fetch
    max_steps: Optional[int] = None  # simulated preemption: stop without a final save


@partial(jax.jit, static_argnames=("cfg", "opt_cfg"))
def _train_step(params, opt_state, cond, target, cfg: SurrogateConfig,
                opt_cfg: AdamConfig):
    loss, grads = jax.value_and_grad(l1_loss)(params, cfg, cond, target)
    params, opt_state = adam_update(grads, opt_state, params, opt_cfg)
    return params, opt_state, loss


def _needs_certify(train_cfg: "TrainConfig") -> bool:
    codec = train_cfg.ckpt_codec
    return (codec is not None
            and getattr(codec, "tolerance", 0) is None
            and codec.name.startswith("fixed_accuracy"))


def _save(train_cfg: "TrainConfig", step: int, params, opt_state,
          loader_state: dict, params_prev=None) -> None:
    codec = train_cfg.ckpt_codec
    lossy_bits = None if codec is not None else train_cfg.lossy_ckpt_bits
    tolerances = None
    if _needs_certify(train_cfg) and params_prev is not None:
        tolerances = {"params": ckpt.certify_param_tolerances(
            params_prev, params)}
    ckpt.save_checkpoint(
        train_cfg.ckpt_dir, step, {"params": params, "opt": opt_state},
        extra={"loader": dict(loader_state),
               "epoch": loader_state["epoch"],
               "seed": loader_state["seed"]},
        lossy_bits=lossy_bits, codec=codec, tolerances=tolerances,
        keep=train_cfg.ckpt_keep)


def train_surrogate(model_cfg: SurrogateConfig, train_cfg: TrainConfig,
                    conditions: np.ndarray,
                    data: Union[Callable, object],
                    num_samples: Optional[int] = None, params=None,
                    hooks=None, loader: Optional[ShardedLoader] = None,
                    target_transform: Optional[Callable] = None):
    """Train; returns (params, loss_history).

    ``data`` is the compression seam: an ArrayStore (``get_batch(idx)`` --
    raw memmap, online ZFP decode, or a ``DeviceResidentCompressedStore``
    whose gather + decode fuse into the jitted step), a produced-dataset
    path from ``repro.datagen.produce`` (resolved to its
    ``ShardedCompressedStore``; produced stores are channels-first, so pass
    ``target_transform=channels_last`` and conditions from
    ``repro.datagen.scenario_conditions``), or a legacy
    ``idx -> (B, H, W, F)`` callable (then ``num_samples`` is required).
    ``target_transform`` post-processes fetched batches (e.g. channels-first
    stores feeding the channels-last model); it must be jit-traceable for
    device-resident stores.  ``loader`` overrides the auto-built one -- pass
    a ``ShardAwareLoader`` with host_id/num_hosts for multi-host training.
    """
    if isinstance(data, str):
        from repro.datagen import resolve_store
        data = resolve_store(data)
    source = make_batch_source(data, conditions, target_transform,
                               num_samples)
    opt_cfg = AdamConfig(lr=train_cfg.lr)
    key = jax.random.PRNGKey(train_cfg.seed)
    if params is None:
        params = init_surrogate(key, model_cfg)
    opt_state = adam_init(params, opt_cfg)
    if loader is None:
        loader = make_loader(data, num_samples, train_cfg.batch_size,
                             train_cfg.seed)

    step = 0
    if train_cfg.ckpt_dir:
        latest = ckpt.latest_checkpoint(train_cfg.ckpt_dir)
        if latest:
            state, meta = ckpt.restore_checkpoint(
                latest, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            step = meta["step"]
            lstate = meta["extra"].get("loader")
            if lstate is None:          # pre-loader manifest: epoch granularity
                lstate = {"epoch": meta["extra"].get("epoch", 0),
                          "step_in_epoch": 0, "seed": loader.seed}
            loader.restore(lstate)

    if train_cfg.max_steps is not None and step >= train_cfg.max_steps:
        return params, []               # already at the preemption point

    device_path = source.kind == "device"
    if device_path:
        # the fused step consumes raw indices; decode happens in-jit against
        # the resident payload, so there is no host work to prefetch
        fused_step = make_fused_step(source, model_cfg, opt_cfg)
        prefetch = 0
    else:
        prefetch = train_cfg.prefetch

    # ``last_state`` is the loader position to store in the next checkpoint.
    # With prefetch the live loader runs ahead of consumption, so each batch
    # carries the state snapshot taken when it was drawn.
    last_state = dict(loader.state())

    # certified lossy checkpoints need the pre-step params at save time (the
    # per-step displacement is the Algorithm-1 error bound)
    track_prev = bool(train_cfg.ckpt_dir) and _needs_certify(train_cfg)
    params_prev = None

    # -- telemetry: compile vs steady-state split, recompile watch ----------
    # The first step of a run pays jit compilation; folding it into the
    # per-step rate skews every log_every-window throughput number.
    # ``train.compile_seconds`` is reported once; the steady-state
    # counters/histogram and the per-window events exclude it.  A step call
    # returns once the step is enqueued, so steps are timed by the window:
    # the wall time from one log boundary to the next, each ending in the
    # ``float(loss)`` read that waits for the window's last step (and the
    # end of the run in one wait for its trailing steps).
    from repro.train import source as source_mod
    reg = obs_metrics.get_registry()
    watcher = jaxprof.get_watcher()
    watcher.watch("train.fused_step" if device_path else "train.step",
                  source_mod._fused_step if device_path else _train_step)
    step_hist = reg.histogram("train.step_seconds")
    first_in_run = True
    steady_s = 0.0
    win_steps, win_t0 = 0, 0.0
    start_step = step

    def close_window() -> None:
        """Charge the steps since the last boundary, now complete."""
        nonlocal steady_s, win_steps, win_t0
        now = time.perf_counter()
        if win_steps:
            dur = now - win_t0
            for _ in range(win_steps):
                step_hist.observe(dur / win_steps)
            steady_s += dur
            obs_trace.instant("train.window", cat="train", step=step,
                              steps=win_steps, seconds=dur,
                              steps_per_s=win_steps / max(dur, 1e-9))
        win_steps, win_t0 = 0, now

    stream = batch_stream(loader, source.fetch, train_cfg.epochs, prefetch)
    losses = []
    saved_step = -1
    preempted = False
    try:
        t_iter = time.perf_counter()
        for lstate, item in stream:
            # wait-for-batch time: ~0 when the prefetch worker keeps up, the
            # host gather/decode stall otherwise (decode split per store is
            # in its IoStats)
            reg.counter("train.fetch_wait_seconds").add(
                time.perf_counter() - t_iter)
            if track_prev:
                params_prev = params
            t0s = time.perf_counter()
            if device_path:
                params, opt_state, loss = fused_step(params, opt_state, item)
            else:
                cond, target = item
                params, opt_state, loss = _train_step(
                    params, opt_state, cond, target, model_cfg, opt_cfg)
            step += 1
            if first_in_run:
                first_in_run = False
                jax.block_until_ready(loss)
                compile_s = time.perf_counter() - t0s
                reg.gauge("train.compile_seconds").set(compile_s)
                obs_trace.instant("train.compile", cat="train", step=step,
                                  seconds=compile_s)
                watcher.rebase()        # first-step compiles are expected
                close_window()          # steady windows start here
            else:
                win_steps += 1
            last_state = lstate
            if step % train_cfg.log_every == 0:
                losses.append((step, float(loss)))
                close_window()          # steady-state only: compile excluded
            if hooks:
                for h in hooks:
                    h(step, params, float(loss))
            if (train_cfg.ckpt_dir and step % train_cfg.ckpt_every_steps == 0):
                with obs_trace.span("train.checkpoint", cat="train",
                                    step=step):
                    _save(train_cfg, step, params, opt_state, last_state,
                          params_prev)
                saved_step = step
            if train_cfg.max_steps is not None and step >= train_cfg.max_steps:
                preempted = True
                break
            t_iter = time.perf_counter()
        if win_steps:
            jax.block_until_ready(loss)
            close_window()
    finally:
        stream.close()
        reg.counter("train.steps").add(step - start_step)
        reg.counter("train.steady_seconds").add(steady_s)
        watcher.check()     # flags (event + counter) steady-state recompiles
    if preempted:
        return params, losses   # no final save
    if train_cfg.ckpt_dir and step != saved_step:
        _save(train_cfg, step, params, opt_state, last_state, params_prev)
    return params, losses


def predict_fields(params, model_cfg: SurrogateConfig, conditions,
                   batch: int = 256) -> np.ndarray:
    outs = []
    conditions = np.asarray(conditions)
    fn = jax.jit(lambda p, c: apply_surrogate(p, model_cfg, c))
    for i in range(0, len(conditions), batch):
        outs.append(np.asarray(fn(params, jnp.asarray(conditions[i:i + batch]))))
    return np.concatenate(outs)
