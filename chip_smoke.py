#!/usr/bin/env python3
"""Smoke run of the compressed-training path on one TPU chip.

Drives the paper's main path once, in one process, through the entry points
a user calls, at the width of the Fig. 1 surrogate (``SurrogateConfig()``:
96x32 grid, 6 fields, 256 base channels).  Everything is made from
``--seed``; no cached study is read.

  1. simulate -- ``generate_ensemble(RT_SPEC, 8, seed)``: 8 members x 51
     snapshots of 96x32x6 fields, integrated on the chip;
  2. encode   -- normalise, pick per-sample tolerances with Algorithm 1
     (``find_tolerance_batch``), encode with the fixed-accuracy codec's
     Pallas kernel, and require payload/emax/nplanes bit-identical to the
     jnp encoder on the chip and on the host CPU backend;
  3. train    -- ``train_surrogate`` from a ``DeviceResidentCompressedStore``
     for 20 steps of batch 64 on the fused gather -> decode -> update step:
     finite losses, no recompile after the first step, the decode kernel in
     the lowered step, and one decoded batch bit-identical to the jnp decoder;
  4. serve    -- ``SurrogateServeEngine`` over 2 stacked members answers 16
     queries of mixed rollout length with finite ``(T, 96, 32, 6)`` mean and
     band width.

Each phase prints one JSON line: ``compile_s`` is its first call, which
pays compilation, and ``steady_s`` a repeat of the same work.  A smoke run
is no benchmark: these times say the path runs, not how fast.  The last
line is ``{"ok": true, "device": {...}}`` as JAX reports the device.  The
script exits non-zero, without that line, when JAX finds no TPU or any
check fails.

Run:  python chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the encode check compares against the host CPU backend, so keep it
# available next to whatever platform list the environment names
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from repro.compression import CompressedField, get_codec   # noqa: E402
from repro.core import find_tolerance_batch              # noqa: E402
from repro.core.ensemble import init_ensemble            # noqa: E402
from repro.data import DeviceResidentCompressedStore, channels_last  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.models.surrogate import (FieldNormalizer, SurrogateConfig,  # noqa: E402
                                    make_conditions)
from repro.obs import metrics as obs_metrics             # noqa: E402
from repro.serving import SurrogateServeEngine           # noqa: E402
from repro.serving.loadgen import surrogate_workload     # noqa: E402
from repro.sim import RT_SPEC, generate_ensemble         # noqa: E402
from repro.train import source                           # noqa: E402
from repro.train.loop import TrainConfig, train_surrogate  # noqa: E402
from repro.train.optimizer import AdamConfig, adam_init  # noqa: E402

MEMBERS = 8              # simulations in the ensemble
BATCH = 64               # training batch (samples)
STEPS = 20               # training steps
QUERIES = 16             # served queries per run
SERVE_MEMBERS = 2        # stacked surrogates behind the engine
MODEL_L1 = 0.02          # stand-in model L1 error (normalised) for Algorithm 1


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def timed(fn):
    """(result, seconds) of ``fn()``, waiting for every device result."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def mismatched_samples(a, b) -> int:
    """Samples (leading axis) in which ``a`` and ``b`` differ in any bit."""
    a, b = np.asarray(a), np.asarray(b)
    check(a.shape == b.shape, f"shape {a.shape} != {b.shape}")
    return int(np.sum(np.any((a != b).reshape(a.shape[0], -1), axis=1)))


def simulate(seed: int, spec, members: int):
    _, first_s = timed(lambda: generate_ensemble(spec, 1, seed))
    (pvec, fields), steady_s = timed(
        lambda: generate_ensemble(spec, members, seed))
    check(fields.shape == (members, spec.nsnaps, spec.ny, spec.nx, 6),
          f"ensemble shape {fields.shape}")
    check(bool(np.isfinite(fields).all()), "non-finite simulation output")
    report("simulate", compile_s=first_s, steady_s=steady_s,
           members=members, shape=list(fields.shape))
    return pvec, fields


def encode(fields: np.ndarray):
    nf = FieldNormalizer.fit(fields).normalize(
        jnp.asarray(fields.reshape(-1, *fields.shape[2:])))
    xs = jnp.transpose(nf, (0, 3, 1, 2))         # channels-first samples
    es = np.full((xs.shape[0],), MODEL_L1, np.float32)
    search, search_first_s = timed(lambda: find_tolerance_batch(xs, es))
    _, search_s = timed(lambda: find_tolerance_batch(xs, es))
    tols = jnp.asarray(search.tolerance, jnp.float32)
    check(bool(np.all(np.isfinite(search.tolerance))
               & np.all(search.tolerance > 0)), "bad tolerances")

    pallas = get_codec("fixed_accuracy")
    reference = get_codec("fixed_accuracy", backend="jnp")
    cf, first_s = timed(lambda: pallas.encode_batch(xs, tols))
    cf, steady_s = timed(lambda: pallas.encode_batch(xs, tols))
    ref_chip = jax.block_until_ready(reference.encode_batch(xs, tols))
    cpu = jax.devices("cpu")[0]
    ref_host = jax.block_until_ready(reference.encode_batch(
        jax.device_put(xs, cpu), jax.device_put(tols, cpu)))
    check(ref_host.payload.devices() == {cpu}, "host encode left the CPU")

    identity = {}
    for name, ref_cf in (("chip_jnp", ref_chip), ("host_cpu_jnp", ref_host)):
        for leaf in ("payload", "emax", "nplanes"):
            identity[f"{name}.{leaf}"] = mismatched_samples(
                getattr(cf, leaf), getattr(ref_cf, leaf))
    err = jnp.max(jnp.abs(reference.decode_batch(cf) - xs), axis=(1, 2, 3))
    bound_ok = bool(jnp.all(err <= tols))
    ratio = float(np.prod(xs.shape) * 4
                  / np.sum(np.asarray(pallas.nbytes(cf))))
    report("encode", compile_s=first_s, steady_s=steady_s,
           search_compile_s=search_first_s, search_steady_s=search_s,
           samples=int(xs.shape[0]), blocks=int(np.prod(cf.emax.shape)),
           ratio=ratio, mismatched_samples=identity, error_bound_held=bound_ok)
    check(not any(identity.values()),
          f"Pallas encode differs from the jnp encoder: {identity}")
    check(bound_ok, "decoded error exceeds the tolerance")
    return cf, tols


def train(seed: int, pvec, nsnaps: int, cf, tols, cfg: SurrogateConfig,
          batch: int, steps: int):
    store = DeviceResidentCompressedStore.from_compressed(cf, tols)
    cond = make_conditions(pvec, nsnaps)
    tc = TrainConfig(epochs=None, batch_size=batch, lr=1e-4, seed=seed,
                     log_every=1, prefetch=0, max_steps=steps)
    reg = obs_metrics.get_registry()
    reg.reset()
    params, losses = train_surrogate(cfg, tc, cond, store,
                                     target_transform=channels_last)
    snap = reg.snapshot()
    recompiles = int(snap.get("jax.recompiles", 0))
    loss_values = [v for _, v in losses]
    check(len(loss_values) == steps, f"{len(loss_values)} of {steps} steps")
    check(bool(np.all(np.isfinite(loss_values))), f"losses {loss_values}")
    check(recompiles == 0, f"{recompiles} recompiles after the first step")

    # the program the loop ran carries the decode kernel, not the oracle
    opt_cfg = AdamConfig(lr=tc.lr)
    src = source.make_batch_source(store, cond, channels_last)
    idx = jnp.arange(batch, dtype=jnp.int32)
    lowered = source._fused_step.lower(
        params, adam_init(params, opt_cfg), idx, store.payload, store.emax,
        store.nplanes, src.conditions, cfg=cfg, opt_cfg=opt_cfg,
        padded_shape=store._padded_shape, shape=store.shape,
        transform=src.transform)
    kernel_in_step = "tpu_custom_call" in lowered.as_text()

    # one batch through the fused path's decode and through the jnp decoder
    rng = np.random.default_rng(seed)
    pick = np.sort(rng.choice(store.num_samples, batch, replace=False))
    fused = store.get_batch(pick)
    ref_cf = CompressedField(store.payload[pick], store.emax[pick],
                             store.nplanes[pick], store.shape,
                             store._padded_shape)
    oracle = get_codec("fixed_accuracy", backend="jnp").decode_batch(ref_cf)
    decode_mismatch = mismatched_samples(fused, oracle)
    steady = float(snap["train.steady_seconds"])
    report("train", compile_s=float(snap["train.compile_seconds"]),
           steady_s=steady / max(steps - 1, 1), steps=steps, batch=batch,
           first_loss=loss_values[0], last_loss=loss_values[-1],
           recompiles=recompiles, kernel_in_fused_step=kernel_in_step,
           resident_bytes=store.resident_bytes,
           decode_mismatched_samples=decode_mismatch)
    check(kernel_in_step, "no tpu_custom_call in the fused train step")
    check(decode_mismatch == 0,
          f"fused decode differs from the jnp decoder in {decode_mismatch}")
    return params


def serve(seed: int, params, cfg: SurrogateConfig, queries: int):
    fresh = init_ensemble(cfg, [seed + m for m in range(1, SERVE_MEMBERS)])
    members = jax.tree.map(lambda p, f: jnp.concatenate([p[None], f]),
                           params, fresh)
    engine = SurrogateServeEngine(members, cfg, batch_slots=8)
    reg = obs_metrics.get_registry()
    reg.reset()
    runs = []
    for r in range(2):                           # compile run, steady run
        qs = surrogate_workload(cfg.cond_dim - 1, queries, seed=seed + r)
        done, secs = timed(lambda: engine.run(qs))
        check(len(done) == queries, f"{len(done)} of {queries} answered")
        for q in done:
            want = (q.steps, cfg.height, cfg.width, cfg.fields)
            check(q.mean.shape == want and q.width.shape == want,
                  f"query shapes {q.mean.shape}, {q.width.shape} != {want}")
            check(bool(np.isfinite(q.mean).all() & np.isfinite(q.width).all()),
                  "non-finite served fields")
        runs.append((done, secs))
    recompiles = int(reg.snapshot().get("jax.recompiles", 0))
    check(recompiles == 0, f"{recompiles} serving recompiles")
    report("serve", compile_s=runs[0][1], steady_s=runs[1][1],
           queries=queries, members=SERVE_MEMBERS,
           rollout_lengths=sorted({q.steps for q in runs[1][0]}),
           recompiles=recompiles)


def smoke(seed: int, spec=RT_SPEC, cfg: SurrogateConfig = SurrogateConfig(),
          members: int = MEMBERS, batch: int = BATCH, steps: int = STEPS,
          queries: int = QUERIES) -> None:
    """Run the four phases; raises on the first failed check."""
    pvec, fields = simulate(seed, spec, members)
    cf, tols = encode(fields)
    params = train(seed, pvec, fields.shape[1], cf, tols, cfg, batch, steps)
    serve(seed, params, cfg, queries)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r}")
    configure_compile_cache()
    smoke(args.seed)
    stats = devices[0].memory_stats() or {}
    report("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
