"""Distributed LM training launcher.

Modes:
  --dry-run      lower + compile the selected (arch, shape) on the production
                 mesh (delegates to repro.launch.dryrun.run_cell)
  (default)      run real steps with the REDUCED config on the host devices
                 (CPU smoke / small TPU slice): synthetic tokens, Adam,
                 checkpoint/restart, optional compressed gradients

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b --steps 10
  PYTHONPATH=src python -m repro.launch.train --arch arctic-480b --shape train_4k --dry-run
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--trace-dir", default=None,
                    help="enable telemetry: write <run>.trace.json "
                         "(Perfetto-loadable) + <run>.events.jsonl here")
    ap.add_argument("--jax-profile", action="store_true",
                    help="also capture a jax.profiler trace under "
                         "TRACE_DIR/jaxprof (requires --trace-dir)")
    args = ap.parse_args()

    if args.dry_run:
        # dryrun module owns the 512-device env; exec it in a fresh process
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--cell", args.shape,
               "--mesh", "multi" if args.multi_pod else "single"]
        raise SystemExit(subprocess.call(cmd))

    import contextlib
    import os

    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.configs import reduced_config
    from repro.launch.compile_cache import configure_compile_cache
    from repro.models import lm
    from repro.obs import jaxprof
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    from repro.train import checkpoint as ckpt
    from repro.train.optimizer import AdamConfig, adam_init, adam_update

    configure_compile_cache()
    if args.trace_dir:
        obs_trace.configure(args.trace_dir, run=f"train_{args.arch}")

    cfg = reduced_config(args.arch)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamConfig(lr=3e-4, grad_clip=1.0)
    opt = adam_init(params, opt_cfg)
    start = 0
    if args.ckpt_dir:
        latest = ckpt.latest_checkpoint(args.ckpt_dir)
        if latest:
            state, meta = ckpt.restore_checkpoint(latest,
                                                  {"params": params, "opt": opt})
            params, opt, start = state["params"], state["opt"], meta["step"]
            print(f"resumed from step {start}")

    @jax.jit
    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(lm.lm_loss)(params, cfg, batch)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        params, opt = adam_update(grads, opt, params, opt_cfg)
        return params, opt, loss

    reg = obs_metrics.get_registry()
    watcher = jaxprof.get_watcher()
    watcher.watch("launch.train_step", step)
    tracer = obs_trace.get_tracer()
    profile_ctx = (jaxprof.profiler_trace(os.path.join(args.trace_dir,
                                                       "jaxprof"))
                   if args.jax_profile and args.trace_dir
                   else contextlib.nullcontext())

    rng = np.random.default_rng(start)
    compile_s = 0.0
    steady_s = 0.0
    with profile_ctx:
        for i in range(start, start + args.steps):
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                            (args.batch, args.seq)), jnp.int32)
            batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
            if cfg.frontend == "vision":
                batch["frontend_embeds"] = jnp.zeros(
                    (args.batch, cfg.frontend_seq, cfg.frontend_dim))
            if cfg.encoder_layers:
                batch["encoder_embeds"] = jnp.zeros(
                    (args.batch, args.seq, cfg.frontend_dim))
            t0s = time.perf_counter()
            params, opt, loss = step(params, opt, batch)
            loss = jax.block_until_ready(loss)
            dt = time.perf_counter() - t0s
            if i == start:
                # the first step pays jit compilation: report it once and
                # keep it out of the steady-state rate
                compile_s = dt
                reg.gauge("train.compile_seconds").set(dt)
                obs_trace.instant("train.compile", cat="train", seconds=dt)
                watcher.rebase()
            else:
                steady_s += dt
                reg.histogram("train.step_seconds").observe(dt)
            if tracer is not None:
                tracer.complete("train.step", tracer.rel(t0s), dt,
                                cat="train", step=i)
            print(f"step {i:4d} loss {float(loss):.4f}")
            if args.ckpt_dir and (i + 1) % 5 == 0:
                ckpt.save_checkpoint(args.ckpt_dir, i + 1,
                                     {"params": params, "opt": opt})
    recompiles = watcher.check()
    steady_steps = max(args.steps - 1, 0)
    rate = steady_steps / steady_s if steady_s > 0 else float("nan")
    print(f"{args.steps} steps: compile {compile_s:.2f}s + steady "
          f"{steady_s:.2f}s ({rate:.1f} steps/s steady-state)")
    if recompiles:
        print(f"WARNING: {len(recompiles)} unexpected recompile(s): "
              + ", ".join(e.name for e in recompiles))
    if args.trace_dir:
        paths = obs_trace.shutdown()
        print(f"trace: {paths['trace']}\nevents: {paths['events']}")


if __name__ == "__main__":
    main()
