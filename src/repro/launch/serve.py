"""Serving launcher: continuous batching under synthetic open-loop load.

Drives either engine in ``repro.serving`` with the mixed-length workloads
from ``repro.serving.loadgen``:

  * ``--mode lm``        -- LM ``ServeEngine`` on a reduced decoder arch;
  * ``--mode surrogate`` -- ``SurrogateServeEngine`` on a fresh N-member
                            fleet (the paper's served deliverable: per-query
                            ensemble mean + variability-band width).

``--rate QPS`` switches from closed-loop (all requests at t=0, pure
throughput) to an open-loop Poisson arrival process -- latencies then count
queueing delay from each request's scheduled arrival.  ``--lockstep`` runs
the chunked ``steps = max(...)`` baseline instead of continuous batching,
for eyeballing the slot-recycling win.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --requests 16
  PYTHONPATH=src python -m repro.launch.serve --mode surrogate --rate 8
For the production-mesh serving dry-run use repro.launch.dryrun with the
decode_32k / long_500k cells.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import reduced_config
from repro.launch.compile_cache import configure_compile_cache
from repro.models import lm
from repro.obs import trace as obs_trace
from repro.serving import ServeEngine, SurrogateServeEngine
from repro.serving.loadgen import (latency_percentiles, lm_workload,
                                   surrogate_workload)


def _report(tag: str, done, pct: dict, extra: str) -> None:
    print(f"{tag}: {len(done)} completed  "
          f"p50={pct['p50'] * 1e3:.1f}ms p99={pct['p99'] * 1e3:.1f}ms  "
          f"{extra}")


def serve_lm(args) -> None:
    cfg = reduced_config(args.arch)
    if cfg.encoder_layers:
        raise SystemExit("use the decode dry-run for enc-dec serving")
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, batch_slots=args.slots,
                         max_seq=args.max_seq)
    reqs = lm_workload(cfg.vocab_size, args.requests,
                       rate_qps=args.rate, seed=args.seed)
    done = engine.run_lockstep(reqs) if args.lockstep else engine.run(reqs)
    for i, r in enumerate(done[:4]):
        print(f"req {i}: prompt[{len(r.prompt)}]={r.prompt.tolist()[:6]}... "
              f"-> {r.output.tolist()}")
    _report("lm" + ("/lockstep" if args.lockstep else ""),
            done, latency_percentiles(done),
            f"{engine.tokens_per_second:.1f} decode tok/s "
            f"({engine.prefill_tokens_per_second:.0f} prefill tok/s, "
            f"util={engine.slot_utilization:.2f}; CPU smoke -- production "
            f"numbers come from the TPU mesh)")


def serve_surrogate(args) -> None:
    from repro.core.ensemble import init_ensemble
    from repro.models.surrogate import SurrogateConfig
    cfg = SurrogateConfig(height=32, width=16, base_channels=32)
    members = init_ensemble(cfg, list(range(args.members)))
    engine = SurrogateServeEngine(members, cfg, batch_slots=args.slots)
    queries = surrogate_workload(cfg.cond_dim - 1, args.requests,
                                 rate_qps=args.rate, seed=args.seed)
    done = (engine.run_lockstep(queries) if args.lockstep
            else engine.run(queries))
    q = next(d for d in done if d.steps > 0)
    print(f"query: T={q.steps} mean{q.mean.shape} "
          f"band width mean={float(q.width.mean()):.4f}")
    _report("surrogate" + ("/lockstep" if args.lockstep else ""),
            done, latency_percentiles(done),
            f"{engine.queries_per_second:.1f} q/s "
            f"util={engine.slot_utilization:.2f} "
            f"({args.members}-member fleet, one fused dispatch/step)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "surrogate"), default="lm")
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--members", type=int, default=2,
                    help="surrogate fleet size")
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop Poisson arrival rate (qps); "
                         "default: closed loop")
    ap.add_argument("--lockstep", action="store_true",
                    help="run the chunked max(...) baseline instead of "
                         "continuous batching")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="enable telemetry: write <run>.trace.json "
                         "(Perfetto-loadable) + <run>.events.jsonl here")
    args = ap.parse_args()
    configure_compile_cache()
    if args.trace_dir:
        obs_trace.configure(args.trace_dir, run=f"serve_{args.mode}")
    (serve_lm if args.mode == "lm" else serve_surrogate)(args)
    if args.trace_dir:
        paths = obs_trace.shutdown()
        print(f"trace: {paths['trace']}\nevents: {paths['events']}")


if __name__ == "__main__":
    main()
