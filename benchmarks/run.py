"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and, per module, writes a
machine-readable ``experiments/bench/BENCH_<module>.json`` carrying the raw
rows, the key=value metrics parsed out of each ``derived`` string (ratios,
throughputs, speedups), the module wall-clock, an environment-provenance
block (jax version, backend, device count, git describe, hostname -- a
number without its environment is not comparable across PRs), and the
module's telemetry snapshot from the obs metrics registry.  With
``--trace-dir`` each module additionally records a span trace
(``BENCH_<module>.json`` then points at the Perfetto-loadable trace +
events files).  The shared study (ensembles + seed models + lossy models)
builds once per process and is cached under experiments/data/.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import re
import socket
import subprocess
import sys
import time
import traceback

MODULES = [
    "benchmarks.data_description",     # Table I
    "benchmarks.variability_bands",    # Fig. 3 / Fig. 6
    "benchmarks.ensemble_certify",     # §III-§IV end-to-end certification
    "benchmarks.generation_loss",      # Fig. 5
    "benchmarks.tolerance_search",     # Algorithm 1
    "benchmarks.psnr_distributions",   # Fig. 7 / Fig. 9
    "benchmarks.mixing_layer",         # Fig. 8
    "benchmarks.loading_throughput",   # Fig. 11
    "benchmarks.datagen_throughput",   # streaming produce: seq vs overlapped
    "benchmarks.epoch_time",           # Fig. 12 (+ device-resident row)
    "benchmarks.serving_throughput",   # continuous batching vs lockstep
    "benchmarks.checkpoint_io",        # codec-founded lossy checkpoints
    "benchmarks.roofline",             # §Roofline table (dry-run artifacts)
]

BENCH_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                         "bench")

_METRIC = re.compile(r"([A-Za-z_][\w]*)=([-+0-9.eE]+)x?s?")


def parse_metrics(derived: str) -> dict:
    """Pull ``key=value`` numeric tokens out of a derived string (units like
    the trailing 'x' / 's' are stripped; non-numeric values are skipped)."""
    out = {}
    for key, val in _METRIC.findall(str(derived)):
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


def _git_describe() -> str:
    try:
        out = subprocess.run(
            ["git", "describe", "--always", "--dirty"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        if out.returncode == 0:
            return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def env_provenance() -> dict:
    """The environment block stamped into every bench artifact: a number
    without its producing environment is not comparable across PRs."""
    import jax
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "devices": [str(d) for d in jax.devices()][:8],
        "python": platform.python_version(),
        "platform": platform.platform(),
        "hostname": socket.gethostname(),
        "git": _git_describe(),
    }


def write_bench_json(mod_name: str, rows, seconds: float, status: str,
                     env=None, telemetry=None, trace=None) -> str:
    """Persist one module's results as BENCH_<module>.json (atomic write)."""
    from repro.data.shards import atomic_write_json
    os.makedirs(BENCH_DIR, exist_ok=True)
    short = mod_name.rsplit(".", 1)[-1]
    path = os.path.join(BENCH_DIR, f"BENCH_{short}.json")
    doc = {
        "module": mod_name,
        "status": status,
        "seconds": round(seconds, 2),
        "generated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": [{
            "name": name,
            "us_per_call": float(us),
            "derived": str(derived),
            "metrics": parse_metrics(derived),
        } for name, us, derived in rows],
    }
    if env is not None:
        doc["env"] = env
    if telemetry is not None:
        doc["telemetry"] = telemetry
    if trace is not None:
        doc["trace"] = trace
    atomic_write_json(path, doc)
    return path


def main() -> None:
    import importlib

    from repro.launch.compile_cache import configure_compile_cache
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", default=None,
                    help="record a span trace per module "
                         "(BENCH_*.json points at the files)")
    args = ap.parse_args()

    configure_compile_cache()
    env = env_provenance()
    print("name,us_per_call,derived")
    failures = 0
    for mod_name in MODULES:
        short = mod_name.rsplit(".", 1)[-1]
        # fresh per-module telemetry so each BENCH json's snapshot is its own
        obs_metrics.get_registry().reset()
        if args.trace_dir:
            obs_trace.configure(args.trace_dir, run=f"bench_{short}")
        t0 = time.time()
        rows = []
        status = "ok"
        try:
            mod = importlib.import_module(mod_name)
            rows = list(mod.run())
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
        except Exception:
            failures += 1
            status = "failed"
            print(f"{mod_name},0,FAILED")
            traceback.print_exc(file=sys.stderr)
        seconds = time.time() - t0
        telemetry = obs_metrics.get_registry().snapshot()
        trace_paths = obs_trace.shutdown() if args.trace_dir else None
        write_bench_json(mod_name, rows, seconds, status, env=env,
                         telemetry=telemetry, trace=trace_paths)
        print(f"# {mod_name} took {seconds:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
