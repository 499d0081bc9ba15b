"""Raw simulation ensembles for the benchmark's configurations.

A configuration's dataset is fixed: ``dataset_seed`` draws its members'
parameters, and the program's own solver integrates them.  The raw fields are
simulated once per checkout and kept under ``bench/cache/`` as ``.npy`` files.
The cache key hashes every file under ``src/repro/sim/`` and the
configuration's dataset keys, so a change to the solver, or to the dataset a
configuration asks for, simulates anew.  Everything downstream of the raw
fields (normalisation, Algorithm 1, encode, store build) runs in every run's
set-up, through the code under test.

A run simulates in a child process (``ensure_cached``), before its own
process touches the chip: the solver's programs and buffers then never show
in the run's peak memory, which holds only the cell's own work.

    python3 bench/dataset.py '<configuration as JSON>'

simulates one configuration's ensemble into the cache, on the chip.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, "cache")
SIM_DIR = os.path.join(REPO_ROOT, "src", "repro", "sim")


def cache_key(dataset: dict, sim_dir: str = SIM_DIR) -> str:
    """Hex digest over the solver's sources and the dataset's keys."""
    h = hashlib.sha256()
    for root, dirs, files in os.walk(sim_dir):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            h.update(os.path.relpath(path, sim_dir).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    h.update(json.dumps(dataset, sort_keys=True).encode())
    return h.hexdigest()[:20]


def simulate(dataset: dict):
    """(params (M, 6) f32, fields (M, T, H, W, 6) f32) from the solver."""
    from repro.sim.ensemble import EnsembleSpec, sample_params
    from repro.sim.solver import run_simulation
    spec = EnsembleSpec(name=dataset["name"], ny=dataset["ny"],
                        nx=dataset["nx"], nsnaps=dataset["nsnaps"],
                        nsteps=dataset["nsteps"], pchip=dataset["pchip"])
    plist = sample_params(spec, dataset["members"], dataset["dataset_seed"])
    fields = np.empty((len(plist), spec.nsnaps, spec.ny, spec.nx, 6),
                      np.float32)
    for i, p in enumerate(plist):
        fields[i] = np.asarray(run_simulation(
            p, ny=spec.ny, nx=spec.nx, nsteps=spec.nsteps,
            nsnaps=spec.nsnaps, dt=dataset["dt"]))
    if not np.isfinite(fields).all():
        raise RuntimeError(f"dataset {dataset['name']}: the solver returned "
                           "non-finite fields")
    return np.stack([p.as_vector() for p in plist]), fields


def cache_path(config: dict) -> str:
    return os.path.join(CACHE_DIR, f"{config['name']}-"
                                   f"{cache_key(config['dataset'])}")


def load_ensemble(config: dict):
    """The configuration's raw ensemble, simulated on the first call in a
    checkout and read back from ``CACHE_DIR`` after that.

    Returns ``(params (M, 6) f32, fields (M, T, H, W, 6) f32)``; the fields
    are a read-only memory map.
    """
    cache_dir = CACHE_DIR
    name = config["name"]
    target = cache_path(config)
    if not os.path.isdir(target):
        os.makedirs(cache_dir, exist_ok=True)
        for old in os.listdir(cache_dir):       # stale keys of this config
            if old.startswith(name + "-"):
                shutil.rmtree(os.path.join(cache_dir, old),
                              ignore_errors=True)
        params, fields = simulate(config["dataset"])
        tmp = target + ".tmp"
        os.makedirs(tmp)
        np.save(os.path.join(tmp, "params.npy"), params)
        np.save(os.path.join(tmp, "fields.npy"), fields)
        del fields
        os.rename(tmp, target)
    return (np.load(os.path.join(target, "params.npy")),
            np.load(os.path.join(target, "fields.npy"), mmap_mode="r"))


def ensure_cached(config: dict) -> bool:
    """Simulate the configuration's ensemble in a child process when this
    checkout has none cached.  Call it before the calling process touches
    the chip, which one process holds at a time.  True when the ensemble is
    cached."""
    if os.path.isdir(cache_path(config)):
        return True
    proc = subprocess.run([sys.executable, os.path.abspath(__file__),
                           json.dumps(config)])
    return proc.returncode == 0 and os.path.isdir(cache_path(config))


def _main(config_json: str) -> int:
    sys.path[:0] = [REPO_ROOT, os.path.join(REPO_ROOT, "src")]
    from bench import harness
    try:
        harness.use_compile_cache()
        harness.device_info(1)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    load_ensemble(json.loads(config_json))
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1]))
