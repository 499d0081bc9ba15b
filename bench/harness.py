"""The benchmark harness: resolves a cell by name and runs it once.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  -- the configuration's sizes;
* ``bench/traffic/<traffic>.json`` -- the mix's parameters; its ``loop``
  key names the module under ``bench/loops/`` that drives the program;
* ``bench/metrics/<metric>.py``    -- a per-layer metric's reader, a
  ``read(ctx)`` that returns a number or ``None`` when the run has nothing
  for it to read.

A loop module has ``setup(ctx) -> cell``, and ``NEEDS_DATASET = True`` when
the cell reads its configuration's raw ensemble (``bench/dataset.py``).  The
cell has ``window(seconds, mark)``, which returns the end-to-end metrics it
measured, ``free()``, which lets go of the program's state, and ``check()``,
which returns the numbers compared with the plain reference as ``(name,
value, limit)`` triples.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List

from bench import dataset

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """The benchmark cannot run here (no chip, unknown name, bad file)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError(f"no file {os.path.relpath(path, REPO_ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_dyn_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""
    workload: dict
    config: dict
    traffic: dict
    loop: object
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]


def _applies(metric: dict, cell_name: str, e2e_names) -> bool:
    listed = metric.get("workloads")
    if listed is not None:
        return cell_name in listed
    return e2e_names is None or metric["moves"] in e2e_names


def resolve(workload: str, root: str = REPO_ROOT) -> Cell:
    """Find the workload's configuration, traffic, loop and metric files."""
    bench_dir = os.path.join(root, "bench")
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"{workload}: no config {w['config']!r}")
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    loop = load_module(os.path.join(bench_dir, "loops",
                                      traffic["loop"] + ".py"),
                         "loop_" + traffic["loop"])
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload, None)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _applies(m, workload, e2e_names)]
    readers = {m["name"]: load_module(
        os.path.join(bench_dir, "metrics", m["name"] + ".py"),
        "metric_" + m["name"]).read for m in layer}
    return Cell(w, config, traffic, loop, e2e, layer, readers)


@dataclasses.dataclass
class Context:
    """What a loop and the metric readers see of one run."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    peaks: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)
    window_s: float = 0.0
    trace_summary: object = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if require_tpu and d.platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {d.platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileCounter:
    """Counts backend compilations while armed (the window must have none)."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if self.armed and "backend_compile" in event:
            self.count += 1


COMPILE_CACHE_DIR = os.path.join(BENCH_DIR, "cache", "jax")


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, so that only a checkout's first run of a cell compiles and two
    checkouts share nothing.  The program's own cache set-up is handed the
    same directory."""
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> dict:
    """Set up, measure, check; returns the result object the run prints."""
    import jax
    from bench import trace_reduce
    device = device_info(int(cell.workload["chips"]), require_tpu)
    # off the chip (tests only) the arithmetic runs against the v5e peaks
    ctx = Context(cell, seed, seconds, trace, peaks=peaks_for(
        device["kind"] if require_tpu else "TPU v5 lite"))
    compiles = CompileCounter()
    state = cell.loop.setup(ctx)
    setup_s = time.perf_counter() - t_start

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    compiles.armed = True
    if trace:
        jax.profiler.start_trace(tdir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            e2e = state.window(seconds, trace_reduce.mark)
    finally:
        if trace:
            jax.profiler.stop_trace()
        compiles.armed = False
    ctx.counts.update(e2e.pop("counts", {}))
    ctx.window_s = ctx.counts.get("window_s", seconds)
    device["memory_peak_bytes"] = memory_peak_bytes(ctx.cell.workload["chips"])
    e2e["setup_s"] = setup_s
    e2e["peak_hbm_mib"] = device["memory_peak_bytes"] / 2 ** 20

    state.free()
    checks = list(state.check())
    checks.append(("compiles_in_window", float(compiles.count), 0.0))
    correct = all(v <= lim for _, v, lim in checks)

    result = {"correct": bool(correct),
              "attempted": int(ctx.counts.get("attempted", 0)),
              "failed": int(ctx.counts.get("failed", 0))}
    if trace:
        summary = trace_reduce.reduce_dir(tdir, device["count"])
        trace_reduce.remove_dir(tdir)
        ctx.trace_summary = summary
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = summary.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in e2e}
    result["device"] = device
    result["checks"] = {name: {"value": float(v), "limit": float(lim)}
                        for name, v, lim in checks}
    return result


def main(args, t_start: float) -> int:
    try:
        cell = resolve(args.workload)
        if args.seconds <= 0:
            raise BenchError("--seconds must be positive")
        if (getattr(cell.loop, "NEEDS_DATASET", False)
                and not dataset.ensure_cached(cell.config)):
            raise BenchError(f"no ensemble for {cell.config['name']}")
        use_compile_cache()
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
