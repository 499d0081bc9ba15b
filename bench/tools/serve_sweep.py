"""Find the highest query rate the serving cell sustains.

    python3 bench/tools/serve_sweep.py <workload> <seed> <seconds> <rate> [<rate> ...]

Set-up as a run of the cell, then one window per rate.  For each rate it
prints the queries answered, the latency percentiles, the time the engine
took past the last arrival (the drain), and the mean latency of the last
quarter of arrivals over the first quarter's: a backlog that grows through
the window shows as a drain and a ratio well above 1.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> None:
    workload, seed, seconds, *rates = sys.argv[1:]
    import numpy as np
    from bench import harness, trace_reduce
    harness.use_compile_cache()
    cell = harness.resolve(workload)
    device = harness.device_info(int(cell.workload["chips"]))
    ctx = harness.Context(cell, int(seed), 0.0, False,
                          peaks=harness.peaks_for(device["kind"]))
    state = cell.loop.setup(ctx)
    for rate in rates:
        state.tr = dict(state.tr, rate_per_s=float(rate))
        captured = []
        run = state.engine.run

        def spy(queries):
            done = run(queries)
            captured.extend(queries)
            return done
        state.engine.run = spy
        out = state.window(float(seconds), trace_reduce.mark)
        state.engine.run = run
        arr = np.array([q.arrival for q in captured])
        lat = np.array([q.latency for q in captured])
        order = np.argsort(arr)
        q = max(len(order) // 4, 1)
        c = out["counts"]
        print(json.dumps({
            "rate": float(rate), "queries": c["queries"],
            "p50_ms": 1000 * float(np.percentile(lat, 50)),
            "p95_ms": out["serve_latency_p95_ms"],
            "drain_s": c["window_s"] - float(arr.max()),
            "late_over_early": float(lat[order[-q:]].mean()
                                     / lat[order[:q]].mean()),
            "queue_wait_p95_ms": c["queue_wait_p95_ms"]}), flush=True)


if __name__ == "__main__":
    main()
