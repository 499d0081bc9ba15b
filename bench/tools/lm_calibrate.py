"""Readings that the LM serving cell's limits of ``correct`` are set from.

    python3 bench/tools/lm_calibrate.py <workload> <first_seed> <seeds> <control_seeds> [<requests> [<fault_seeds>]]

For each seed the cell is set up anew (weights from the seed) and serves a
short window of ``requests`` requests (default 8) at its own rate, each
answer cut to the ``check_steps + 1`` tokens that are checked, the checked
requests drawn among them as a run draws them; the program's readings are
their gaps from the float32 reference (``compare`` of the loop).  For the
first ``control_seeds`` seeds the same requests also give the control's
readings -- the reference one precision below the configuration (float8
operands, bfloat16 state) against the float32 one -- and two partial
stand-ins: the bfloat16 state alone, and bfloat16 operands alone.  For the
first ``fault_seeds`` seeds (default 0) each fault of ``bench/lm_faults.py``
is planted in the program, which serves the checked prompts again at the
cell's shapes, and its gaps from the reference are read.

Prints one JSON line per seed and kind.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def report(**kw) -> None:
    print(json.dumps(kw), flush=True)


def planted(state, name: str, compare) -> dict:
    """The checked requests served anew by a program with fault ``name``
    planted (an engine of the cell's slots and buckets), against the
    reference teacher-forced on what that program emitted."""
    import jax
    from bench import lm_faults
    from repro.serving import Request, ServeEngine
    undo = lm_faults.plant(name)
    jax.clear_caches()
    try:
        serving = state.conf["serving"]
        engine = ServeEngine(state.params, state.cfg,
                             batch_slots=int(serving["slots"]),
                             max_seq=int(serving["max_seq"]),
                             prefill_buckets=state.tr["prefill_buckets"])
        reqs = [Request(r.prompt, len(r.output), keep_logits=len(r.output))
                for r in state.checked]
        engine.run(reqs)
    finally:
        undo()
        jax.clear_caches()
    state.checked = reqs
    return compare(state.program_logits(), state.reference_logits())


def main() -> None:
    workload, first, seeds, controls, *rest = sys.argv[1:]
    requests = int(rest[0]) if rest else 8
    faults = int(rest[1]) if len(rest) > 1 else 0
    import jax.numpy as jnp
    from bench import harness, trace_reduce
    from bench.lm_faults import FAULTS
    harness.use_compile_cache()
    cell = harness.resolve(workload)
    steps = int(cell.traffic["check_steps"])
    cell.traffic = dict(cell.traffic, min_requests=requests, output_tokens=dict(
        cell.traffic["output_tokens"], max=steps + 1))
    device = harness.device_info(int(cell.workload["chips"]))
    stand_ins = {
        "control": {"state_dtype": jnp.bfloat16,
                    "operand_dtype": jnp.float8_e4m3fn},
        "state_bf16": {"state_dtype": jnp.bfloat16},
        "operands_bf16": {"operand_dtype": jnp.bfloat16}}
    report(device=device)
    for k in range(int(seeds)):
        seed = int(first) + k
        ctx = harness.Context(cell, seed, 0.0, False,
                              peaks=harness.peaks_for(device["kind"]))
        state = cell.loop.setup(ctx)
        state.window(requests / float(cell.traffic["rate_per_s"]),
                     trace_reduce.mark)
        state.free()
        ref = state.reference_logits()
        lens = [[len(r.prompt), len(r.output)] for r in state.checked]
        report(seed=seed, kind="program", checked=lens,
               **cell.loop.compare(state.program_logits(), ref))
        if k < int(controls):
            for kind, dtypes in stand_ins.items():
                report(seed=seed, kind=kind, **cell.loop.compare(
                    state.reference_logits(**dtypes), ref))
        if k < faults:
            for name in FAULTS:
                report(seed=seed, kind=name,
                       **planted(state, name, cell.loop.compare))
        del state
        gc.collect()
    report(total_s=time.perf_counter() - T_START)


if __name__ == "__main__":
    main()
