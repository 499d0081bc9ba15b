"""Readings that the limits of ``correct`` are set from.

    python3 bench/tools/calibrate.py <workload> <first_seed> <seeds> <control_seeds>

For a training cell, set-up builds the store once; then, for each seed, the
program's first steps (as a run's set-up drives them) against the plain
float32 reference give the program's readings, and for the first
``control_seeds`` seeds three stand-ins put in the program's place give the
upper readings: the reference in bfloat16 (the control), the reference over
the first half of each batch (half the batch left out, the mean over the
rest) and the reference with one target row altered (an answer altered where
it is produced).  A step that leaves its state unchanged reads 1 on
``update_gap`` by construction and needs no run.

For a certification cell, each seed draws a member of the pool; the
program's stage against the reference gives its readings, and the reference
run on the member rounded to bfloat16 gives the control's.

For a serving cell, each seed sets the cell up anew (weights from the seed)
and serves a short window at the cell's own rate, long enough to finish a
51-step rollout and to draw as many queries as a run checks; the program's
readings are its gaps from the reference over the checked queries, and the
reference band computed in bfloat16 against the float32 one, on the same
queries, gives the control's; each is reported as the largest, the average
and the root mean square over every checked snapshot and field.

Prints one JSON line per seed and kind.
"""
from __future__ import annotations

import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def report(**kw) -> None:
    print(json.dumps(kw), flush=True)


def train(cell, first: int, seeds: int, controls: int) -> None:
    import jax.numpy as jnp
    from bench.loops import train as drv
    for k in range(seeds):
        seed = first + k
        cell.start(seed)
        payload, emax, nplanes, cond = cell.ref_rows
        targets = drv.reference_targets(payload, emax, nplanes,
                                        cell.sample_shape)
        b = cell.batch
        batches = [(cond[s * b:(s + 1) * b], targets[s * b:(s + 1) * b])
                   for s in range(drv.REF_STEPS)]
        ref = drv.reference_readings(cell.params0, batches, cell.model,
                                     cell.lr)
        report(seed=seed, kind="program", **dict(drv.compare(cell.prog, ref)))
        if k >= controls:
            continue
        plain = drv.reference_readings(cell.params0, batches, cell.model,
                                       cell.lr, rounded=False)
        report(seed=seed, kind="program_vs_plain_f32",
               **dict(drv.compare(cell.prog, plain)))
        stand_ins = {
            "control_bf16": drv.reference_readings(
                cell.params0, batches, cell.model, cell.lr,
                dtype=jnp.bfloat16),
            "fault_half_batch": drv.reference_readings(
                cell.params0, [(c[:b // 2], t[:b // 2]) for c, t in batches],
                cell.model, cell.lr),
            "fault_altered_row": drv.reference_readings(
                cell.params0, [(c, t.at[0].multiply(-1.0))
                               for c, t in batches], cell.model, cell.lr),
        }
        for kind, got in stand_ins.items():
            report(seed=seed, kind=kind, **dict(drv.compare(got, ref)))


def certify(cell, first: int, seeds: int, controls: int) -> None:
    import jax.numpy as jnp
    import numpy as np
    from bench.loops import certify as drv
    for k in range(seeds):
        seed = first + k
        m = int(np.random.default_rng(seed).integers(len(cell.pool)))
        tols, cf = cell._certify(m)
        xs = drv.reference_input(cell.pool[m], cell.mean, cell.std)
        ref = drv.reference_encode(xs, cell.model_l1)
        prog = (tols, np.asarray(cf.payload), np.asarray(cf.emax),
                np.asarray(cf.nplanes))
        res = drv.compare(xs, cell.model_l1, prog, ref)
        report(seed=seed, member=m, kind="program",
               mismatch_share=float(np.mean(res["differs"])),
               bound_ratio=res["bound_ratio"], l1_ratio=res["l1_ratio"])
        if k >= controls:
            continue
        low = xs.astype(jnp.bfloat16).astype(jnp.float32)
        res = drv.compare(xs, cell.model_l1,
                          drv.reference_encode(low, cell.model_l1), ref)
        report(seed=seed, member=m, kind="control_bf16",
               mismatch_share=float(np.mean(res["differs"])),
               bound_ratio=res["bound_ratio"], l1_ratio=res["l1_ratio"])


def serve(cell, first: int, seeds: int, controls: int, ctx=None,
          seconds: float = 5.0) -> None:
    import jax.numpy as jnp
    import numpy as np
    from bench import trace_reduce
    from bench.loops import serve as drv

    def stats(pooled):
        out = {}
        for name, parts in pooled.items():
            e = np.concatenate([np.ravel(p) for p in parts])
            out.update({f"{name}.max": float(e.max()),
                        f"{name}.avg": float(e.mean()),
                        f"{name}.rms": float(np.sqrt(np.mean(e * e)))})
        return out

    for k in range(seeds):
        seed = first + k
        if k:
            ctx.seed = seed
            cell = ctx.cell.loop.setup(ctx)
        cell.window(seconds, trace_reduce.mark)
        kinds = ["program"] + (["control_bf16", "program_vs_plain_f32"]
                               if k < controls else [])
        pooled = {kd: {"mean_gap": [], "band_gap": []} for kd in kinds}
        for q in cell.checked:
            conds = jnp.asarray(np.concatenate(
                [np.repeat(q.params_vec[None], q.steps, 0),
                 np.asarray(q.times)[:, None]], axis=1).astype(np.float32))
            ref = drv.reference_band(cell.members, conds, cell.model,
                                     cell.sigmas)
            pairs = {"program": ((q.mean, q.width), ref)}
            if k < controls:
                pairs["control_bf16"] = (drv.reference_band(
                    cell.members, conds, cell.model, cell.sigmas,
                    dtype=jnp.bfloat16), ref)
                pairs["program_vs_plain_f32"] = ((q.mean, q.width),
                                                 drv.reference_band(
                    cell.members, conds, cell.model, cell.sigmas,
                    rounded=False))
            for kd, (a, b) in pairs.items():
                for name, v in drv.entry_gaps(*a, *b).items():
                    pooled[kd][name].append(v)
        for kd in kinds:
            report(seed=seed, kind=kd, checked=len(cell.checked),
                   snapshots=sum(q.steps for q in cell.checked),
                   **stats(pooled[kd]))


def main() -> None:
    workload, first, seeds, controls = sys.argv[1:5]
    from bench import dataset, harness
    cell = harness.resolve(workload)
    if (getattr(cell.loop, "NEEDS_DATASET", False)
            and not dataset.ensure_cached(cell.config)):
        sys.exit(f"no ensemble for {cell.config['name']}")
    harness.use_compile_cache()
    device = harness.device_info(int(cell.workload["chips"]))
    ctx = harness.Context(cell, int(first), 0.0, False,
                          peaks=harness.peaks_for(device["kind"]))
    state = cell.loop.setup(ctx)
    report(setup_s=time.perf_counter() - T_START, device=device)
    kind = cell.traffic["loop"]
    if kind == "serve":
        serve(state, int(first), int(seeds), int(controls), ctx)
    else:
        {"train": train, "certify": certify}[kind](
            state, int(first), int(seeds), int(controls))


if __name__ == "__main__":
    main()
