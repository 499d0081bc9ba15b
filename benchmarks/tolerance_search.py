"""Paper Algorithm 1 / Table: per-sample tolerance search statistics.

Runs Algorithm 1 over a set of samples and reports iterations-to-converge
(paper: 1-2), realized ratios, and the compression-vs-model error margin.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import algorithm1_per_sample, find_tolerance_batch


def run():
    from benchmarks.common import build_study
    study = build_study()
    test = study["test_nf"]
    e = study["meta"]["model_l1_error"]
    samples = [np.transpose(test[i], (2, 0, 1)) for i in range(0, 32, 2)]
    t0 = time.time()
    results = algorithm1_per_sample(samples, [e] * len(samples))
    dt = (time.time() - t0) * 1e6 / len(samples)
    iters = [r.iterations for r in results]
    ratios = [r.ratio for r in results]
    margins = [r.compression_l1 / r.model_l1 for r in results]

    # batched Algorithm 1: the whole stack searches inside ONE jitted
    # lax.while_loop (first call pays the compile; the second is the
    # steady-state dispatch cost)
    batch = np.stack([np.transpose(test[i % len(test)], (2, 0, 1))
                      for i in range(32)])
    errs = [e] * len(batch)
    find_tolerance_batch(batch, errs)              # compile
    t0 = time.time()
    br = find_tolerance_batch(batch, errs)
    dt_batch = (time.time() - t0) * 1e6 / len(batch)
    # batch[i] == test[i], so batch results at even i align with `results`
    off_by = np.abs(np.log2(np.asarray(
        [br.tolerance[i] / results[j].tolerance
         for j, i in enumerate(range(0, 32, 2))])))
    rows = [
        ("alg1/iterations", dt, f"mean={np.mean(iters):.1f} max={max(iters)}"),
        ("alg1/ratio", 0.0,
         f"mean={np.mean(ratios):.1f}x min={min(ratios):.1f}x max={max(ratios):.1f}x"),
        ("alg1/error_margin", 0.0,
         f"compression_L1/model_L1 mean={np.mean(margins):.3f} (<=1 required)"),
        ("alg1/batch32", dt_batch,
         f"speedup={dt / max(dt_batch, 1e-9):.1f}x "
         f"max_doubling_steps_off={off_by.max():.2f}"),
    ]
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
