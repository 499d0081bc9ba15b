"""Tiny cells for the CPU tests: the real loops and readers, at a size a
test run can hold, with the dataset cached under a temporary directory."""
from __future__ import annotations

import os

from bench import dataset, harness

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny.json")
WORKLOADS = {"train": ("train-rt", {"batch": 4, "loss_every": 5}),
             "certify": ("certify-pchip", {}),
             "serve": ("serve-rt", {"rate_per_s": 40.0})}


def tiny_cell(kind: str, cache_dir: str) -> harness.Cell:
    """The ``kind`` cell of ``BENCHMARK.json`` with the tiny configuration."""
    dataset.CACHE_DIR = str(cache_dir)
    name, traffic = WORKLOADS[kind]
    cell = harness.resolve(name)
    cell.config = harness.load_json(TINY)
    cell.traffic = dict(cell.traffic, **traffic)
    return cell


def run(cell: harness.Cell, seed: int = 2 ** 31 + 7, seconds: float = 0.5,
        trace: bool = False) -> dict:
    import time
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            require_tpu=False)
