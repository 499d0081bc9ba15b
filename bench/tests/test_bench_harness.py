"""The harness finds everything by name, prints the contract's last line,
refuses a machine without a TPU, and keys its dataset cache on the solver."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import dataset, harness
from bench.tests import tiny

ROOT = harness.REPO_ROOT
SPEC = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_workload_resolves(workload):
    cell = harness.resolve(workload)
    assert cell.config["name"] == cell.workload["config"]
    assert callable(cell.loop.setup)
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s"} < names
    assert cell.per_layer and set(cell.readers) == {
        m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        assert m["moves"] in names


def _digest(tree: str) -> dict:
    out = {}
    for root, dirs, files in os.walk(tree):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "cache")]
        for f in files:
            path = os.path.join(root, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, tree)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.mark.parametrize("listed", [True, False])
def test_new_config_mix_and_metric_need_only_new_files(listed, tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "cache"))
    before = _digest(str(bench))
    (bench / "configs" / "tiny.json").write_text(open(tiny.TINY).read())
    (bench / "traffic" / "train-b4.json").write_text(json.dumps(
        {"loop": "train", "batch": 4, "loss_every": 5, "lr": 1e-4}))
    (bench / "metrics" / "steps_per_s.py").write_text(
        "def read(ctx):\n    return ctx.counts['steps'] / ctx.window_s\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json", "reduced": []})
    spec["workloads"].append({"name": "train-tiny", "config": "tiny",
                              "traffic": "train-b4", "chips": 1,
                              "why": "test"})
    metric = {"name": "steps_per_s", "unit": "1/s", "better": "higher",
              "source": "host_clock", "layer": "fused step",
              "moves": "train_samples_per_s"}
    if listed:
        metric["workloads"] = ["train-tiny"]
    # without a list, a metric reads in every cell that reports what it moves
    spec["per_layer"].append(metric)
    for m in spec["end_to_end"]:
        if m["name"] == "train_samples_per_s":
            m["workloads"].append("train-tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.resolve("train-tiny", root=str(tmp_path))
    assert cell.config["name"] == "tiny"
    assert cell.traffic["batch"] == 4
    assert "steps_per_s" in cell.readers
    assert ("steps_per_s" in harness.resolve(
        "train-rt", root=str(tmp_path)).readers) is not listed
    assert "steps_per_s" not in harness.resolve(
        "certify-pchip", root=str(tmp_path)).readers
    after = _digest(str(bench))
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_names_are_refused():
    with pytest.raises(harness.BenchError):
        harness.resolve("no-such-cell")


@pytest.mark.parametrize("kind", ["train", "certify", "serve"])
@pytest.mark.parametrize("trace", [False, True])
def test_last_line_schema(kind, trace, tmp_path):
    cell = tiny.tiny_cell(kind, tmp_path)
    res = tiny.run(cell, trace=trace)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if trace:
        keys.append("breakdown")
    assert set(res) == set(keys + ["checks"])
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["attempted"] > 0
    want = {m["name"] for m in (cell.per_layer if trace
                                else cell.end_to_end)}
    assert set(res["metrics"]) <= want
    if not trace:
        assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert all(len(v) <= 10 for v in res["breakdown"].values())
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


def test_cpu_backend_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", SPEC["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_a_checkout_without_the_program_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "cache"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, os.path.join("bench", "run.py"),
         "--workload", SPEC["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_cache_key_follows_the_solver_and_the_dataset(tmp_path):
    sim = tmp_path / "sim"
    shutil.copytree(dataset.SIM_DIR, sim,
                    ignore=shutil.ignore_patterns("__pycache__"))
    ds = tiny.harness.load_json(tiny.TINY)["dataset"]
    key = dataset.cache_key(ds, str(sim))
    assert key == dataset.cache_key(ds, str(sim))
    assert key != dataset.cache_key(dict(ds, members=3), str(sim))
    with open(sim / "solver.py", "a") as f:
        f.write("\n# changed\n")
    assert key != dataset.cache_key(ds, str(sim))
