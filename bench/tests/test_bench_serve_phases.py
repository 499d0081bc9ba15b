"""The serving engine's phase shares: a traced tiny serve-rt run reports the
fetch, collect and no-work shares of its window, each a percentage, and a
program without the phase counters leaves them out."""
from __future__ import annotations

import os

import pytest

from bench import harness
from bench.tests import tiny
from repro.obs import metrics as obs_metrics

SHARES = ("serve_fetch_share", "serve_collect_share", "serve_no_work_share")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    obs_metrics.get_registry().reset()          # the counters are the process's
    cell = tiny.tiny_cell("serve", tmp_path_factory.mktemp("serve"))
    return tiny.run(cell, trace=True)


@pytest.mark.parametrize("name", SHARES)
def test_traced_serve_run_reports_the_share(traced, name):
    m = traced["metrics"][name]
    assert m["unit"] == "%"
    assert 0.0 <= m["value"] <= 100.0


@pytest.mark.parametrize("name", SHARES)
def test_share_is_left_out_without_its_counter(name, monkeypatch):
    reader = harness.load_module(
        os.path.join(harness.BENCH_DIR, "metrics", name + ".py"), name)
    monkeypatch.setattr(reader, "get_registry", obs_metrics.MetricsRegistry)

    class Ctx:
        window_s = 51.0
    assert reader.read(Ctx()) is None
