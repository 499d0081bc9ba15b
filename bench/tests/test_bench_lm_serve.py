"""The LM serving cell on the CPU at a tiny size: the real loop, reference
and readers, on ``tiny.py``'s pattern (a tiny Falcon-H1 configuration with
the published multipliers, and a fast tiny traffic)."""
from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, lm_faults, lm_work, trace_reduce
from bench.loops import lm_serve
from repro.configs import get_config

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = harness.load_json(os.path.join(DATA, "tiny-falcon-h1.json"))
TRAFFIC = {"rate_per_s": 50.0, "min_requests": 6, "checked": 2,
           "check_steps": 4,
           "prompt_tokens": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
           "output_tokens": {"median": 6, "sigma": 0.8, "min": 2, "max": 24},
           "prefill_buckets": [8, 16, 32, 48]}
HOST_METRICS = ("lm_serve_mfu", "lm_prefill_share", "lm_fetch_share",
                "lm_collect_share", "lm_no_work_share")


# the cell's attention and Mamba-2 at its own widths (heads, head and state
# sizes, groups), with the model width, depth and vocabulary cut to fit a
# test run
MIXER_CUT = {"hidden_size": 256, "intermediate_size": 512,
             "num_hidden_layers": 2, "vocab_size": 512,
             "serving": {"slots": 4, "max_seq": 144}}
MIXER_TRAFFIC = {"rate_per_s": 50.0, "min_requests": 3, "checked": 2,
                 "check_steps": 8,
                 "prompt_tokens": {"median": 96, "sigma": 0.4, "min": 48,
                                   "max": 128},
                 "output_tokens": {"median": 12, "sigma": 0.3, "min": 9,
                                   "max": 16},
                 "prefill_buckets": [64, 128]}


def tiny_cell(config=TINY, traffic=TRAFFIC) -> harness.Cell:
    cell = harness.resolve("serve-falcon-h1")
    cell.config = config
    cell.traffic = dict(cell.traffic, **traffic)
    return cell


def mixer_cell() -> harness.Cell:
    conf = harness.load_json(os.path.join(
        harness.REPO_ROOT, "bench", "configs", "falcon-h1-34b-8l.json"))
    return tiny_cell(dict(conf, **MIXER_CUT), MIXER_TRAFFIC)


@pytest.fixture(scope="module")
def traced():
    import time
    return harness.run_cell(tiny_cell(), 2 ** 31 + 11, 0.1, True,
                            time.perf_counter(), require_tpu=False)


def test_tiny_cell_is_correct_and_compiles_nothing_in_the_window(traced):
    assert traced["correct"] is True
    assert traced["attempted"] >= TRAFFIC["min_requests"]
    assert traced["failed"] == 0
    checks = traced["checks"]
    assert set(checks) == {"prefill_gap", "decode_gap", "compiles_in_window"}
    assert checks["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("name", HOST_METRICS)
def test_traced_run_reports_the_host_metrics(traced, name):
    m = traced["metrics"][name]
    assert m["unit"] == "%" and 0.0 < m["value"] <= 100.0


def test_traced_run_reports_the_queue_wait_and_phases_within_the_window(
        traced):
    assert traced["metrics"]["queue_wait_p95_ms"]["value"] >= 0.0
    shares = [traced["metrics"][n]["value"] for n in HOST_METRICS[1:]]
    assert sum(shares) <= 100.0


def test_bench_config_is_the_registry_entry_cut_in_depth_and_vocab():
    conf = harness.load_json(os.path.join(
        harness.REPO_ROOT, "bench", "configs", "falcon-h1-34b-8l.json"))
    cut = lm_serve.arch_config(conf)
    assert set(conf["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert cut == dataclasses.replace(get_config("falcon-h1-34b"),
                                      num_layers=8, vocab_size=32640)


def test_lengths_are_the_same_multiset_for_every_seed():
    spec = TRAFFIC["prompt_tokens"]
    a = lm_serve.lognormal_lengths(50, spec, np.random.default_rng(1))
    b = lm_serve.lognormal_lengths(50, spec, np.random.default_rng(2))
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.min() >= spec["min"] and a.max() <= spec["max"]
    assert abs(np.median(a) - spec["median"]) <= 1


def test_flops_and_bytes_by_hand():
    c = TINY
    d, f, hd = c["hidden_size"], c["intermediate_size"], c["head_dim"]
    h, kv, nh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["mamba_n_heads"])
    ssm, n, g, k = c["mamba_d_ssm"], c["mamba_d_state"], c["mamba_n_groups"], \
        c["mamba_d_conv"]
    proj = (d * h * hd * 2 + d * kv * hd * 2 + d * (2 * ssm + 2 * g * n + nh)
            + ssm * d + 3 * d * f)
    layers, vocab = c["num_hidden_layers"], c["vocab_size"]
    # one prompt of 3 tokens, two outputs: prefill 3 tokens + head once,
    # one decode token attending 4 positions + head once
    attended = [1, 2, 3, 4]
    want = sum(layers * (2 * proj + 4 * h * hd * a + 4 * ssm * n)
               for a in attended) + 2 * 2 * d * vocab
    assert lm_work.serve_flops(c, [3], [2]) == pytest.approx(want)
    layer = (proj + k * (ssm + 2 * g * n) + (ssm + 2 * g * n) + 3 * nh + ssm
             + 2 * d)
    weights = 2 * (layers * layer + d * vocab + d + 4 * d)      # bfloat16
    state = 2 * 4 * layers * 4 * (ssm * n + (k - 1) * (ssm + 2 * g * n))
    kv_bytes = 4 * layers * 2 * kv * hd * 4
    assert lm_work.decode_bytes(c, 1, 4, [3], [2]) == pytest.approx(
        weights + state + kv_bytes)


class _Summary:
    def __init__(self, modules, scopes):
        self.modules, self.scopes = modules, scopes

    def module_s(self, fragment):
        calls = secs = 0
        for name, (c, s) in self.modules.items():
            if fragment in name:
                calls, secs = calls + c, secs + s
        return calls, secs

    def scope_s(self, scope):
        return self.scopes.get(scope, 0.0)


def _reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH_DIR, "metrics", name + ".py"), name).read


def test_device_readers_on_a_summary():
    class Ctx:
        window_s = 10.0
        peaks = {"hbm_bytes_per_s": 819e9}
        counts = {"decode_bytes": 819e9 * 0.5}
        trace_summary = _Summary(
            {"jit__decode_step": (100, 2.0), "jit__prefill": (5, 3.0)},
            {"attn_mixer": 1.0, "ssm_mixer": 1.5})
    assert _reader("lm_decode_step_ms")(Ctx) == pytest.approx(20.0)
    assert _reader("lm_decode_roofline")(Ctx) == pytest.approx(25.0)
    assert _reader("lm_mixer_share")(Ctx) == pytest.approx(50.0)
    Ctx.trace_summary = _Summary({}, {})
    for name in ("lm_decode_step_ms", "lm_decode_roofline", "lm_mixer_share"):
        assert _reader(name)(Ctx) is None


def test_the_control_fails_a_limit():
    """The reference one precision below the configuration (float8
    operands, bfloat16 state) against the float32 one, on a tiny cell's
    checked requests: it must come out not correct."""
    cell = tiny_cell()
    ctx = harness.Context(cell, 5, 0.1, False)
    state = cell.loop.setup(ctx)
    state.window(0.1, trace_reduce.mark)
    ref = state.reference_logits()
    control = state.reference_logits(state_dtype=jnp.bfloat16,
                                     operand_dtype=jnp.float8_e4m3fn)
    got = lm_serve.compare(control, ref)
    assert any(got[k] > lm_serve.LIMITS[k] for k in got), json.dumps(got)


def _run_mixer_cell(fault, monkeypatch):
    import time

    import jax
    if fault:
        lm_faults.plant(fault, monkeypatch.setattr)
    jax.clear_caches()
    try:
        return harness.run_cell(mixer_cell(), 2 ** 31 + 23, 0.1, False,
                                time.perf_counter(), require_tpu=False)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_mixer_widths_program_is_correct(monkeypatch):
    res = _run_mixer_cell(None, monkeypatch)
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("fault", list(lm_faults.FAULTS))
def test_mixer_widths_fault_is_not_correct(fault, monkeypatch):
    """At the cell's mixer widths, with the loop's weights, each planted
    fault -- SSM state not carried into decode, B/C groups swapped, the KV
    write one position off -- fails a limit of ``correct``."""
    res = _run_mixer_cell(fault, monkeypatch)
    assert res["correct"] is False, res["checks"]
