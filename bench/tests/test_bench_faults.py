"""``correct`` fails every fault a cell can have.

At a size a test run holds, on the CPU, a run driven through the harness
with the timed path broken underneath reports ``correct`` false: a training
step that returns its state unchanged, one that leaves half of the batch out
(the mean over the rest), and an answer altered where it is produced (a
decoded training row, an encoded payload word, a served mean).  The cells
run on one chip, so there is no exchange between chips to leave out.
"""
from __future__ import annotations

import functools

import jax
import pytest

from bench.tests import tiny


def _unchanged_state(make):
    def make_step(*a, **k):
        real = make(*a, **k)
        return lambda p, o, idx: (p, o, real(p, o, idx)[2])
    return make_step


def _half_batch(make):
    def make_step(*a, **k):
        real = make(*a, **k)
        return lambda p, o, idx: real(p, o, idx[:idx.shape[0] // 2])
    return make_step


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_train_fault_is_not_correct(fault, tmp_path, monkeypatch):
    import repro.compression as compression
    from repro.train import source
    cell = tiny.tiny_cell("train", tmp_path)
    if fault == "altered_answer":       # one decoded row sign-flipped
        real = compression.decode_stacked_payloads

        def altered(*a, **k):
            return real(*a, **k).at[0].multiply(-1.0)
        monkeypatch.setattr(compression, "decode_stacked_payloads", altered)
    else:
        wrap = {"unchanged_state": _unchanged_state,
                "half_batch": _half_batch}[fault]
        monkeypatch.setattr(source, "make_fused_step",
                            wrap(source.make_fused_step))
    jax.clear_caches()
    try:
        res = tiny.run(cell)
    finally:
        jax.clear_caches()
    assert res["correct"] is False, res["checks"]


def test_certify_altered_answer_is_not_correct(tmp_path, monkeypatch):
    import repro.compression as compression
    real = compression.get_codec

    class Altered:
        def __init__(self, codec):
            self.codec = codec

        def encode_batch(self, xs, tolerances=None):
            cf = self.codec.encode_batch(xs, tolerances)
            cf.payload = cf.payload.at[0, 0, 0].add(1)
            return cf

    monkeypatch.setattr(compression, "get_codec",
                        lambda *a, **k: Altered(real(*a, **k)))
    res = tiny.run(tiny.tiny_cell("certify", tmp_path))
    assert res["correct"] is False, res["checks"]


def test_serve_altered_answer_is_not_correct(tmp_path, monkeypatch):
    from repro.serving import surrogate_engine
    real = surrogate_engine._fleet_step

    @functools.partial(jax.jit, static_argnames=("cfg", "sigmas"))
    def altered(members, cond, cfg, sigmas):
        mean, width = real(members, cond, cfg, sigmas)
        return mean.at[0].multiply(-1.0), width
    monkeypatch.setattr(surrogate_engine, "_fleet_step", altered)
    res = tiny.run(tiny.tiny_cell("serve", tmp_path))
    assert res["correct"] is False, res["checks"]
