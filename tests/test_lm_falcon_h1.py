"""Falcon-H1's parallel attention + Mamba-2 block against its plain
reference (``bench/reference/falcon_h1_ref.py``) on the CPU at a small size,
with seeded random weights; the grouped SSD scan against the sequential
recurrence; and the single-group architectures unchanged by the grouping.
"""
import dataclasses
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bench.loops import lm_serve
from bench.reference import falcon_h1_ref
from repro.configs import reduced_config
from repro.models import lm
from repro.serving import Request, ServeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "bench", "tests", "data", "tiny-falcon-h1.json")
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "lm_single_group_logits.npz")


def _sequential_ssd(xh, dt, a_log, bm, cm):
    """s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T, y_t = s_t C_t, one token
    at a time in float64; head h reads group h // (heads / groups)."""
    xh, dt, bm, cm = (np.asarray(v, np.float64) for v in (xh, dt, bm, cm))
    b, s, h, p = xh.shape
    g, n = bm.shape[-2:]
    a = -np.exp(np.asarray(a_log, np.float64))
    state = np.zeros((b, h, p, n))
    ys = np.zeros((b, s, h, p))
    for t in range(s):
        for hh in range(h):
            grp = hh // (h // g)
            state[:, hh] = (np.exp(dt[:, t, hh] * a[hh])[:, None, None]
                            * state[:, hh]
                            + (dt[:, t, hh, None] * xh[:, t, hh])[:, :, None]
                            * bm[:, t, grp, None, :])
            ys[:, t, hh] = np.einsum("bpn,bn->bp", state[:, hh],
                                     cm[:, t, grp])
    return ys, state


@pytest.mark.parametrize("groups,chunk", [(1, 8), (2, 8), (2, 24), (4, 6)])
def test_grouped_ssd_matches_sequential_recurrence(groups, chunk):
    rng = np.random.default_rng(groups * 100 + chunk)
    b, s, h, p, n = 2, 24, 4, 3, 5
    xh = rng.standard_normal((b, s, h, p)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (b, s, h)).astype(np.float32)
    a_log = np.log(rng.uniform(0.5, 4.0, h)).astype(np.float32)
    bm = rng.standard_normal((b, s, groups, n)).astype(np.float32)
    cm = rng.standard_normal((b, s, groups, n)).astype(np.float32)
    y, state = jax.jit(lambda *a: lm.ssd_scan(*a, chunk=chunk))(
        xh, dt, a_log, bm, cm)
    want_y, want_state = _sequential_ssd(xh, dt, a_log, bm, cm)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), want_state, rtol=1e-4,
                               atol=1e-4)


@pytest.fixture(scope="module")
def tiny():
    with open(TINY) as f:
        conf = dict(json.load(f), param_dtype="float32")
    cfg = lm_serve.arch_config(conf)
    return conf, cfg, lm.init_lm(jax.random.PRNGKey(3), cfg)


def test_serve_matches_the_reference_on_logits(tiny):
    """Prefill at padded buckets and decode through the cache, four slots,
    against the reference's full causal forward over prompt and emitted
    tokens: every logits row the engine took a token from."""
    conf, cfg, params = tiny
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab_size, n).astype(np.int32), m,
                    keep_logits=m)
            for n, m in ((5, 6), (20, 9), (33, 4), (48, 12), (7, 1))]
    eng = ServeEngine(params, cfg, batch_slots=4, max_seq=64,
                      prefill_buckets=(8, 16, 32, 48))
    eng.warmup()
    eng.run(reqs)
    ref_params = lm_serve.reference_params(params)
    for r in reqs:
        toks = np.concatenate([r.prompt, r.output[:-1]])
        rows = len(r.prompt) - 1 + np.arange(len(r.output))
        ref = np.asarray(falcon_h1_ref.forward(conf, ref_params, toks, rows))
        gaps = lm_serve.row_gaps(np.stack(r.logits), ref)
        assert len(r.logits) == len(r.output)
        assert gaps.max() < 1e-5, gaps


def test_reference_catches_a_missing_multiplier(tiny):
    """The comparison is tight enough to see the key multiplier left out."""
    conf, cfg, params = tiny
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, 24)
    logits, _ = lm.lm_prefill(params, cfg, {"tokens": jnp.asarray(toks[None])},
                              24, cache_dtype=jnp.float32)
    ref_params = lm_serve.reference_params(params)
    wrong = falcon_h1_ref.forward(dict(conf, key_multiplier=1.0), ref_params,
                                  toks, [23])
    right = falcon_h1_ref.forward(conf, ref_params, toks, [23])
    assert lm_serve.row_gaps(logits, right)[0] < 1e-5
    assert lm_serve.row_gaps(logits, wrong)[0] > 1e-2


@pytest.mark.parametrize("arch", ["hymba-1.5b", "mamba2-130m"])
def test_single_group_logits_unchanged(arch):
    """Recorded from the single-group implementation (before B/C groups,
    multipliers and the conv bias): forward logits at 16 positions of a
    two-chunk sequence, then a prefill and four decode steps."""
    cfg = reduced_config(arch)
    params = lm.init_lm(jax.random.PRNGKey(11), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 516), 0,
                              cfg.vocab_size)
    hidden, _ = jax.jit(lambda p: lm.lm_forward(
        p, cfg, {"tokens": toks[:, :512]}))(params)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    fwd = np.asarray(jnp.einsum("bsd,dv->bsv", hidden, w))[0, 7:512:32]
    logits, cache = jax.jit(lambda p: lm.lm_prefill(
        p, cfg, {"tokens": toks[:, :512]}, 520,
        cache_dtype=jnp.float32))(params)
    rows = [np.asarray(logits[0])]
    step = jax.jit(lambda p, c, t, pos: lm.serve_step(p, cfg, c, t, pos))
    for t in range(512, 516):
        logits, cache = step(params, cache, toks[:, t], jnp.int32(t))
        rows.append(np.asarray(logits[0]))
    key = arch.replace("-", "_").replace(".", "p")
    golden = np.load(GOLDEN)
    np.testing.assert_allclose(fwd, golden[key + "_forward"], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.stack(rows), golden[key + "_serve"],
                               rtol=1e-5, atol=1e-5)


def test_falcon_h1_layer_has_published_parameter_count():
    """430.1 M parameters a layer, 33.6 B for the published 72 layers."""
    from repro.configs import get_config
    cfg = get_config("falcon-h1-34b")
    one = dataclasses.replace(cfg, num_layers=1, vocab_size=0)
    per_layer = lm.param_count(one) - cfg.d_model
    assert 430.0e6 < per_layer < 430.2e6
    assert 33.5e9 < lm.param_count(cfg) < 33.7e9
