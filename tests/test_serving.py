"""Continuous-batching serving: scheduler, engine correctness, surrogate fleet.

Regression coverage for the PR-6 bug set:
  * mixed-length batched prefill must match solo serving token-for-token
    (the old left-pad + uniform-pos path contaminated logits);
  * ``max_new_tokens=0`` requests are returned (empty output), never
    silently dropped -- pad slots are scheduler state, not sentinel counts;
  * step functions are module-level jits shared across engine instances
    (no per-engine retrace);
  * ``tokens_per_second`` uses decode seconds only (prefill split out);
  * surrogate band width is consistent with ``core.variability``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import reduced_config
from repro.models import lm
from repro.serving import (Request, ServeEngine, SlotScheduler,
                           SurrogateQuery, SurrogateServeEngine)
from repro.serving import engine as engine_mod
from repro.serving.loadgen import (latency_percentiles, lm_workload,
                                   poisson_arrivals, surrogate_workload)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

class TestSlotScheduler:
    def test_fifo_admission_order(self):
        s = SlotScheduler(2)
        s.submit_all(["a", "b", "c"])
        assert [r for _, r in s.admit()] == ["a", "b"]
        assert s.pending == 1 and s.busy == 2
        assert s.admit() == []                 # no free slot

    def test_midflight_refill_staggered(self):
        """Freed slots refill while the other slot keeps running."""
        s = SlotScheduler(2)
        s.submit_all(["a", "b", "c", "d"])
        seated = dict(s.admit())
        slot_a = next(k for k, v in seated.items() if v == "a")
        s.complete(slot_a)                     # "a" retires early
        refill = s.admit()
        assert refill == [(slot_a, "c")]       # recycled into a's slot
        assert s.is_active(1 - slot_a)         # "b" untouched mid-flight
        assert s.occupant(1 - slot_a) == "b"
        s.complete(1 - slot_a)
        assert dict(s.admit())[1 - slot_a] == "d"
        for slot, _ in s.active_items():
            s.complete(slot)
        assert s.done and s.completed == 4

    def test_arrival_gating(self):
        """Open-loop: a request is only admissible once the clock passes
        its arrival, even with free slots."""
        s = SlotScheduler(4)
        s.submit("early", arrival=0.0)
        s.submit("late", arrival=10.0)
        assert [r for _, r in s.admit(now=0.5)] == ["early"]
        assert s.admit(now=0.5) == []          # "late" not ripe
        assert s.next_arrival() == 10.0
        assert [r for _, r in s.admit(now=10.5)] == ["late"]

    def test_fifo_head_blocks_even_if_later_ripe(self):
        """FIFO is strict: a ripe request behind an unripe head waits."""
        s = SlotScheduler(4)
        s.submit("head", arrival=5.0)
        s.submit("ripe", arrival=0.0)
        assert s.admit(now=1.0) == []

    def test_errors_and_done(self):
        with pytest.raises(ValueError):
            SlotScheduler(0)
        s = SlotScheduler(1)
        with pytest.raises(ValueError):
            s.occupant(0)
        assert s.done                          # empty queue, no busy slots
        s.submit("a")
        assert not s.done


# ---------------------------------------------------------------------------
# LM engine
# ---------------------------------------------------------------------------

ARCHS = ["internlm2-1.8b", "mamba2-130m"]


@pytest.fixture(scope="module")
def lm_setup():
    out = {}
    for arch in ARCHS:
        cfg = reduced_config(arch)
        out[arch] = (cfg, lm.init_lm(jax.random.PRNGKey(0), cfg))
    return out


def _mixed_requests(cfg, *, seed=0, n=6):
    return lm_workload(cfg.vocab_size, n, prompt_lens=(3, 5, 9),
                       new_tokens=(1, 3, 6), seed=seed)


def _solo_outputs(params, cfg, requests):
    """Ground truth: each request served alone in a 1-slot engine."""
    outs = []
    for r in requests:
        eng = ServeEngine(params, cfg, batch_slots=1, max_seq=32)
        outs.append(eng.run([Request(prompt=r.prompt.copy(),
                                     max_new_tokens=r.max_new_tokens)]
                            )[0].output)
    return outs


@pytest.mark.parametrize("arch", ARCHS)
def test_mixed_batch_matches_solo_continuous(lm_setup, arch):
    """THE prefill regression: a short prompt batched with longer ones
    produces exactly the tokens it produces alone."""
    cfg, params = lm_setup[arch]
    reqs = _mixed_requests(cfg)
    solo = _solo_outputs(params, cfg, reqs)
    eng = ServeEngine(params, cfg, batch_slots=4, max_seq=32)
    done = eng.run([Request(prompt=r.prompt.copy(),
                            max_new_tokens=r.max_new_tokens) for r in reqs])
    by_id = {id(r): s for r, s in zip(reqs, solo)}
    assert len(done) == len(reqs)
    for r, s in zip(reqs, solo):
        batched = next(d for d in done
                       if np.array_equal(d.prompt, r.prompt)
                       and d.max_new_tokens == r.max_new_tokens
                       and d.output is not None)
        assert np.array_equal(batched.output, s), (
            f"{arch}: batched output diverged from solo")
    del by_id


@pytest.mark.parametrize("arch", ARCHS)
def test_mixed_batch_matches_solo_lockstep(lm_setup, arch):
    """The right-padded lockstep baseline is ALSO solo-exact (the fixed
    lm_prefill pad masking, per-slot lens and per-slot pos)."""
    cfg, params = lm_setup[arch]
    reqs = _mixed_requests(cfg, seed=1)
    solo = _solo_outputs(params, cfg, reqs)
    eng = ServeEngine(params, cfg, batch_slots=4, max_seq=32)
    done = eng.run_lockstep(reqs)
    assert [d is r for d, r in zip(done, reqs)]   # order preserved
    for d, s in zip(done, solo):
        assert np.array_equal(d.output, s)


@pytest.mark.parametrize("arch", ARCHS)
def test_lm_prefill_prompt_lens_matches_solo(lm_setup, arch):
    """Model-level check: right-padded lm_prefill with prompt_lens yields
    the same next-token logits and cache state as the unpadded prompt."""
    cfg, params = lm_setup[arch]
    rng = np.random.default_rng(0)
    short = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    long_ = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    toks = np.zeros((2, 9), np.int32)
    toks[0, :4], toks[1] = short, long_
    logits_b, cache_b = lm.lm_prefill(
        params, cfg, {"tokens": jnp.asarray(toks)}, 16,
        cache_dtype=jnp.float32, prompt_lens=jnp.asarray([4, 9], jnp.int32))
    logits_s, _ = lm.lm_prefill(
        params, cfg, {"tokens": jnp.asarray(short[None])}, 16,
        cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits_b[0]),
                               np.asarray(logits_s[0]),
                               rtol=1e-5, atol=1e-5)
    # and one decode step from the padded cache stays on the solo path
    nxt = jnp.argmax(logits_b, -1).astype(jnp.int32)
    step_logits, _ = lm.serve_step(params, cfg, cache_b, nxt,
                                   jnp.asarray([4, 9], jnp.int32))
    eng = ServeEngine(params, cfg, batch_slots=1, max_seq=16)
    solo = eng.run([Request(prompt=short, max_new_tokens=2)])[0].output
    assert int(jnp.argmax(step_logits[0])) == int(solo[1])


def test_zero_new_tokens_returned_both_paths(lm_setup):
    """max_new_tokens=0 must come back (empty output), not vanish."""
    cfg, params = lm_setup["mamba2-130m"]
    rng = np.random.default_rng(2)
    mk = lambda: [
        Request(prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
                max_new_tokens=m) for m in (0, 3, 0, 1)]
    for runner in ("run", "run_lockstep"):
        eng = ServeEngine(params, cfg, batch_slots=2, max_seq=32)
        done = getattr(eng, runner)(mk())
        assert len(done) == 4, f"{runner} dropped requests"
        sizes = sorted(d.output.shape[0] for d in done)
        assert sizes == [0, 0, 1, 3]
        assert all(d.latency is not None for d in done)
        assert eng.stats["tokens"] == 4


def test_stats_split_prefill_decode(lm_setup):
    """tokens_per_second divides by decode seconds only; prefill time is
    accounted separately (the old metric folded prefill into the rate)."""
    cfg, params = lm_setup["internlm2-1.8b"]
    eng = ServeEngine(params, cfg, batch_slots=2, max_seq=32)
    done = eng.run(_mixed_requests(cfg, n=4))
    st = eng.stats
    assert st["prefill_seconds"] > 0 and st["decode_seconds"] > 0
    assert st["seconds"] == pytest.approx(
        st["prefill_seconds"] + st["decode_seconds"])
    assert eng.tokens_per_second == pytest.approx(
        st["tokens"] / st["decode_seconds"])
    assert st["tokens"] == sum(d.output.shape[0] for d in done)
    assert st["prefill_tokens"] == sum(len(d.prompt) for d in done)
    assert 0 < eng.slot_utilization <= 1


def test_compile_cache_shared_across_engines(lm_setup):
    """Step functions are module-level jits: constructing more engines on
    the same config must not add compile-cache entries."""
    cfg, params = lm_setup["internlm2-1.8b"]
    reqs = lambda: _mixed_requests(cfg, n=3)
    ServeEngine(params, cfg, batch_slots=2, max_seq=32).run(reqs())
    before = engine_mod._decode_step._cache_size()
    ServeEngine(params, cfg, batch_slots=2, max_seq=32).run(reqs())
    ServeEngine(params, cfg, batch_slots=2, max_seq=32).run_lockstep(reqs())
    assert engine_mod._decode_step._cache_size() == before


def test_deterministic_across_slot_assignments(lm_setup):
    """Greedy outputs are a function of the request, not of slot count,
    submission order, or which slot a request lands in."""
    cfg, params = lm_setup["mamba2-130m"]
    reqs = _mixed_requests(cfg, seed=3, n=6)
    key = lambda d: (tuple(d.prompt.tolist()), d.max_new_tokens)
    ref = {key(d): d.output.tolist()
           for d in ServeEngine(params, cfg, batch_slots=4, max_seq=32).run(
               [Request(r.prompt.copy(), r.max_new_tokens) for r in reqs])}
    for slots, order in ((1, 1), (2, -1), (3, 1)):
        eng = ServeEngine(params, cfg, batch_slots=slots, max_seq=32)
        done = eng.run([Request(r.prompt.copy(), r.max_new_tokens)
                        for r in reqs[::order]])
        assert {key(d): d.output.tolist() for d in done} == ref


def test_validation_errors(lm_setup):
    cfg, params = lm_setup["internlm2-1.8b"]
    eng = ServeEngine(params, cfg, batch_slots=1, max_seq=8)
    with pytest.raises(ValueError, match="max_seq"):
        eng.run([Request(prompt=np.arange(6, dtype=np.int32),
                         max_new_tokens=4)])
    with pytest.raises(ValueError, match="empty"):
        eng.run([Request(prompt=np.zeros(0, np.int32), max_new_tokens=1)])


# ---------------------------------------------------------------------------
# surrogate fleet engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet():
    from repro.core.ensemble import init_ensemble
    from repro.models.surrogate import SurrogateConfig
    cfg = SurrogateConfig(height=32, width=16, base_channels=32)
    return cfg, init_ensemble(cfg, [0, 1])


def test_surrogate_band_matches_core_variability(fleet):
    """Served width == hi - lo of core.variability.compute_band over the
    two members; served mean == member mean."""
    from repro.core.variability import compute_band
    from repro.models.surrogate import apply_surrogate
    cfg, members = fleet
    q = surrogate_workload(cfg.cond_dim - 1, 4, rollout_lens=(3,), seed=5)[0]
    eng = SurrogateServeEngine(members, cfg, batch_slots=2, sigmas=2.0)
    done = eng.run([q])
    cond = jnp.asarray(np.stack([
        np.concatenate([q.params_vec, [t]]) for t in q.times]).astype(np.float32))
    preds = [np.asarray(apply_surrogate(
        jax.tree_util.tree_map(lambda x: x[m], members), cfg, cond))
        for m in range(2)]
    band = compute_band(preds, sigmas=2.0)
    np.testing.assert_allclose(done[0].mean, band.mean, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(done[0].width, band.hi - band.lo,
                               rtol=1e-5, atol=1e-5)


def test_surrogate_continuous_matches_lockstep(fleet):
    """Mixed rollout lengths: continuous batching returns every query with
    the same mean/width as the lockstep baseline, and recycles slots."""
    cfg, members = fleet
    wl = lambda: surrogate_workload(cfg.cond_dim - 1, 9,
                                    rollout_lens=(0, 1, 2, 5), seed=7)
    cont = SurrogateServeEngine(members, cfg, batch_slots=3)
    lock = SurrogateServeEngine(members, cfg, batch_slots=3)
    done_c, done_l = cont.run(wl()), lock.run_lockstep(wl())
    assert len(done_c) == len(done_l) == 9
    key = lambda q: (q.params_vec.tolist(), q.steps)
    for a, b in zip(sorted(done_c, key=key), sorted(done_l, key=key)):
        assert a.mean.shape == (a.steps, cfg.height, cfg.width, cfg.fields)
        np.testing.assert_allclose(a.mean, b.mean, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(a.width, b.width, rtol=1e-5, atol=1e-6)
    # zero-length rollout came back, not dropped
    assert any(d.steps == 0 and d.mean.shape[0] == 0 for d in done_c)
    # continuous wastes fewer slot-steps than the max(T) drain
    assert cont.slot_utilization >= lock.slot_utilization


def test_surrogate_frees_each_step_outputs_before_the_next(fleet,
                                                         monkeypatch):
    """No fleet step's device outputs outlive their copy to the host: the
    next step's outputs never share the device with them (peak memory)."""
    import weakref
    from repro.serving import surrogate_engine

    class CopyingNumpy:
        """numpy whose ``asarray`` copies, as the fetch from a chip does
        (on the CPU it is a view that keeps the device array alive)."""
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(a, *args, **kwargs):
            return np.array(a, *args, copy=True, **kwargs)
    monkeypatch.setattr(surrogate_engine, "np", CopyingNumpy())
    cfg, members = fleet
    eng = SurrogateServeEngine(members, cfg, batch_slots=2)
    held, live_at_dispatch = [], []
    dispatch = eng._dispatch

    def tracked(cond):
        live_at_dispatch.append(sum(r() is not None for r in held))
        out = dispatch(cond)
        held.extend(weakref.ref(a) for a in out)
        return out
    eng._dispatch = tracked
    eng.run(surrogate_workload(cfg.cond_dim - 1, 3, rollout_lens=(2, 4),
                               seed=3))
    assert len(live_at_dispatch) == eng.stats["steps"] > 1
    assert live_at_dispatch == [0] * len(live_at_dispatch)


def test_surrogate_requires_stacked_members(fleet):
    cfg, members = fleet
    with pytest.raises(ValueError, match="stacked"):
        SurrogateServeEngine(
            jax.tree_util.tree_map(lambda x: jnp.float32(0.0), members), cfg)
    eng = SurrogateServeEngine(members, cfg)
    assert eng.num_members == 2


# ---------------------------------------------------------------------------
# load generation
# ---------------------------------------------------------------------------

def test_poisson_arrivals_and_percentiles():
    rng = np.random.default_rng(0)
    closed = poisson_arrivals(5, None, rng)
    assert np.all(closed == 0.0)
    arr = poisson_arrivals(100, 50.0, rng)
    assert np.all(np.diff(arr) >= 0)           # cumulative
    assert 100 / 50.0 * 0.5 < arr[-1] < 100 / 50.0 * 2.0
    reqs = lm_workload(64, 20, rate_qps=25.0, seed=0)
    assert all(r.arrival >= 0 for r in reqs)
    assert any(r.arrival > 0 for r in reqs)
    for r in reqs:
        r.latency = 0.5
    pct = latency_percentiles(reqs)
    assert pct["p50"] == pct["p99"] == pytest.approx(0.5)
    assert latency_percentiles([]) == {"p50": 0.0, "p99": 0.0, "mean": 0.0}


def test_open_loop_latency_counts_queueing(fleet):
    """A late-arriving query's latency runs from its arrival, and arrivals
    gate admission: the engine idles until the clock catches up."""
    cfg, members = fleet
    eng = SurrogateServeEngine(members, cfg, batch_slots=2)
    qs = surrogate_workload(cfg.cond_dim - 1, 3, rollout_lens=(1,), seed=0)
    for i, q in enumerate(qs):
        q.arrival = 0.05 * i
    done = eng.run(qs)
    assert len(done) == 3
    assert all(d.latency >= 0 for d in done)
