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
import dataclasses

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


# ---------------------------------------------------------------------------
# prefill length buckets and the engine's phases
# ---------------------------------------------------------------------------

BUCKET_ARCHS = ["internlm2-1.8b", "mamba2-130m", "falcon-h1-34b"]


@pytest.fixture(scope="module")
def bucket_setup():
    out = {}
    for arch in BUCKET_ARCHS:
        # a name of its own: these tests count this config's compiles
        cfg = dataclasses.replace(reduced_config(arch), name=arch + "-buckets")
        out[arch] = (cfg, lm.init_lm(jax.random.PRNGKey(1), cfg))
    return out


def _heavy_tailed(cfg, n, seed, max_prompt=30):
    rng = np.random.default_rng(seed)
    lens = np.clip(np.round(4 * np.exp(rng.standard_normal(n))), 1,
                   max_prompt).astype(int)
    return [Request(rng.integers(0, cfg.vocab_size, p).astype(np.int32),
                    int(rng.integers(1, 6)), arrival=0.002 * i)
            for i, p in enumerate(lens)]


@pytest.mark.parametrize("arch", BUCKET_ARCHS)
def test_bucketed_batch_matches_solo(bucket_setup, arch):
    """Prompts spread over four buckets, four slots: each request's tokens
    equal those it gets alone in a one-slot engine."""
    cfg, params = bucket_setup[arch]
    buckets = (4, 8, 16, 32)
    reqs = _heavy_tailed(cfg, 8, seed=4)
    assert len({ServeEngine(params, cfg, 1, 40, buckets).bucket(
        len(r.prompt)) for r in reqs}) >= 3
    batched = ServeEngine(params, cfg, batch_slots=4, max_seq=40,
                          prefill_buckets=buckets).run(
        [Request(r.prompt.copy(), r.max_new_tokens, r.arrival)
         for r in reqs])
    by_prompt = {tuple(d.prompt.tolist()): d.output for d in batched}
    for r in reqs:
        solo = ServeEngine(params, cfg, batch_slots=1, max_seq=40,
                           prefill_buckets=buckets).run(
            [Request(r.prompt.copy(), r.max_new_tokens)])[0].output
        assert np.array_equal(by_prompt[tuple(r.prompt.tolist())], solo)


def test_heavy_tailed_mix_compiles_only_the_buckets(bucket_setup):
    """Warm-up compiles one prefill program per bucket and one decode step;
    a heavy-tailed prompt mix then compiles nothing, and the recompile
    watcher, which watches prefill too, flags nothing."""
    from repro.obs import jaxprof
    cfg, params = bucket_setup["falcon-h1-34b"]
    cfg = dataclasses.replace(cfg, name="falcon-h1-heavy-tail")
    buckets = (4, 8, 16, 32)
    eng = ServeEngine(params, cfg, batch_slots=4, max_seq=40,
                      prefill_buckets=buckets)
    sizes = lambda: (engine_mod._prefill._cache_size(),
                     engine_mod._decode_step._cache_size())
    before = sizes()
    eng.warmup()
    warm = sizes()
    assert warm[0] - before[0] == len(buckets)
    assert warm[1] - before[1] == 1
    reqs = _heavy_tailed(cfg, 24, seed=9)
    assert max(len(r.prompt) for r in reqs) > 16
    done = eng.run(reqs)
    assert len(done) == len(reqs)
    assert sizes() == warm
    assert jaxprof.get_watcher().check() == []
    assert {"serve.prefill", "serve.decode_step"} <= set(
        jaxprof.get_watcher().sizes())


def test_prompt_longer_than_every_bucket_is_refused(bucket_setup):
    cfg, params = bucket_setup["internlm2-1.8b"]
    eng = ServeEngine(params, cfg, batch_slots=1, max_seq=40,
                      prefill_buckets=(8, 16))
    with pytest.raises(ValueError, match="bucket"):
        eng.run([Request(np.zeros(17, np.int32), 2)])
    with pytest.raises(ValueError, match="max_seq"):
        ServeEngine(params, cfg, max_seq=16, prefill_buckets=(8, 32))


def test_lm_phases_partition_the_run_wall_time(bucket_setup):
    """The ``lm_serve.<phase>_seconds`` counters of one run sum to its wall
    time, and the token counters count prompts, bucket padding and the
    decode steps' tokens."""
    import time
    from repro.obs.metrics import get_registry
    from repro.serving.engine import PHASES
    cfg, params = bucket_setup["falcon-h1-34b"]
    eng = ServeEngine(params, cfg, batch_slots=3, max_seq=40,
                      prefill_buckets=(8, 16, 32))
    eng.warmup()
    reqs = _heavy_tailed(cfg, 10, seed=2)
    for i, r in enumerate(reqs):
        r.arrival = 0.03 * (i + 1)           # the first pass finds no work
    reg = get_registry()
    names = [f"lm_serve.{p}_seconds" for p in PHASES] + [
        "lm_serve.prefill_tokens", "lm_serve.prefill_pad_tokens",
        "lm_serve.decode_tokens"]
    snap = lambda: {k: reg.snapshot().get(k, 0.0) for k in names}
    s0 = snap()
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    d = {k: v - s0[k] for k, v in snap().items()}
    phases = sum(d[f"lm_serve.{p}_seconds"] for p in PHASES)
    # the run's own set-up and final check lie outside the phases
    assert phases == pytest.approx(wall, abs=1e-2)
    assert phases < wall
    assert all(d[f"lm_serve.{p}_seconds"] > 0 for p in PHASES)
    plens = [len(r.prompt) for r in done]
    assert d["lm_serve.prefill_tokens"] == sum(plens)
    assert d["lm_serve.prefill_pad_tokens"] == sum(
        eng.bucket(p) - p for p in plens)
    assert d["lm_serve.decode_tokens"] == sum(len(r.output) - 1
                                              for r in done)


def test_kept_logits_stop_at_their_budget(bucket_setup):
    """A request keeps ``keep_logits`` rows -- its prefill's last row, then
    one a decode step -- and no more, read back from its own slot: the rows
    equal the first rows of a request that keeps every one, and a request
    that keeps none holds none."""
    cfg, params = bucket_setup["falcon-h1-34b"]
    eng = ServeEngine(params, cfg, batch_slots=3, max_seq=40,
                      prefill_buckets=(8, 16, 32))
    reqs = _heavy_tailed(cfg, 6, seed=5)
    for r in reqs:
        r.max_new_tokens = 5
    mk = lambda keep: [Request(r.prompt.copy(), 5, r.arrival,
                               keep_logits=keep) for r in reqs]
    full, part, none = (eng.run(mk(k)) for k in (5, 2, 0))
    by_prompt = {tuple(d.prompt.tolist()): d for d in full}
    for d in part:
        rows = by_prompt[tuple(d.prompt.tolist())].logits
        assert len(rows) == 5 and len(d.logits) == 2
        np.testing.assert_array_equal(np.stack(d.logits), np.stack(rows[:2]))
    assert all(d.logits is None for d in none)
