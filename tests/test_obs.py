"""Telemetry subsystem: span tracer, metrics registry, JAX profiling hooks.

Covers the obs contracts the rest of the repo now leans on:
  * span nesting / attributes / thread separation, and bounded event buffers;
  * Chrome trace-event export is schema-valid (Perfetto-loadable) and the
    JSONL stream parses line by line;
  * disabled mode is the shared null object -- no allocation, no clock read;
  * one span call reaches both timelines: the tracer's events and the host
    plane of a running JAX profiler capture, with or without a tracer;
  * metrics merge/snapshot round-trips; ``IoStats`` is ONE class (the
    ``repro.data.store`` import is a re-export) with the historical
    attribute API intact;
  * the recompile watcher flags an injected shape-change retrace and stays
    quiet in steady state;
  * end-to-end: a traced ``train_surrogate`` run separates compile from
    steady-state and emits per-step spans; a traced serving run emits
    per-query spans + slot-occupancy samples, and its loop phases partition
    the run's wall time on both timelines; ``tools/trace_report``
    summarizes the stream into a per-stage table.
"""
import glob
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.obs import jaxprof
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import (Counter, Histogram, IoStats, MetricsRegistry)
from repro.obs.trace import NULL_SPAN, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture
def clean_telemetry():
    """Fresh global tracer/registry around a test, restored afterwards."""
    obs_trace.shutdown(write=False)
    obs_metrics.get_registry().reset()
    yield
    obs_trace.shutdown(write=False)
    obs_metrics.get_registry().reset()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_nesting_depth_and_attrs(self):
        t = Tracer(run="t")
        with t.span("outer", cat="a", k=1):
            with t.span("inner", cat="b") as sp:
                sp.set(found=3)
                assert t.depth() == 2
        evs = t.events()
        # children exit first, so order is inner, outer
        assert [e["name"] for e in evs] == ["inner", "outer"]
        inner, outer = evs
        assert inner["depth"] == 1 and outer["depth"] == 0
        assert inner["args"] == {"found": 3}
        assert outer["args"] == {"k": 1}
        # the child's interval nests inside the parent's
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9

    def test_span_records_error_type(self):
        t = Tracer()
        with pytest.raises(ValueError):
            with t.span("boom"):
                raise ValueError("x")
        assert t.events()[0]["args"]["error"] == "ValueError"
        assert t.depth() == 0                  # stack unwound

    def test_thread_safety_and_per_thread_stacks(self):
        t = Tracer()
        n = 200
        barrier = threading.Barrier(2)         # overlap => distinct idents

        def work():
            barrier.wait()
            for _ in range(n):
                with t.span("w"):
                    assert t.depth() == 1      # never sees the other thread
        threads = [threading.Thread(target=work) for _ in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        evs = t.events()
        assert len(evs) == 2 * n
        assert len({e["tid"] for e in evs}) == 2

    def test_max_events_bounded(self):
        t = Tracer(max_events=5)
        for i in range(9):
            t.instant(f"e{i}")
        assert len(t.events()) == 5
        assert t.dropped == 4
        assert t.chrome_trace()["otherData"]["dropped"] == 4

    def test_chrome_trace_schema(self, tmp_path):
        t = Tracer(trace_dir=str(tmp_path), run="r")
        with t.span("s", cat="c", k=1):
            pass
        t.instant("i")
        t.counter("c", v=2)
        doc = t.chrome_trace()
        assert isinstance(doc["traceEvents"], list)
        assert doc["displayTimeUnit"] == "ms"
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("X", "i", "C")
            assert isinstance(ev["name"], str)
            assert isinstance(ev["ts"], float)
            assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
            if ev["ph"] == "X":
                assert ev["dur"] >= 0.0
            if ev["ph"] == "i":
                assert ev["s"] == "t"
        paths = t.write()
        loaded = json.load(open(paths["trace"]))       # valid JSON on disk
        assert len(loaded["traceEvents"]) == 3
        lines = [json.loads(l) for l in open(paths["events"])]
        assert [l["type"] for l in lines] == ["span", "instant", "counter"]
        assert all("ts_s" in l and "thread" in l for l in lines)

    def test_complete_and_rel(self):
        t = Tracer()
        import time
        t0 = time.perf_counter()
        t.complete("x", t.rel(t0), 0.25, cat="c", step=3)
        (e,) = t.events()
        assert e["ph"] == "X" and abs(e["dur"] - 0.25) < 1e-9
        assert e["args"]["step"] == 3

    def test_disabled_mode_is_null_object(self, clean_telemetry):
        assert not obs_trace.enabled()
        assert obs_trace.span("anything", k=1) is NULL_SPAN
        obs_trace.instant("nothing")           # no-ops, no error
        obs_trace.counter("nothing", v=1)
        with obs_trace.span("still nothing") as sp:
            assert sp.set(a=1) is sp

    def test_configure_shutdown_writes(self, tmp_path, clean_telemetry):
        obs_trace.configure(str(tmp_path), run="rr")
        assert obs_trace.enabled()
        with obs_trace.span("s"):
            pass
        paths = obs_trace.shutdown()
        assert os.path.exists(paths["trace"])
        assert not obs_trace.enabled()


# ---------------------------------------------------------------------------
# metrics registry + IoStats
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_histogram_summary_percentiles(self):
        h = Histogram(window=100)
        for v in range(1, 101):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 100 and s["min"] == 1 and s["max"] == 100
        assert abs(s["p50"] - 50.5) < 1e-9
        assert s["p99"] > 99

    def test_histogram_window_keeps_exact_totals(self):
        h = Histogram(window=4)
        for v in range(10):
            h.observe(v)
        assert h.count == 10 and h.total == sum(range(10))
        assert list(h.samples) == [6, 7, 8, 9]

    def test_registry_snapshot_and_merge_roundtrip(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("c").add(2)
        a.gauge("g").set(1.5)
        a.histogram("h").observe(1.0)
        b.counter("c").add(3)
        b.gauge("g").set(2.5)
        b.histogram("h").observe(3.0)
        a.merge(b)
        snap = a.snapshot()
        assert snap["c"] == 5
        assert snap["g"] == 2.5                # gauge: last write wins
        assert snap["h"]["count"] == 2 and snap["h"]["mean"] == 2.0
        json.dumps(snap)                       # JSON-safe by contract
        a.reset()
        assert a.snapshot()["c"] == 0 and a.snapshot()["h"] == {"count": 0}

    def test_registry_type_conflict_raises(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_iostats_single_implementation(self):
        from repro.data.store import IoStats as StoreIoStats
        assert StoreIoStats is IoStats

    def test_iostats_attribute_api_compat(self):
        st = IoStats()
        st.bytes_read += 10                    # historical dataclass idiom
        st.batches += 1
        assert st.bytes_read == 10 and st.batches == 1
        st.account(5, read_seconds=0.5, decode_seconds=0.5)
        assert st.bytes_read == 15 and st.batches == 2
        assert abs(st.throughput_mbs() - 15 / 1e6) < 1e-12
        assert "bytes_read=15" in repr(st)

    def test_iostats_merge_reset_snapshot(self):
        a, b = IoStats(), IoStats()
        a.account(100, read_seconds=1.0)
        b.account(50, decode_seconds=2.0, batches=3)
        a.merge(b)
        snap = a.snapshot()
        assert snap["bytes_read"] == 150 and snap["batches"] == 4
        assert snap["read_seconds"] == 1.0 and snap["decode_seconds"] == 2.0
        a.reset()
        assert a == IoStats()

    def test_stores_account_through_iostats(self):
        from repro.data.store import RawArrayStore
        store = RawArrayStore(np.zeros((8, 4, 4, 2), np.float32))
        store.get_batch(np.arange(4))
        assert store.stats.batches == 1 and store.stats.bytes_read > 0
        store.stats = IoStats()                # benchmark reset idiom
        assert store.stats.batches == 0


# ---------------------------------------------------------------------------
# recompile watcher
# ---------------------------------------------------------------------------

class TestRecompileWatcher:
    def test_flags_injected_shape_change(self, clean_telemetry):
        @jax.jit
        def f(x):
            return x * 2

        f(jnp.zeros(4))                        # warmup compile
        reg = MetricsRegistry()
        w = jaxprof.RecompileWatcher(registry=reg)
        w.watch("f", f)
        f(jnp.zeros(4))
        assert w.check() == []                 # steady state: quiet
        f(jnp.zeros(8))                        # injected shape change
        (ev,) = w.check()
        assert ev.name == "f" and ev.growth == 1
        assert reg.counter("jax.recompiles").value == 1
        assert w.check() == []                 # baseline absorbed the growth

    def test_rebase_absorbs_warmup(self):
        @jax.jit
        def g(x):
            return x + 1

        w = jaxprof.RecompileWatcher(registry=MetricsRegistry())
        w.watch("g", g)
        g(jnp.zeros(3))                        # expected first compile
        w.rebase()
        assert w.check() == []

    def test_watch_rejects_non_jitted(self):
        with pytest.raises(TypeError):
            jaxprof.RecompileWatcher().watch("plain", lambda x: x)

    def test_jit_cache_size(self):
        assert jaxprof.jit_cache_size(lambda x: x) is None
        fn = jax.jit(lambda x: x)
        before = jaxprof.jit_cache_size(fn)
        fn(jnp.zeros(2))
        assert jaxprof.jit_cache_size(fn) == before + 1


# ---------------------------------------------------------------------------
# profiler capture
# ---------------------------------------------------------------------------

class TestProfilerTrace:
    def test_not_requested_is_a_no_op(self, monkeypatch):
        def never(*a, **k):
            raise AssertionError("profiler touched")
        monkeypatch.setattr(jax.profiler, "start_trace", never)
        with jaxprof.profiler_trace(None) as on:
            assert on is False

    def test_requested_trace_that_cannot_start_raises(self, tmp_path,
                                                      monkeypatch):
        def broken(log_dir, *a, **k):
            raise RuntimeError("no profiler plugin")
        monkeypatch.setattr(jax.profiler, "start_trace", broken)
        with pytest.raises(RuntimeError, match="no profiler plugin"):
            with jaxprof.profiler_trace(str(tmp_path)):
                pytest.fail("body ran without a trace")

    def test_requested_trace_that_cannot_stop_raises(self, tmp_path,
                                                     monkeypatch):
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda log_dir, *a, **k: calls.append(log_dir))

        def broken():
            raise RuntimeError("stop failed")
        monkeypatch.setattr(jax.profiler, "stop_trace", broken)
        with pytest.raises(RuntimeError, match="stop failed"):
            with jaxprof.profiler_trace(str(tmp_path)) as on:
                assert on is True
        assert calls == [str(tmp_path)]


def host_events(log_dir) -> list:
    """``(name, line, start_ns, end_ns, stats)`` of every event on the host
    planes of the capture under ``log_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, line.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


def inside(child, parents) -> bool:
    """Whether ``child`` lies within one of ``parents`` on its line."""
    return any(p[1] == child[1] and p[2] <= child[2] and child[3] <= p[3]
               for p in parents)


class TestSpansOnTheProfiler:
    def test_span_without_tracer_is_an_annotation(self, tmp_path,
                                                  clean_telemetry):
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs_trace.span("obs.outer", cat="x", k=3) as sp:
                assert sp is not NULL_SPAN
                sp.set(found=7)
                with obs_trace.span("obs.inner"):
                    time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        assert obs_trace.span("after") is NULL_SPAN   # capture over
        evs = {e[0]: e for e in host_events(tmp_path)}
        assert evs["obs.outer"][4] == {"k": 3, "found": 7}
        assert inside(evs["obs.inner"], [evs["obs.outer"]])
        assert evs["obs.inner"][3] - evs["obs.inner"][2] >= 2e6

    def test_tracer_span_reaches_both_sinks(self, tmp_path, clean_telemetry):
        tracer = obs_trace.configure(None, run="both")
        jax.profiler.start_trace(str(tmp_path / "xp"))
        try:
            with obs_trace.span("obs.both", step=2) as sp:
                sp.set(done=True)
        finally:
            jax.profiler.stop_trace()
        (ev,) = tracer.events()
        assert ev["name"] == "obs.both"
        assert ev["args"] == {"step": 2, "done": True}
        (xp,) = [e for e in host_events(tmp_path / "xp")
                 if e[0] == "obs.both"]
        assert xp[4]["step"] == 2
        # both sinks time the same interval
        assert abs((xp[3] - xp[2]) * 1e-9 - ev["dur"]) < 1e-3

    def test_certify_spans_nest(self, clean_telemetry):
        from repro.compression import get_codec
        from repro.core import find_tolerance_batch

        tracer = obs_trace.configure(None, run="certify")
        xs = np.random.default_rng(0).normal(size=(2, 8, 8)).astype(
            np.float32)
        passes = obs_metrics.get_registry().counter("tolerance.stats_passes")
        before = passes.value
        res = find_tolerance_batch(xs, np.full(2, 0.02, np.float32))
        get_codec("fixed_accuracy", backend="jnp").encode_batch(
            jnp.asarray(xs), jnp.asarray(res.tolerance))
        evs = {e["name"]: e for e in tracer.events()}
        search, readback = evs["tolerance.search_batch"], evs[
            "tolerance.readback"]
        assert readback["depth"] == search["depth"] + 1
        assert search["ts"] <= readback["ts"]
        assert (readback["ts"] + readback["dur"]
                <= search["ts"] + search["dur"] + 1e-9)
        assert search["args"]["max_iterations"] >= 1
        # one stats pass of the whole stack per iteration of the search
        assert passes.value - before == search["args"]["max_iterations"]
        assert evs["codec.encode_batch"]["args"]["samples"] == 2


# ---------------------------------------------------------------------------
# end-to-end: traced training and serving
# ---------------------------------------------------------------------------

class TestEndToEnd:
    def test_train_loop_compile_steady_split(self, tmp_path, clean_telemetry):
        from repro.models.surrogate import SurrogateConfig
        from repro.train.loop import TrainConfig, train_surrogate

        obs_trace.configure(str(tmp_path), run="train")
        cfg = SurrogateConfig(height=16, width=8, base_channels=8)
        data = np.random.default_rng(0).normal(
            size=(32, 16, 8, 6)).astype(np.float32)
        cond = np.random.default_rng(1).normal(
            size=(32, cfg.cond_dim)).astype(np.float32)
        tc = TrainConfig(epochs=2, batch_size=8, log_every=2)
        train_surrogate(cfg, tc, cond, lambda i: jnp.asarray(data[i]),
                        len(data))

        snap = obs_metrics.get_registry().snapshot()
        assert snap["train.compile_seconds"] > 0
        assert snap["train.steps"] == 8
        # steady-state histogram excludes the compile step
        assert snap["train.step_seconds"]["count"] == 7
        assert (snap["train.step_seconds"]["max"]
                < snap["train.compile_seconds"])
        assert snap["train.steady_seconds"] > 0
        # steps are timed whole, window by window: the histogram sums to
        # the steady seconds, and the windows cover every steady step
        assert (abs(snap["train.step_seconds"]["mean"] * 7
                    - snap["train.steady_seconds"]) < 1e-9)

        evs = obs_trace.get_tracer().events()
        assert sum(1 for e in evs if e["name"] == "train.compile") == 1
        windows = [e for e in evs if e["name"] == "train.window"]
        assert [e["args"]["step"] for e in windows] == [2, 4, 6, 8]
        assert sum(e["args"]["steps"] for e in windows) == 7
        assert (abs(sum(e["args"]["seconds"] for e in windows)
                    - snap["train.steady_seconds"]) < 1e-9)
        assert all(e["args"]["steps_per_s"] > 0 and abs(
            e["args"]["steps_per_s"] * e["args"]["seconds"]
            - e["args"]["steps"]) < 1e-6 for e in windows)
        fetches = [e for e in evs if e["name"] == "train.fetch"]
        assert fetches                          # prefetch worker traced
        assert {e["tid"] for e in fetches} != {windows[0]["tid"]}

    def test_surrogate_serving_telemetry(self, tmp_path, clean_telemetry):
        from repro.core.ensemble import init_ensemble
        from repro.models.surrogate import SurrogateConfig
        from repro.serving import SurrogateQuery, SurrogateServeEngine

        obs_trace.configure(str(tmp_path), run="serve")
        cfg = SurrogateConfig(height=16, width=8, base_channels=8)
        engine = SurrogateServeEngine(init_ensemble(cfg, [0, 1]), cfg,
                                      batch_slots=2)
        queries = [SurrogateQuery(np.zeros(cfg.cond_dim - 1, np.float32),
                                  np.linspace(0, 1, t).astype(np.float32))
                   for t in (2, 3, 4)]
        done = engine.run(queries)
        assert len(done) == 3

        snap = obs_metrics.get_registry().snapshot()
        assert snap["surrogate_serve.queries"] == 3
        occ = snap["surrogate_serve.slot_occupancy"]
        assert occ["count"] == engine.stats["steps"]
        assert 0 < occ["mean"] <= 1.0
        lat = snap["surrogate_serve.query_latency_seconds"]
        assert lat["count"] == 3 and lat["p99"] >= lat["p50"] > 0

        evs = obs_trace.get_tracer().events()
        reqs = [e for e in evs if e["name"] == "surrogate_serve.query"]
        assert len(reqs) == 3
        assert all(e["args"]["queue_wait_s"] >= 0 for e in reqs)
        assert [e for e in evs if e["ph"] == "C"]   # occupancy counter track

    @staticmethod
    def _tiny_engine():
        from repro.core.ensemble import init_ensemble
        from repro.models.surrogate import SurrogateConfig
        from repro.serving import SurrogateQuery, SurrogateServeEngine

        cfg = SurrogateConfig(height=16, width=8, base_channels=8)
        engine = SurrogateServeEngine(init_ensemble(cfg, [0, 1]), cfg,
                                      batch_slots=2)

        def queries():
            return [SurrogateQuery(np.zeros(cfg.cond_dim - 1, np.float32),
                                   np.linspace(0, 1, t).astype(np.float32),
                                   arrival=0.03 + 0.02 * i)
                    for i, t in enumerate((2, 3, 1, 4))]
        engine.run(queries())                   # compiles the fleet step
        return engine, queries

    def test_serve_phases_on_the_profiler_host_plane(self, tmp_path,
                                                     clean_telemetry):
        engine, queries = self._tiny_engine()
        steps = engine.stats["steps"]
        jax.profiler.start_trace(str(tmp_path))
        try:
            engine.run(queries())
        finally:
            jax.profiler.stop_trace()
        steps = engine.stats["steps"] - steps
        evs = host_events(tmp_path)
        by = {p: [e for e in evs if e[0] == "surrogate_serve." + p]
              for p in ("fleet_step", "dispatch", "device_wait", "fetch",
                        "collect", "no_work")}
        assert len(by["fleet_step"]) == steps
        assert all(e[4]["members"] == 2 and 1 <= e[4]["active"] <= 2
                   for e in by["fleet_step"])
        for p in ("dispatch", "device_wait", "fetch"):
            assert len(by[p]) == steps
            assert all(inside(e, by["fleet_step"]) for e in by[p])
        assert by["collect"] and by["no_work"]
        assert not any(inside(e, by["fleet_step"])
                       for e in by["collect"] + by["no_work"])

    def test_serve_phases_in_the_tracer(self, tmp_path, clean_telemetry):
        engine, queries = self._tiny_engine()
        obs_trace.configure(str(tmp_path), run="phases")
        steps = engine.stats["steps"]
        engine.run(queries())
        steps = engine.stats["steps"] - steps
        evs = obs_trace.get_tracer().events()
        names = [e["name"] for e in evs]
        for p in ("fleet_step", "dispatch", "device_wait", "fetch"):
            assert names.count("surrogate_serve." + p) == steps
        assert "surrogate_serve.collect" in names
        step = next(e for e in evs if e["name"] == "surrogate_serve.fleet_step")
        assert step["args"]["members"] == 2 and step["depth"] == 0
        assert all(e["depth"] == 1 for e in evs if e["name"] in (
            "surrogate_serve.dispatch", "surrogate_serve.device_wait",
            "surrogate_serve.fetch"))

    def test_serve_phase_counters_partition_the_run(self, clean_telemetry):
        from repro.serving.surrogate_engine import PHASES

        engine, queries = self._tiny_engine()
        reg = obs_metrics.get_registry()

        def phase_seconds():
            snap = reg.snapshot()
            return sum(snap[f"surrogate_serve.{p}_seconds"] for p in PHASES)
        before = phase_seconds()
        t0 = time.perf_counter()
        engine.run(queries())
        wall = time.perf_counter() - t0
        grown = phase_seconds() - before
        assert 0.9 * wall <= grown <= wall

    def test_trace_report_summarizes(self, tmp_path, clean_telemetry):
        import trace_report

        obs_trace.configure(str(tmp_path), run="r")
        t = obs_trace.get_tracer()
        for _ in range(3):
            with t.span("stage.outer", cat="x"):
                with t.span("stage.inner", cat="x"):
                    pass
        t.instant("recompile", fn="f", before=1, after=2)
        paths = obs_trace.shutdown()

        rep = trace_report.summarize(trace_report.load_events(paths["events"]))
        assert rep["stages"]["stage.outer"]["count"] == 3
        assert rep["stages"]["stage.inner"]["count"] == 3
        # self time excludes the nested child
        outer = rep["stages"]["stage.outer"]
        inner = rep["stages"]["stage.inner"]
        assert outer["self_s"] <= outer["total_s"] - inner["total_s"] + 1e-6
        assert rep["instants"]["recompile"]["count"] == 1
        # the Chrome trace parses to the same stage counts (depth recomputed)
        rep2 = trace_report.summarize(
            trace_report.load_events(paths["trace"]))
        assert rep2["stages"]["stage.outer"]["count"] == 3

    def test_benchmark_env_provenance(self):
        sys.path.insert(0, REPO)
        from benchmarks.run import env_provenance
        env = env_provenance()
        assert env["jax"] and env["backend"] and env["device_count"] >= 1
        assert env["hostname"] and env["python"]
        json.dumps(env)
