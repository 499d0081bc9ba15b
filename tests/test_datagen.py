"""Streaming datagen subsystem: bit-identity, resume, multi-host, consumers."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.data.shards import MANIFEST_NAME, ShardedCompressedStore
from repro.datagen import (CodecPlan, ProductionPlan, ScenarioPlan,
                           ShardWriter, finalize, open_produced, produce,
                           produced_training_arrays, resolve_store,
                           scenario_conditions)
from repro.sim.ensemble import EnsembleSpec
from repro.sim.solver import run_simulation

SPEC = EnsembleSpec(name="rt", ny=16, nx=8, nsnaps=6, nsteps=30)
PLAN = ProductionPlan(
    scenarios=(ScenarioPlan("rt", SPEC, num_sims=3, seed=7),),
    codec=CodecPlan(tolerance=1e-3), shard_size=4)
TOL = 1e-3
N, SHARDS = 18, 5                      # 3 sims x 6 snaps, shard_size 4


def _shard_bytes(d, k):
    with open(os.path.join(d, f"shard_{k:05d}.bin"), "rb") as f:
        return f.read()


def _store_equal(a, b):
    assert (json.load(open(os.path.join(a, MANIFEST_NAME)))
            == json.load(open(os.path.join(b, MANIFEST_NAME))))
    for k in range(SHARDS):
        assert _shard_bytes(a, k) == _shard_bytes(b, k), f"shard {k} differs"


@pytest.fixture(scope="module")
def produced(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("produced"))
    report = produce(PLAN, root)
    return root, report


@pytest.fixture(scope="module")
def ref_fields():
    return [np.asarray(run_simulation(p, ny=SPEC.ny, nx=SPEC.nx,
                                      nsteps=SPEC.nsteps, nsnaps=SPEC.nsnaps))
            for p in PLAN.scenarios[0].params()]


@pytest.fixture(scope="module")
def ref_store_dir(ref_fields, tmp_path_factory):
    samples = np.concatenate([np.moveaxis(f, -1, 1) for f in ref_fields])
    root = str(tmp_path_factory.mktemp("refstore"))
    ShardedCompressedStore(list(samples), tolerances=[TOL] * len(samples),
                           root=root, shard_size=PLAN.shard_size)
    return root


# ---------------------------------------------------------------------------
# plan schema
# ---------------------------------------------------------------------------

def test_plan_roundtrip_and_hash():
    again = ProductionPlan.from_dict(PLAN.to_dict())
    assert again == PLAN
    assert again.config_hash() == PLAN.config_hash()
    other = dataclasses.replace(PLAN, shard_size=8)
    assert other.config_hash() != PLAN.config_hash()


@pytest.mark.parametrize("bad", [
    lambda: ProductionPlan(scenarios=()),
    lambda: ProductionPlan(scenarios=(
        ScenarioPlan("a/b", SPEC, num_sims=1),)),
    lambda: ProductionPlan(scenarios=(
        ScenarioPlan("a", SPEC, num_sims=0),)),
    lambda: ProductionPlan(scenarios=(ScenarioPlan("a", SPEC, num_sims=1),),
                           codec=CodecPlan(mode="nope")),
    lambda: ProductionPlan(scenarios=(ScenarioPlan("a", SPEC, num_sims=1),),
                           codec=CodecPlan(tolerance=0.0)),
    lambda: ProductionPlan(scenarios=(ScenarioPlan("a", SPEC, num_sims=1),
                                      ScenarioPlan("a", SPEC, num_sims=1))),
])
def test_plan_validation(bad):
    with pytest.raises((ValueError, KeyError)):
        bad().validate()


# ---------------------------------------------------------------------------
# streaming == in-memory, bit for bit
# ---------------------------------------------------------------------------

def test_produced_report(produced):
    _, report = produced
    r = report.scenario("rt")
    assert r.finalized and not r.preempted
    assert r.sims_run == 3 and r.shards_written == SHARDS
    assert r.samples_produced == N


def test_bit_identical_to_in_memory_build(produced, ref_store_dir):
    root, _ = produced
    _store_equal(os.path.join(root, "rt"), ref_store_dir)


def test_sequential_produce_identical(tmp_path, produced):
    """overlap=False runs the same ingest inline -> identical bytes."""
    root, _ = produced
    seq = str(tmp_path / "seq")
    assert produce(PLAN, seq, overlap=False).finalized
    _store_equal(os.path.join(seq, "rt"), os.path.join(root, "rt"))


def test_open_and_decode_error_bound(produced, ref_fields):
    root, _ = produced
    store = resolve_store(root)
    assert store.num_samples == N and store.shape == (6, 16, 8)
    batch = np.moveaxis(np.asarray(store.get_batch(np.arange(6))), 1, -1)
    assert np.max(np.abs(batch - ref_fields[0])) <= TOL * (1 + 1e-5)


# ---------------------------------------------------------------------------
# kill + resume
# ---------------------------------------------------------------------------

def test_kill_and_resume_bit_identical(tmp_path, produced):
    root, _ = produced
    rdir = str(tmp_path / "resume")
    first = produce(PLAN, rdir, max_shards=2).scenario("rt")
    assert first.preempted and not first.finalized
    assert first.shards_written == 2
    assert not os.path.exists(os.path.join(rdir, "rt", MANIFEST_NAME))
    mtimes = {k: os.stat(os.path.join(rdir, "rt", f"shard_{k:05d}.bin"))
              .st_mtime_ns for k in range(2)}

    second = produce(PLAN, rdir).scenario("rt")
    assert second.finalized
    assert second.shards_written == SHARDS - 2       # only unfinished shards
    assert second.sims_run == 2                       # sims 1,2 overlap them
    for k, m in mtimes.items():                       # finished: untouched
        assert os.stat(os.path.join(rdir, "rt",
                                    f"shard_{k:05d}.bin")).st_mtime_ns == m
    _store_equal(os.path.join(rdir, "rt"), os.path.join(root, "rt"))

    third = produce(PLAN, rdir).scenario("rt")        # fully done: no-op
    assert third.finalized and third.sims_run == 0
    assert third.shards_written == 0


def test_resume_refuses_different_plan(tmp_path):
    rdir = str(tmp_path / "mixed")
    produce(PLAN, rdir, max_shards=1)
    other = ProductionPlan(
        scenarios=(ScenarioPlan("rt", SPEC, num_sims=3, seed=8),),
        codec=CodecPlan(tolerance=TOL), shard_size=4)
    with pytest.raises(ValueError, match="refusing"):
        produce(other, rdir)


def test_crash_during_finalize_manifest(tmp_path, monkeypatch, produced):
    """A kill mid-manifest-write leaves no torn manifest; re-running
    produce() finalizes with zero recomputation."""
    root, _ = produced
    rdir = str(tmp_path / "crash")
    real_replace = os.replace

    def dying_replace(src, dst):
        if dst.endswith(MANIFEST_NAME):
            raise OSError("simulated kill mid-finalize")
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", dying_replace)
    with pytest.raises(OSError, match="simulated kill"):
        produce(PLAN, rdir)
    monkeypatch.undo()

    sdir = os.path.join(rdir, "rt")
    assert not os.path.exists(os.path.join(sdir, MANIFEST_NAME))
    rep = produce(PLAN, rdir).scenario("rt")          # all shards committed:
    assert rep.finalized and rep.sims_run == 0        # finalize only
    _store_equal(sdir, os.path.join(root, "rt"))


# ---------------------------------------------------------------------------
# multi-host partition
# ---------------------------------------------------------------------------

def test_multi_host_partition(tmp_path, produced):
    root, _ = produced
    mdir = str(tmp_path / "hosts")
    r0 = produce(PLAN, mdir, host_id=0, num_hosts=2).scenario("rt")
    assert not r0.finalized                           # host 1 still missing
    r1 = produce(PLAN, mdir, host_id=1, num_hosts=2).scenario("rt")
    assert r1.finalized
    assert r0.shards_written + r1.shards_written == SHARDS
    assert finalize(PLAN, mdir)                       # idempotent
    _store_equal(os.path.join(mdir, "rt"), os.path.join(root, "rt"))


# ---------------------------------------------------------------------------
# fixed-rate codec path
# ---------------------------------------------------------------------------

def test_fixed_rate_production(tmp_path, ref_fields):
    from repro.compression import decode_fixed_rate, encode_fixed_rate
    import jax.numpy as jnp
    plan = ProductionPlan(
        scenarios=(ScenarioPlan("rt", SPEC, num_sims=2, seed=7),),
        codec=CodecPlan(mode="fixed_rate", bits_per_value=9),
        shard_size=4)
    rdir = str(tmp_path / "fr")
    assert produce(plan, rdir).finalized
    store = resolve_store(rdir)
    got = np.asarray(store.get_batch(np.array([0])))[0]
    want = np.asarray(decode_fixed_rate(encode_fixed_rate(
        jnp.asarray(np.moveaxis(ref_fields[0], -1, 1)[0]), 9)))
    assert (got == want).all()


# ---------------------------------------------------------------------------
# consumers
# ---------------------------------------------------------------------------

def test_conditions_from_provenance(produced):
    root, _ = produced
    cond = scenario_conditions(os.path.join(root, "rt"))
    assert cond.shape == (N, 7)
    # time channel cycles 0..1 per sim
    assert cond[0, -1] == 0.0 and cond[5, -1] == 1.0 and cond[6, -1] == 0.0


def test_produced_training_arrays(produced, ref_fields):
    root, _ = produced
    cond, fields = produced_training_arrays(root)
    assert cond.shape == (N, 7) and fields.shape == (N, 16, 8, 6)
    assert np.max(np.abs(fields[:6] - ref_fields[0])) <= TOL * (1 + 1e-5)


def test_open_produced_handle(produced):
    root, _ = produced
    ds = open_produced(root)
    assert ds.names == ["rt"]
    assert ds.store("rt").num_samples == N
    prov = ds.provenance("rt")
    assert prov["plan_hash"] == PLAN.config_hash()
    assert len(prov["sims"]) == 3
    assert prov["plan"]["codec"]["tolerance"] == TOL


def test_train_on_produced_path(produced):
    from repro.data.store import channels_last
    from repro.models.surrogate import SurrogateConfig
    from repro.train.loop import TrainConfig, train_surrogate
    root, _ = produced
    cond = scenario_conditions(os.path.join(root, "rt"))
    cfg = SurrogateConfig(height=16, width=8, base_channels=8)
    tc = TrainConfig(epochs=1, batch_size=4, lr=1e-3, log_every=1)
    _, losses = train_surrogate(cfg, tc, cond, os.path.join(root, "rt"),
                                target_transform=channels_last)
    assert len(losses) == 4 and np.isfinite([l for _, l in losses]).all()


def test_resolve_store_errors(tmp_path):
    with pytest.raises(FileNotFoundError, match="no produced dataset"):
        resolve_store(str(tmp_path))
    produce(PLAN, str(tmp_path / "part"), max_shards=1)
    with pytest.raises(FileNotFoundError, match="unfinished"):
        resolve_store(str(tmp_path / "part"))


# ---------------------------------------------------------------------------
# writer contract
# ---------------------------------------------------------------------------

def _fake_cf(n, nb=4, w=2):
    """Minimal batched CompressedField-shaped records for writer tests."""
    from repro.compression import CompressedField
    import jax.numpy as jnp
    return CompressedField(
        payload=jnp.ones((n, nb, w), jnp.int32),
        emax=jnp.zeros((n, nb), jnp.int32),
        nplanes=jnp.full((n, nb), 2 * w, jnp.int32),
        shape=(4, 4), padded_shape=(4, 4))


def test_writer_incomplete_coverage_fails(tmp_path):
    w = ShardWriter(str(tmp_path), shard_size=4, num_samples=8,
                    target_shards=[0, 1])
    w.put(0, _fake_cf(6))                 # shard 1 never completes
    with pytest.raises(RuntimeError, match="incomplete shards \\[1\\]"):
        w.close()


def test_writer_drops_non_target_samples(tmp_path):
    done = []
    w = ShardWriter(str(tmp_path), shard_size=4, num_samples=8,
                    target_shards=[1], on_shard=lambda k, m: done.append(k))
    w.put(0, _fake_cf(8))
    w.close()
    assert done == [1]
    assert not os.path.exists(str(tmp_path / "shard_00000.bin"))
    assert os.path.exists(str(tmp_path / "shard_00001.bin"))


def test_writer_worker_error_is_sticky_and_joins(tmp_path):
    """A worker failure re-raises the ORIGINAL error (not an
    incomplete-shards report) and never leaks the worker thread."""
    def bad_cb(k, meta):
        raise ValueError("disk exploded")

    w = ShardWriter(str(tmp_path), shard_size=4, num_samples=8,
                    target_shards=[0, 1], on_shard=bad_cb)
    w.put(0, _fake_cf(8))
    with pytest.raises(ValueError, match="disk exploded"):
        w.close()
    assert not w._thread.is_alive()
    w.abort()                                         # idempotent, no raise


def test_writer_abort_joins_worker(tmp_path):
    w = ShardWriter(str(tmp_path), shard_size=4, num_samples=8,
                    target_shards=[0, 1])
    w.put(0, _fake_cf(3))                             # incomplete on purpose
    w.abort()
    assert not w._thread.is_alive()
    w.abort()


def test_config_hash_ignores_unused_codec_fields():
    """Settings the selected codec mode never reads cannot rename the
    dataset (and so cannot spuriously refuse a resume)."""
    a = dataclasses.replace(PLAN, codec=CodecPlan(tolerance=1e-3))
    b = dataclasses.replace(PLAN, codec=CodecPlan(tolerance=1e-3,
                                                  bits_per_value=5))
    assert a.config_hash() == b.config_hash()
    fr = dataclasses.replace(PLAN, codec=CodecPlan(mode="fixed_rate",
                                                   bits_per_value=9))
    assert fr.config_hash() != a.config_hash()
