"""Device-resident compressed training: store parity, Codec registry, fused
train step, exact resume and certification on the device backend.

The load-bearing contract: a ``DeviceResidentCompressedStore`` decodes
bit-identically to the ``ShardedCompressedStore`` it was built from (same
records, padded words decode as zero planes, the per-block nplanes mask only
zeroes planes the encoder already truncated), so host-streaming and
device-resident training consume byte-for-byte the same targets.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.compression import (FixedAccuracyCodec, FixedRateCodec, get_codec,
                               codec_names, decode_batch,
                               encode_fixed_accuracy_batch,
                               encode_fixed_rate_batch)
from repro.data import (DeviceResidentCompressedStore, ShardedCompressedStore,
                        channels_last)
from repro.models.surrogate import SurrogateConfig
from repro.train.loop import TrainConfig, train_surrogate
from repro.train.source import (DeviceResidentSource, HostStreamSource,
                                make_batch_source, make_loader)

CFG = SurrogateConfig(height=48, width=16, base_channels=8)


def _samples(rng, n=24, scale_spread=True, c=6, h=48, w=16):
    """Channels-first samples with per-sample scale spread -> mixed payload
    widths across the store."""
    scales = np.logspace(-1, 1, n) if scale_spread else np.ones(n)
    t = np.linspace(0, 1, h)[:, None] + np.linspace(0, 1, w)[None, :]
    return [(s * (np.sin(5 * t + i) + 0.1 * rng.standard_normal((h, w))))
            .astype(np.float32)[None].repeat(c, 0)
            for i, s in enumerate(scales)]


# ---------------------------------------------------------------------------
# codec registry
# ---------------------------------------------------------------------------

def test_codec_registry_names_and_errors():
    assert {"fixed_accuracy", "fixed_rate"} <= set(codec_names())
    with pytest.raises(KeyError):
        get_codec("nope")
    with pytest.raises(ValueError):
        get_codec("fixed_accuracy", backend="cuda")
    assert isinstance(get_codec("fixed_accuracy"), FixedAccuracyCodec)
    assert isinstance(get_codec("fixed_rate", bits_per_value=8),
                      FixedRateCodec)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fixed_accuracy_codec_matches_free_functions(rng, backend):
    xs = jnp.asarray(np.stack(_samples(rng, n=6)))
    tols = jnp.asarray(np.logspace(-3, -1, 6), jnp.float32)
    codec = get_codec("fixed_accuracy", backend=backend)
    cf = codec.encode_batch(xs, tols)
    ref_cf = encode_fixed_accuracy_batch(xs, tols)
    for a, b in zip(jax.tree_util.tree_leaves(cf),
                    jax.tree_util.tree_leaves(ref_cf)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(codec.decode_batch(cf)),
                          np.asarray(decode_batch(ref_cf)))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fixed_rate_codec_matches_free_functions(rng, backend):
    xs = jnp.asarray(np.stack(_samples(rng, n=4)))
    codec = get_codec("fixed_rate", bits_per_value=10, backend=backend)
    cf = codec.encode_batch(xs)
    ref_cf = encode_fixed_rate_batch(xs, 10)
    for a, b in zip(jax.tree_util.tree_leaves(cf),
                    jax.tree_util.tree_leaves(ref_cf)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(codec.decode_batch(cf)),
                          np.asarray(decode_batch(ref_cf)))


def test_codec_from_plan_roundtrip():
    from repro.compression import codec_from_plan
    from repro.datagen import CodecPlan
    fa = codec_from_plan(CodecPlan(mode="fixed_accuracy", tolerance=2e-3))
    assert fa.name == "fixed_accuracy" and fa.tolerance == 2e-3
    fr = codec_from_plan(CodecPlan(mode="fixed_rate", bits_per_value=9))
    assert fr.name == "fixed_rate" and fr.bits_per_value == 9
    assert fr.backend == "pallas"


# ---------------------------------------------------------------------------
# device store parity with the sharded store
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("via", ["memory", "disk"])
def test_device_store_bit_identical_to_sharded(rng, tmp_path, via):
    samples = _samples(rng)
    tols = np.logspace(-3, -1, len(samples)).astype(np.float32)
    root = str(tmp_path / "store") if via == "disk" else None
    store = ShardedCompressedStore(samples, tolerances=tols, root=root,
                                   shard_size=8)
    if via == "disk":
        store = ShardedCompressedStore.open(root)
    dev = store.as_device_resident()
    assert dev.num_samples == store.num_samples
    assert dev.shard_size == store.shard_size
    assert dev.stored_bytes == store.stored_bytes      # logical accounting
    for idx in (np.arange(8), rng.integers(0, len(samples), 17),
                np.array([3])):
        a = np.asarray(store.get_batch(idx))
        b = np.asarray(dev.get_batch(idx))
        assert np.array_equal(a, b)
    assert dev.stats.bytes_read == 0                   # zero host bytes


def test_device_store_from_samples_mixed_widths(rng):
    """True per-block nplanes path: per-sample tolerances spread widths
    within one gather-decode call; must still match the sharded store."""
    samples = _samples(rng, n=12)
    tols = np.logspace(-4, 0, 12).astype(np.float32)
    sharded = ShardedCompressedStore(samples, tolerances=tols, shard_size=4)
    dev = DeviceResidentCompressedStore.from_samples(samples, tols,
                                                     shard_size=4)
    # per-block plane counts genuinely vary inside this batch
    assert len(np.unique(np.asarray(dev.nplanes))) > 2
    idx = np.array([0, 11, 5, 2, 7])                   # mixes widths
    assert np.array_equal(np.asarray(sharded.get_batch(idx)),
                          np.asarray(dev.get_batch(idx)))


def test_device_store_zero_plane_and_full_plane_samples(rng):
    """All-zero samples (nplanes 0 everywhere) and near-lossless samples
    (full plane counts) coexisting in one resident store."""
    from repro.compression.transform import TOTAL_PLANES
    samples = _samples(rng, n=6)
    samples[2] = np.zeros_like(samples[2])
    tols = np.full(6, 1e-1, np.float32)
    tols[4] = 1e-12                                    # drive planes to max
    sharded = ShardedCompressedStore(samples, tolerances=tols, shard_size=3)
    dev = DeviceResidentCompressedStore.from_samples(samples, tols,
                                                     shard_size=3)
    npl = np.asarray(dev.nplanes)
    assert npl[2].max() == 0 and npl[4].max() == TOTAL_PLANES
    idx = np.arange(6)
    batch = np.asarray(dev.get_batch(idx))
    assert np.array_equal(batch, np.asarray(sharded.get_batch(idx)))
    assert np.all(batch[2] == 0.0)


def test_device_store_rejects_inconsistent_arrays(rng):
    with pytest.raises(ValueError):
        DeviceResidentCompressedStore(
            np.zeros((4, 3, 2), np.int32), np.zeros((4, 2), np.int32),
            np.zeros((4, 3), np.int32), (4, 4), (4, 4),
            np.zeros(4), np.zeros(4))


# ---------------------------------------------------------------------------
# BatchSource seam
# ---------------------------------------------------------------------------

def test_make_batch_source_dispatch(rng):
    samples = _samples(rng, n=8)
    tols = np.full(8, 0.05, np.float32)
    sharded = ShardedCompressedStore(samples, tolerances=tols, shard_size=4)
    cond = rng.standard_normal((8, CFG.cond_dim)).astype(np.float32)
    assert isinstance(make_batch_source(sharded, cond), HostStreamSource)
    src = make_batch_source(sharded.as_device_resident(), cond,
                            target_transform=channels_last)
    assert isinstance(src, DeviceResidentSource)
    idx = np.array([1, 6, 3])
    fetched = src.fetch(idx)                           # indices only
    assert fetched.dtype == jnp.int32 and fetched.shape == (3,)
    c, t = src.gather(fetched, src.store.payload, src.store.emax,
                      src.store.nplanes, src.conditions)
    assert t.shape == (3, 48, 16, 6)                   # channels-last applied
    np.testing.assert_array_equal(np.asarray(c), cond[idx])


def test_make_loader_shard_aware_for_device_store(rng):
    from repro.data.loader import ShardAwareLoader
    samples = _samples(rng, n=16)
    store = ShardedCompressedStore(samples, tolerances=np.full(16, 0.05),
                                   shard_size=4)
    dev = store.as_device_resident()
    lh = make_loader(store, None, 4, seed=3)
    ld = make_loader(dev, None, 4, seed=3)
    assert isinstance(ld, ShardAwareLoader)
    # identical batch order across backends -> interchangeable resume state
    assert all(np.array_equal(a, b)
               for a, b in zip(lh.take(8), ld.take(8)))


# ---------------------------------------------------------------------------
# fused training: host-vs-device equivalence, exact resume, certification
# ---------------------------------------------------------------------------

def _train_setup(rng, n=48):
    fields = rng.standard_normal((n, 48, 16, 6)).astype(np.float32)
    cond = rng.standard_normal((n, CFG.cond_dim)).astype(np.float32)
    samples = np.transpose(fields, (0, 3, 1, 2))
    store = ShardedCompressedStore(samples, tolerances=np.full(n, 0.1),
                                   shard_size=16)
    return cond, store


def test_device_training_matches_host(rng):
    """Same store bytes, same loader order, same seed: the fused
    gather->decode step must train to (numerically) the same model."""
    cond, store = _train_setup(rng)
    tc = TrainConfig(epochs=2, batch_size=16, lr=1e-3, seed=7, log_every=1)
    ph, lh = train_surrogate(CFG, tc, cond, store,
                             target_transform=channels_last)
    pd, ld = train_surrogate(CFG, tc, cond, store.as_device_resident(),
                             target_transform=channels_last)
    assert [s for s, _ in lh] == [s for s, _ in ld]
    for a, b in zip(jax.tree_util.tree_leaves(ph),
                    jax.tree_util.tree_leaves(pd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2)
    # losses trace the same trajectory
    np.testing.assert_allclose([l for _, l in lh], [l for _, l in ld],
                               atol=1e-2)


def test_device_resume_bit_identical(rng, tmp_path):
    """tests/test_resume.py semantics on the device-resident backend: kill
    at step 5 (mid-epoch), resume from the step-4 checkpoint, end bitwise
    equal to the uninterrupted run."""
    cond, store = _train_setup(rng)
    dev = store.as_device_resident()
    base = dict(epochs=3, batch_size=16, lr=1e-3, seed=7, log_every=1)
    ref_p, ref_l = train_surrogate(CFG, TrainConfig(**base), cond, dev,
                                   target_transform=channels_last)
    tck = TrainConfig(**base, ckpt_dir=str(tmp_path / "dev"),
                      ckpt_every_steps=2)
    train_surrogate(CFG, dataclasses.replace(tck, max_steps=5), cond, dev,
                    target_transform=channels_last)
    res_p, res_l = train_surrogate(CFG, tck, cond, dev,
                                   target_transform=channels_last)
    for a, b in zip(jax.tree_util.tree_leaves(ref_p),
                    jax.tree_util.tree_leaves(res_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ref_tail = [l for s, l in ref_l if s > 5]
    res_tail = [l for s, l in res_l if s > 5]
    assert ref_tail == res_tail


def test_device_ensemble_matches_host_ensemble(rng):
    """Shared resident payload, per-member gathers inside the vmapped step."""
    from repro.core.ensemble import train_ensemble
    cond, store = _train_setup(rng, n=32)
    tc = TrainConfig(epochs=2, batch_size=8, lr=1e-3, log_every=2)
    seeds = (0, 1, 2)
    rh = train_ensemble(CFG, tc, cond, store, seeds,
                        target_transform=channels_last)
    rd = train_ensemble(CFG, tc, cond, store.as_device_resident(), seeds,
                        target_transform=channels_last)
    for a, b in zip(jax.tree_util.tree_leaves(rh.params),
                    jax.tree_util.tree_leaves(rd.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2)


@pytest.mark.slow
def test_certify_tolerance_device_resident():
    """The end-to-end certification pipeline on the device backend keeps its
    benign/degraded discrimination (smoke-scale synthetic study)."""
    from repro.core.ensemble import certify_tolerance
    from repro.sim.synthetic import synthetic_study
    cfg, cond, fields = synthetic_study()
    tc = TrainConfig(epochs=3, batch_size=8, lr=3e-3, log_every=10)
    res = certify_tolerance(cfg, tc, cond, fields, eval_conditions=cond,
                            eval_targets=fields, seeds=(0, 1, 2),
                            multiples=(0.5, 16.0), shard_size=16,
                            device_resident=True)
    assert res.max_benign is not None
    assert res.max_benign.multiple == 0.5
    degraded = [c for c in res.candidates if c.multiple == 16.0]
    assert degraded and not degraded[0].benign
