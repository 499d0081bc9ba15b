"""Sharded compressed store + batched Algorithm 1 + shard-aware loading."""
import numpy as np
import pytest

from repro.core import CompressedArrayStore, find_tolerance, find_tolerance_batch
from repro.data import PrefetchLoader, ShardAwareLoader, ShardedCompressedStore
from repro.data.shards import MANIFEST_NAME
from repro.distributed.sharding import owned_shards


@pytest.fixture(scope="module")
def field_stack():
    r = np.random.default_rng(11)
    t = np.linspace(0, 1, 48)
    xx, yy = np.meshgrid(np.linspace(0, 1, 16), t)
    return np.stack([(np.sin(6 * xx + 0.2 * i) + 0.3 * np.cos(14 * yy * xx)
                      + 0.05 * r.standard_normal((6, 48, 16)))
                     .astype(np.float32) for i in range(37)])


@pytest.fixture(scope="module")
def tolerances(field_stack):
    r = np.random.default_rng(5)
    return (0.01 * (1 + r.random(len(field_stack)))).astype(np.float32)


@pytest.fixture(scope="module")
def disk_store(field_stack, tolerances, tmp_path_factory):
    root = tmp_path_factory.mktemp("shards")
    return ShardedCompressedStore(list(field_stack), tolerances=tolerances,
                                  root=str(root), shard_size=8)


# ---------------------------------------------------------------------------
# store correctness
# ---------------------------------------------------------------------------

def test_get_batch_bit_exact_with_per_sample_store(field_stack, tolerances,
                                                   disk_store):
    """Same tolerances => byte-identical decoded batches (both store kinds)."""
    ref = CompressedArrayStore(list(field_stack),
                               tolerances=[float(t) for t in tolerances])
    idx = np.random.default_rng(0).integers(0, len(field_stack), 16)
    got = np.asarray(disk_store.get_batch(idx))
    want = np.asarray(ref.get_batch(idx))
    assert got.shape == want.shape
    assert (got == want).all()
    # identical logical footprint too: same streams, different container
    assert disk_store.stored_bytes == ref.stored_bytes


def test_error_bound_holds_per_sample(field_stack, tolerances, disk_store):
    out = np.asarray(disk_store.get_batch(np.arange(len(field_stack))))
    errs = np.abs(out - field_stack).max(axis=(1, 2, 3))
    assert (errs <= tolerances).all()


def test_in_memory_matches_disk(field_stack, tolerances, disk_store):
    mem = ShardedCompressedStore(list(field_stack), tolerances=tolerances,
                                 shard_size=8)
    idx = np.arange(0, len(field_stack), 3)
    assert (np.asarray(mem.get_batch(idx))
            == np.asarray(disk_store.get_batch(idx))).all()


def test_manifest_roundtrip(disk_store, field_stack):
    """save -> open reattaches bit-exactly from manifest + shard files."""
    import json, os
    reopened = ShardedCompressedStore.open(disk_store.root)
    assert reopened.num_samples == disk_store.num_samples
    assert reopened.shape == disk_store.shape
    assert reopened.num_shards == disk_store.num_shards
    assert (reopened.widths == disk_store.widths).all()
    assert reopened.stored_bytes == disk_store.stored_bytes
    assert reopened.manifest() == disk_store.manifest()
    idx = np.asarray([0, 7, 8, 36])          # spans shard boundaries + tail
    assert (np.asarray(reopened.get_batch(idx))
            == np.asarray(disk_store.get_batch(idx))).all()
    with open(os.path.join(disk_store.root, MANIFEST_NAME)) as f:
        m = json.load(f)
    assert m["format"] == "repro-shards-v1"
    assert sum(s["count"] for s in m["shards"]) == disk_store.num_samples


def test_io_stats_accounting(field_stack, tolerances):
    st = ShardedCompressedStore(list(field_stack), tolerances=tolerances,
                                shard_size=8)
    st.get_batch(np.arange(4))
    assert st.stats.batches == 1
    assert st.stats.bytes_read > 0
    assert st.ratio > 1.0


# ---------------------------------------------------------------------------
# batched Algorithm 1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("errors,zero_sample", [
    ([0.02, 0.005, 0.05, 0.001, 0.5, 0.0001, 0.01, 0.03, 1e-12], None),
    ([0.02, 0.005, 0.5, 1e-12, 0.0001, 10.0], 2),
], ids=["mixed", "zero_sample_and_extremes"])
def test_find_tolerance_batch_matches_per_sample(field_stack, errors,
                                                 zero_sample):
    # 1e-12 is unreachable (lift round-trip noise ~1e-8): exercises the
    # search-exhausted path, which must report the last *evaluated* t; an
    # all-zero sample saturates the ratio at once
    xs = np.array(field_stack[:len(errors)])
    if zero_sample is not None:
        xs[zero_sample] = 0.0
    br = find_tolerance_batch(xs, errors)
    for i, (x, e) in enumerate(zip(xs, errors)):
        ref = find_tolerance(x, e)
        assert np.isclose(br.tolerance[i], ref.tolerance, rtol=1e-6), \
            f"sample {i}: batch {br.tolerance[i]} vs ref {ref.tolerance}"
        assert int(br.iterations[i]) == ref.iterations
        assert np.isclose(br.ratio[i], ref.ratio, rtol=1e-5)
        assert np.isclose(br.compression_l1[i], ref.compression_l1,
                          rtol=1e-5, atol=1e-9)
    results = br.as_results()
    assert len(results) == len(errors)
    for r, e in zip(results, errors):
        if e == 1e-12:
            assert r.compression_l1 == float("inf") and r.ratio == 1.0
        else:
            assert r.compression_l1 <= r.model_l1


def test_find_tolerance_batch_single_dispatch(field_stack):
    """The search is one compiled call: the jit cache gains exactly one
    entry for a 32-sample stack, regardless of N."""
    from repro.core.tolerance import _search_batch
    xs = np.repeat(field_stack[:8], 4, axis=0)          # (32, ...)
    _search_batch._clear_cache()
    find_tolerance_batch(xs, [0.01] * 32)
    assert _search_batch._cache_size() == 1
    find_tolerance_batch(xs * 0.5, [0.02] * 32)          # same shapes: cached
    assert _search_batch._cache_size() == 1


# ---------------------------------------------------------------------------
# shard-aware loading
# ---------------------------------------------------------------------------

def test_owned_shards_partition_hosts():
    for num_shards, hosts in ((10, 3), (8, 4), (5, 1), (7, 7)):
        all_ids = np.concatenate([owned_shards(num_shards, h, hosts)
                                  for h in range(hosts)])
        assert sorted(all_ids.tolist()) == list(range(num_shards))
        sizes = [len(owned_shards(num_shards, h, hosts))
                 for h in range(hosts)]
        assert max(sizes) - min(sizes) <= 1


def test_shard_aware_loader_locality_and_coverage():
    ld = ShardAwareLoader(num_samples=64, batch_size=8, samples_per_shard=8,
                          seed=4)
    batches = ld.take(8)
    seen = np.concatenate(batches)
    assert sorted(seen.tolist()) == list(range(64))
    # every batch stays within ceil(bs/shard)+1 = 2 shards
    for b in batches:
        assert len(set(b // 8)) <= 2


def test_shard_aware_loader_host_ownership():
    hosts = 2
    per_host = [np.concatenate(ShardAwareLoader(
        64, 8, 8, seed=9, host_id=h, num_hosts=hosts).take(4))
        for h in range(hosts)]
    allidx = np.concatenate(per_host)
    assert sorted(allidx.tolist()) == list(range(64))
    # each host's samples come only from the shards it owns
    for h, idx in enumerate(per_host):
        assert set(idx // 8) == set(owned_shards(8, h, hosts).tolist())


def test_shard_aware_loader_rejects_starved_host():
    """A host owning zero shards (or too few samples for one batch) must
    fail at construction, not hang in __iter__."""
    with pytest.raises(ValueError, match="owns 0 samples"):
        ShardAwareLoader(64, 8, 32, host_id=3, num_hosts=4)
    with pytest.raises(ValueError, match="owns 4 samples"):
        ShardAwareLoader(36, 8, 4, host_id=8, num_hosts=9)
    # same split is fine when partial batches are allowed
    ld = ShardAwareLoader(36, 8, 4, host_id=8, num_hosts=9,
                          drop_remainder=False)
    assert ld.steps_per_epoch == 1


def test_shard_aware_loader_resumes_mid_epoch():
    a = ShardAwareLoader(48, 8, 8, seed=6)
    it = iter(a)
    for _ in range(3):
        next(it)
    state = a.state()
    rest_a = [next(it) for _ in range(4)]            # crosses into epoch 1
    b = ShardAwareLoader(48, 8, 8, seed=0)
    b.restore(state)
    rest_b = [next(iter(b)) for _ in range(4)]
    for x, y in zip(rest_a, rest_b):
        assert np.array_equal(x, y)


def test_prefetch_propagates_store_exceptions(field_stack, tolerances):
    st = ShardedCompressedStore(list(field_stack), tolerances=tolerances,
                                shard_size=8)

    def fetch(idx):
        if (idx >= 30).any():
            raise ValueError("corrupt shard")
        return st.get_batch(idx)

    pf = PrefetchLoader(iter([np.arange(4), np.arange(30, 34)]), fetch=fetch)
    assert np.asarray(next(pf)).shape[0] == 4
    with pytest.raises(ValueError, match="corrupt shard"):
        next(pf)
        next(pf)                                    # depth-2 queue: drain
    pf.close()


def test_prefetched_sharded_pipeline_end_to_end(disk_store):
    """Loader -> prefetch -> store: batches arrive in loader order."""
    ld = ShardAwareLoader.for_store(disk_store, 8, seed=2)
    want_idx = ShardAwareLoader.for_store(disk_store, 8, seed=2).take(3)
    pf = PrefetchLoader(iter(ld), fetch=disk_store.get_batch, depth=2)
    got = [np.asarray(next(pf)) for _ in range(3)]
    pf.close()
    for idx, g in zip(want_idx, got):
        assert (g == np.asarray(disk_store.get_batch(idx))).all()


# ---------------------------------------------------------------------------
# atomic manifest commit
# ---------------------------------------------------------------------------

def test_manifest_write_is_atomic_under_crash(field_stack, tolerances,
                                              tmp_path, monkeypatch):
    """A kill mid-manifest-write must leave either the old manifest or none
    -- never a torn JSON document."""
    import json as _json
    import os
    from repro.data.shards import atomic_write_json

    root = str(tmp_path / "store")
    ShardedCompressedStore(list(field_stack), tolerances=tolerances,
                           root=root, shard_size=8)
    path = os.path.join(root, MANIFEST_NAME)
    before = open(path, "rb").read()

    real_dump = _json.dump

    def dying_dump(obj, f, **kw):
        f.write('{"format": "torn')           # partial bytes hit the temp
        f.flush()
        raise OSError("simulated kill mid-write")

    monkeypatch.setattr(_json, "dump", dying_dump)
    with pytest.raises(OSError, match="simulated kill"):
        atomic_write_json(path, {"format": "new"})
    monkeypatch.setattr(_json, "dump", real_dump)

    assert open(path, "rb").read() == before      # old manifest intact
    store = ShardedCompressedStore.open(root)     # and still consistent
    assert store.num_samples == len(field_stack)

    # crash between temp write and rename: same guarantee
    real_replace = os.replace
    monkeypatch.setattr(os, "replace",
                        lambda *a: (_ for _ in ()).throw(
                            OSError("simulated kill pre-rename")))
    with pytest.raises(OSError, match="pre-rename"):
        atomic_write_json(path, {"format": "new"})
    monkeypatch.setattr(os, "replace", real_replace)
    assert open(path, "rb").read() == before


def test_find_tolerance_halving_path(field_stack):
    """Initial guess overshoots (realized L1 > e) -> halve downward; the
    result must be the first halved tolerance that meets the bound."""
    x = field_stack[0]
    e = 0.003          # t0 = 256e/1.089 realizes L1 well above e: overshoot
    r = find_tolerance(x, e)
    t0 = (4.0 ** 2) * e / 1.089
    assert r.tolerance < t0                              # went down, not up
    assert r.compression_l1 <= e
    assert r.iterations > 1
    # the accepted t is t0 / 2^(iterations - 1): one evaluation per halving
    assert np.isclose(r.tolerance, t0 / 2.0 ** (r.iterations - 1), rtol=1e-6)
    br = find_tolerance_batch(x[None], [e])
    assert np.isclose(br.tolerance[0], r.tolerance, rtol=1e-6)
    assert int(br.iterations[0]) == r.iterations


def test_find_tolerance_no_solution_freezes_last_t(field_stack):
    """Unreachable bound: 8 halvings all fail; the result reports the last
    *evaluated* tolerance (t0 / 2^(max_iters-1)), inf L1 and ratio 1."""
    x = field_stack[1]
    e = 1e-12
    r = find_tolerance(x, e, max_iters=8)
    t0 = (4.0 ** 2) * e / 1.089
    assert r.compression_l1 == float("inf")
    assert r.ratio == 1.0
    assert r.iterations == 8
    assert np.isclose(r.tolerance, t0 / 2.0 ** 7, rtol=1e-6)
    br = find_tolerance_batch(x[None], [e], max_iters=8)
    assert br.compression_l1[0] == np.float32("inf")
    assert br.ratio[0] == 1.0
    assert np.isclose(br.tolerance[0], r.tolerance, rtol=1e-6)


def test_find_tolerance_zero_sample_saturates(field_stack):
    """An all-zero sample compresses to headers only: the ratio saturates
    immediately and the doubling search stops on the saturation rule, not
    by exhausting max_iters."""
    x = np.zeros_like(field_stack[0])
    r = find_tolerance(x, 0.01)
    assert r.compression_l1 == 0.0
    assert r.iterations < 8                              # stopped early
    br = find_tolerance_batch(x[None], [0.01])
    assert np.isclose(br.tolerance[0], r.tolerance, rtol=1e-6)
    assert int(br.iterations[0]) == r.iterations
    assert np.isclose(br.ratio[0], r.ratio, rtol=1e-5)


def test_find_tolerance_batch_freeze_t_is_per_sample(field_stack):
    """Samples terminating at different iterations keep their own final
    tolerances -- the masked while_loop must not advance a finished
    sample's t while others continue (mixed fast/slow/no-solution stack)."""
    errors = [10.0, 0.003, 1e-12, 0.02]
    xs = np.array(field_stack[:len(errors)])
    xs[0] = 0.0                       # terminates in 2 iters (saturation)
    br = find_tolerance_batch(xs, errors)
    for i, e in enumerate(errors):
        r = find_tolerance(xs[i], e)
        assert np.isclose(br.tolerance[i], r.tolerance, rtol=1e-6), i
        assert int(br.iterations[i]) == r.iterations, i
