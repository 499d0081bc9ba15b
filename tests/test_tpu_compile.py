"""Compile the main path's TPU programs for a described v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
chip that is described and not attached.  This catches what interpret mode
cannot (layouts Mosaic refuses, VMEM over-use, a kernel silently swapped for
the jnp oracle) without a chip.  The topology is described only inside the
module fixture below, so importing this file loads no TPU library and every
test worker collects the same tests.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compression.transform import MAX_WORDS, TOTAL_PLANES
from repro.data import channels_last
from repro.kernels import zfp_codec
from repro.models.surrogate import SurrogateConfig, init_surrogate
from repro.train import source
from repro.train.optimizer import AdamConfig, adam_init

# one training batch of the paper's RT surrogate: 64 samples x 6 fields x
# (96/4)*(32/4) blocks
BATCH = 64
SAMPLE = (6, 96, 32)
BLOCKS_PER_SAMPLE = 6 * (96 // 4) * (32 // 4)
NB = BATCH * BLOCKS_PER_SAMPLE                   # 73,728
RESIDENT_SAMPLES = 5100                          # 100 simulations x 51 snapshots
# one fixed-accuracy encode chunk of certification: 17 PCHIP snapshots x 6
# fields x 512 x 512, 1,671,168 blocks
CERTIFY_CHUNK = (17, 6, 512, 512)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases(sharding):
    i32, f32 = jnp.int32, jnp.float32
    payload = _spec((NB, MAX_WORDS), i32, sharding)
    per_block = _spec((NB,), i32, sharding)
    blocks = _spec((NB, 16), f32, sharding)
    rows, _ = zfp_codec.fa_slab_rows(NB)
    return {
        "decode": lambda: zfp_codec.zfp_decode_blocks.lower(
            payload, per_block, bits_per_value=TOTAL_PLANES),
        "decode_fa": lambda: zfp_codec.zfp_decode_blocks_fa.lower(
            payload, per_block, per_block),
        "encode": lambda: zfp_codec.zfp_encode_blocks.lower(
            blocks, bits_per_value=TOTAL_PLANES),
        "encode_fa": lambda: zfp_codec.zfp_encode_blocks_fa.lower(
            _spec((16, NB), f32, sharding), _spec((NB,), f32, sharding)),
        "search_stats_fa": lambda: zfp_codec.zfp_search_stats_fa.lower(
            _spec((16, rows, 128), f32, sharding),
            _spec((rows, 128), f32, sharding),
            _spec((rows, 128), i32, sharding)),
    }


@pytest.mark.parametrize("kernel", ["decode", "decode_fa", "encode",
                                    "encode_fa", "search_stats_fa"])
def test_codec_kernel_compiles_for_v5e(one_chip, kernel):
    compiled = _kernel_cases(one_chip)[kernel]().compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_certify_chunk_encode_is_lane_dense(one_chip):
    """A certification chunk's fixed-accuracy encode carries the kernel's
    custom call under the name ``bench/metrics/encode_roofline.py`` reads,
    and no operand padded out to 128 lanes: the block-major (nb, 16) layout
    took 4 GiB of temporaries here."""
    from repro.compression import api
    compiled = api._encode_fa_kernel.lower(
        _spec(CERTIFY_CHUNK, jnp.float32, one_chip),
        _spec(CERTIFY_CHUNK[:1], jnp.float32, one_chip)).compile()
    assert re.search(r"%zfp_encode_blocks_fa\.\d+ = ", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_search_is_one_stats_kernel_per_pass(one_chip):
    """Algorithm 1's search over one PCHIP member (51 snapshots) runs each
    pass as the stats kernel's custom call, under a name that
    ``bench/metrics/encode_roofline.py`` does not count as the encode, on
    the coefficient-major layout: the block-major search took 2,375 MiB of
    temporaries here."""
    from repro.core.tolerance import _search_batch
    member = (51,) + CERTIFY_CHUNK[1:]
    compiled = _search_batch.lower(
        _spec(member, jnp.float32, one_chip),
        _spec(member[:1], jnp.float32, one_chip), 2, 8).compile()
    text = compiled.as_text()
    assert re.search(r"%zfp_search_stats_fa\.\d+ = ", text)
    assert "%zfp_encode_blocks_fa." not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_fused_train_step_compiles_with_decode_kernel(one_chip):
    """The device-resident train step at the paper's widths carries the
    compiled fixed-accuracy decode kernel, not the jnp oracle."""
    cfg, opt_cfg = SurrogateConfig(), AdamConfig()

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = jax.eval_shape(lambda: init_surrogate(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(lambda p: adam_init(p, opt_cfg), params)
    n, nb = RESIDENT_SAMPLES, BLOCKS_PER_SAMPLE
    lowered = source._fused_step.lower(
        on_chip(params), on_chip(opt_state),
        _spec((BATCH,), jnp.int32, one_chip),
        _spec((n, nb, MAX_WORDS), jnp.int32, one_chip),
        _spec((n, nb), jnp.int32, one_chip),
        _spec((n, nb), jnp.int32, one_chip),
        _spec((n, cfg.cond_dim), jnp.float32, one_chip),
        cfg=cfg, opt_cfg=opt_cfg, padded_shape=SAMPLE, shape=SAMPLE,
        transform=channels_last)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_falcon_h1_stage_serves_on_one_chip(one_chip):
    """The ``serve-falcon-h1`` cell's programs at their timed shapes (one
    8-layer stage at published widths, 16 slots of 5,120 positions, the
    largest prefill bucket) compile for one v5e; both update the donated
    cache in place, and the decode step holds no stack-sized temporary and,
    with the weights and cache it is handed, fits the chip's 16 GiB."""
    import json
    import os
    from bench.loops import lm_serve
    from repro.models import lm
    from repro.serving import engine
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs",
                           "falcon-h1-34b-8l.json")) as f:
        conf = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           "lm-longctx-poisson.json")) as f:
        longest = max(json.load(f)["prefill_buckets"])
    cfg = lm_serve.arch_config(conf)
    slots, max_seq = conf["serving"]["slots"], conf["serving"]["max_seq"]

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: lm.init_lm(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: lm.init_cache(cfg, slots, max_seq, jnp.float32)))
    ids = _spec((slots,), jnp.int32, one_chip)
    decode = engine._decode_step.lower(params, cfg, cache, ids,
                                       ids).compile().memory_analysis()
    cache_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(cache))
    assert decode.alias_size_in_bytes >= cache_bytes
    assert decode.argument_size_in_bytes + decode.temp_size_in_bytes < \
        15 * 2 ** 30
    # the stacked cache rides the layer scan's carry and only the new rows
    # are written: no temporary near the 1.34 GB of the K or the V stack
    assert decode.temp_size_in_bytes < 2 ** 28
    one = _spec((1,), jnp.int32, one_chip)
    prefill = engine._prefill.lower(
        params, cfg, cache, _spec((1, longest), jnp.int32, one_chip), one,
        one, max_seq).compile().memory_analysis()
    assert prefill.alias_size_in_bytes >= cache_bytes
    assert prefill.temp_size_in_bytes < 2 ** 30
