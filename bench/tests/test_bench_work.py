"""Work counts: FLOPs against hand counts, logical bytes against the codec."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench import work


@pytest.mark.parametrize("height,width,flops", [
    (768, 256, 11_050_549_248), (512, 512, 14_734_065_664)])
def test_forward_flops_match_hand_counts(height, width, flops):
    model = {"height": height, "width": width, "fields": 6,
             "base_channels": 256, "cond_dim": 7}
    assert work.surrogate_forward_flops(model) == flops
    assert work.surrogate_train_flops(model) == 3 * flops


def test_logical_bytes_match_the_codec():
    from repro.compression import compressed_nbytes_batch, get_codec
    rng = np.random.default_rng(3)
    xs = jnp.asarray(rng.standard_normal((3, 6, 16, 12)), jnp.float32)
    cf = get_codec("fixed_accuracy", backend="jnp").encode_batch(
        xs, jnp.asarray([0.01, 0.1, 1.0], jnp.float32))
    want = np.asarray(compressed_nbytes_batch(cf, mode="fixed_accuracy"))
    np.testing.assert_array_equal(work.logical_bytes(cf.nplanes), want)
    assert work.raw_bytes((6, 16, 12)) == 6 * 16 * 12 * 4
