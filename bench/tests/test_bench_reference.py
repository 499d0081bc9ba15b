"""The plain references agree with the program at small sizes on the CPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import surrogate_ref as sref
from bench.reference import zfp_ref


@pytest.fixture(scope="module")
def samples():
    rng = np.random.default_rng(11)
    x = rng.standard_normal((3, 6, 16, 20)).astype(np.float32)
    x[1, 2] = 0.0                                   # all-zero blocks
    x[2] *= 1e-3
    return jnp.asarray(x)


def test_encode_and_decode_match_the_codec(samples):
    from repro.compression import get_codec
    codec = get_codec("fixed_accuracy", backend="jnp")
    tols = jnp.asarray([0.05, 0.5, 1e-4], jnp.float32)
    cf = codec.encode_batch(samples, tols)
    pay, emax, npl = zfp_ref.encode(samples, tols)
    np.testing.assert_array_equal(np.asarray(emax), np.asarray(cf.emax))
    np.testing.assert_array_equal(np.asarray(npl), np.asarray(cf.nplanes))
    np.testing.assert_array_equal(np.asarray(pay), np.asarray(cf.payload))
    dec = zfp_ref.decode(pay, emax, npl, tuple(samples.shape[1:]))
    np.testing.assert_array_equal(np.asarray(dec),
                                  np.asarray(codec.decode_batch(cf)))


def test_search_matches_algorithm_1(samples):
    from repro.core import find_tolerance_batch
    want = find_tolerance_batch(samples, np.full((3,), 0.02, np.float32))
    got = zfp_ref.search(samples, 0.02)
    np.testing.assert_array_equal(got, want.tolerance)


def test_forward_and_init_match_the_program():
    from repro.models.surrogate import (SurrogateConfig, apply_surrogate,
                                        init_surrogate)
    cfg = SurrogateConfig(height=32, width=32, base_channels=64)
    shape = (32, 32, 6, 64, cfg.cond_dim)
    params = sref.init_params(jax.random.PRNGKey(0), shape)
    want = init_surrogate(jax.random.PRNGKey(0), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    cond = jax.random.uniform(jax.random.PRNGKey(1), (3, cfg.cond_dim))
    with jax.default_matmul_precision("highest"):
        got = sref.forward(params, cond, 32, 32)
        ref = apply_surrogate(params, cfg, cond)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_chunked_encode_matches_one_encode(monkeypatch):
    from bench import prep
    from repro.compression import get_codec
    rng = np.random.default_rng(5)
    member = jnp.asarray(rng.standard_normal((5, 16, 20, 6)), jnp.float32)
    norm = prep.normalizer(np.zeros(6, np.float32), np.ones(6, np.float32))
    codec = get_codec("fixed_accuracy", backend="jnp")
    whole = prep.certify_member(norm, member, 0.02, codec)
    monkeypatch.setattr(prep, "ENCODE_ROWS", 2)
    parts = prep.certify_member(norm, member, 0.02, codec)
    np.testing.assert_array_equal(whole[0], parts[0])
    for leaf in ("payload", "emax", "nplanes"):
        np.testing.assert_array_equal(np.asarray(getattr(whole[1], leaf)),
                                      np.asarray(getattr(parts[1], leaf)))
    assert whole[1].shape == parts[1].shape


def test_rounded_products_are_bfloat16_operands_summed_in_float32():
    """The reference's ``rounded`` precision, checked against convolutions
    and matmuls of bfloat16 operands that accumulate in float32."""
    cfg_shape = (32, 32, 6, 64, 7)
    params = sref.init_params(jax.random.PRNGKey(3), cfg_shape)
    cond = jax.random.uniform(jax.random.PRNGKey(4), (2, 7))
    bf = jnp.bfloat16

    def conv(x, w, pad, dil=(1, 1)):
        return jax.lax.conv_general_dilated(
            x.astype(bf), w.astype(bf), (1, 1), pad, lhs_dilation=dil,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)

    def ln(p, x):
        return sref._layernorm(p, x)

    x = jnp.matmul(cond.astype(bf), params["proj"]["w"].astype(bf),
                   preferred_element_type=jnp.float32) + params["proj"]["b"]
    x = sref._leaky(ln(params["ln_in"], x.reshape(2, 2, 2, 64)))
    for i in range(4):
        x = sref._leaky(conv(x, params[f"up{i}_t"]["w"], ((2, 2), (2, 2)),
                             (2, 2)) + params[f"up{i}_t"]["b"])
        x = conv(x, params[f"up{i}_c"]["w"], "SAME") + params[f"up{i}_c"]["b"]
        x = sref._leaky(ln(params[f"up{i}_ln"], x))
    want = conv(x, params["out"]["w"], "SAME") + params["out"]["b"]
    with jax.default_matmul_precision("highest"):
        got = sref.forward(params, cond, 32, 32, rounded=True)
        plain = sref.forward(params, cond, 32, 32)
    def gap(a, b):     # root mean square of a - b, against b's
        return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))
    # the two differ only where an f32 sum taken in another order rounds
    # an operand to the neighbouring bfloat16; plain float32 is far off
    assert gap(got, want) < 1e-3
    assert gap(plain, want) > 3e-3
