"""Codec correctness: round trips, error bounds (property-based), ratios.

The property-based tests use hypothesis when available but degrade to a
deterministic seeded grid when it is not installed (the tier-1 suite must
never lose collection to an optional dep).
"""
import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.compression import (
    compressed_nbytes, compression_ratio, decode, decode_fixed_rate,
    encode_fixed_accuracy, encode_fixed_accuracy_batch, encode_fixed_rate,
    blockify, deblockify, get_codec,
)
from repro.compression import transform as T


# ---------------------------------------------------------------------------
# transform invariants
# ---------------------------------------------------------------------------

def test_blockify_roundtrip(rng):
    x = jnp.asarray(rng.standard_normal((3, 8, 12)).astype(np.float32))
    b = blockify(x)
    assert b.shape == (3 * 2 * 3, 16)
    assert np.allclose(deblockify(b, (3, 8, 12)), x)


@pytest.mark.parametrize("shape", [(8, 12), (3, 16, 4), (2, 3, 12, 20)])
def test_blockify_coef_major_is_blockify_transposed(rng, shape):
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    assert np.array_equal(np.asarray(T.blockify_coef_major(x)),
                          np.asarray(blockify(x).T))


def test_negabinary_roundtrip(rng):
    i = jnp.asarray(rng.integers(-2**29, 2**29, 100000).astype(np.int32))
    assert np.array_equal(T.nb2int(T.int2nb(i)), i)


def test_lift_near_inverse(rng):
    """ZFP lift pair is a near-inverse: integer shifts round a few ulps."""
    b = jnp.asarray(rng.integers(-2**26, 2**26, (5000, 16)).astype(np.int32))
    r = T.inv_transform_2d(T.fwd_transform_2d(b))
    assert int(jnp.max(jnp.abs(r - b))) <= 16     # ulps at Q=26 scale


def test_transform_range_contraction(rng):
    b = jnp.asarray(rng.integers(-2**27, 2**27, (5000, 16)).astype(np.int32))
    f = T.fwd_transform_2d(b)
    assert int(jnp.max(jnp.abs(f))) < 2**28       # no overflow headroom used


# ---------------------------------------------------------------------------
# error-bounded mode (the paper's guarantee)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tol", [1e-1, 1e-2, 1e-3, 1e-4])
def test_fixed_accuracy_bound(smooth_field, tol):
    cf = encode_fixed_accuracy(jnp.asarray(smooth_field), tol)
    err = np.abs(np.asarray(decode(cf)) - smooth_field).max()
    assert err <= tol, f"L-inf bound violated: {err} > {tol}"


def _check_fixed_accuracy_bound(seed, scale, tol_frac):
    """Property: for any finite field and tolerance, the bound holds."""
    r = np.random.default_rng(seed)
    x = (r.standard_normal((24, 20)) * scale).astype(np.float32)
    tol = float(tol_frac * scale)
    cf = encode_fixed_accuracy(jnp.asarray(x), tol)
    err = np.abs(np.asarray(decode(cf)) - x).max()
    assert err <= tol * (1 + 1e-6)


# deterministic fallback grid spanning the hypothesis search space
_BOUND_CASES = [(seed, scale, tol_frac)
                for seed in (0, 1, 7919)
                for scale in (1e-3, 1.0, 1e3)
                for tol_frac in (1e-4, 1e-2, 0.5)]

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000),
           scale=st.floats(1e-3, 1e3),
           tol_frac=st.floats(1e-4, 0.5))
    def test_fixed_accuracy_bound_property(seed, scale, tol_frac):
        _check_fixed_accuracy_bound(seed, scale, tol_frac)
else:
    @pytest.mark.parametrize("seed,scale,tol_frac", _BOUND_CASES)
    def test_fixed_accuracy_bound_property(seed, scale, tol_frac):
        _check_fixed_accuracy_bound(seed, scale, tol_frac)


def test_zero_field():
    x = jnp.zeros((16, 16), jnp.float32)
    cf = encode_fixed_accuracy(x, 1e-3)
    assert np.allclose(np.asarray(decode(cf)), 0.0)
    assert float(compression_ratio(cf)) > 30      # near header-only


def test_ratio_monotone_in_tolerance(smooth_field):
    x = jnp.asarray(smooth_field)
    ratios = [float(compression_ratio(encode_fixed_accuracy(x, t)))
              for t in (1e-4, 1e-3, 1e-2, 1e-1)]
    assert ratios == sorted(ratios), f"ratio not monotone: {ratios}"


# ---------------------------------------------------------------------------
# fixed-rate mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 6, 10, 16, 24, 30])
def test_fixed_rate_roundtrip_quality(smooth_field, bits):
    x = jnp.asarray(smooth_field)
    cf = encode_fixed_rate(x, bits)
    err = np.abs(np.asarray(decode_fixed_rate(cf)) - smooth_field).max()
    # each extra plane halves the error; anchor loosely (floor = lift
    # round-trip noise at full precision)
    assert err < 6.0 * 2.0 ** (-bits + 3) + 1e-7
    assert cf.payload.shape[1] == (bits + 1) // 2


def test_odd_shapes_and_leading_dims(rng):
    x = jnp.asarray(rng.standard_normal((2, 3, 13, 19)).astype(np.float32))
    cf = encode_fixed_accuracy(x, 1e-3)
    out = np.asarray(decode(cf))
    assert out.shape == (2, 3, 13, 19)
    assert np.abs(out - np.asarray(x)).max() <= 1e-3


def test_nbytes_accounting(smooth_field):
    cf = encode_fixed_accuracy(jnp.asarray(smooth_field), 1e-2)
    nb = cf.nplanes.shape[0]
    expected = 2 * nb + 2 * int(jnp.sum(cf.nplanes))
    assert int(compressed_nbytes(cf)) == expected


# ---------------------------------------------------------------------------
# batched fixed-rate encode: pure-jnp vmap vs Pallas kernel path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [3, 8, 13])
@pytest.mark.parametrize("shape", [(3, 2, 10, 7), (2, 6, 16, 8)])
def test_fixed_rate_batch_pallas_oracle_parity(rng, bits, shape):
    """The backend must be invisible: payload/emax words bit-identical to
    the independent pure-jnp encoder, per sample."""
    xs = jnp.asarray((rng.standard_normal(shape) *
                      10.0 ** rng.integers(-3, 3)).astype(np.float32))
    pure = get_codec("fixed_rate", bits_per_value=bits,
                     backend="jnp").encode_batch(xs)
    pall = get_codec("fixed_rate", bits_per_value=bits,
                     backend="pallas").encode_batch(xs)
    assert np.array_equal(np.asarray(pure.payload), np.asarray(pall.payload))
    assert np.array_equal(np.asarray(pure.emax), np.asarray(pall.emax))
    assert np.array_equal(np.asarray(pure.nplanes), np.asarray(pall.nplanes))
    assert pure.padded_shape == pall.padded_shape
    # both match the per-sample oracle encoder exactly
    for j in range(shape[0]):
        ref = encode_fixed_rate(xs[j], bits)
        assert np.array_equal(np.asarray(ref.payload),
                              np.asarray(pall.payload[j]))
        assert np.array_equal(np.asarray(ref.emax), np.asarray(pall.emax[j]))


def test_fixed_rate_batch_decodes_like_per_sample(rng):
    from repro.compression import decode_batch
    xs = jnp.asarray(rng.standard_normal((4, 2, 9, 6)).astype(np.float32))
    cf = get_codec("fixed_rate", bits_per_value=11).encode_batch(xs)
    got = np.asarray(decode_batch(cf))
    for j in range(4):
        want = np.asarray(decode_fixed_rate(encode_fixed_rate(xs[j], 11)))
        assert np.array_equal(got[j], want)


@pytest.mark.parametrize("shape", [(5, 3, 13, 19), (4, 16, 16)])
def test_fixed_accuracy_batch_pallas_oracle_parity(rng, shape):
    """backend="pallas" fixed-accuracy encode emits bit-identical streams.

    This is the contract that keeps the backend out of the datagen plan
    hash: the platform that runs the encode cannot change produced bytes.
    """
    from repro.compression import encode_fixed_accuracy
    xs = jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 7.0)
    tols = jnp.asarray(10.0 ** rng.uniform(-4, -1, shape[0]), jnp.float32)
    cf_j = get_codec("fixed_accuracy", backend="jnp").encode_batch(xs, tols)
    cf_p = get_codec("fixed_accuracy", backend="pallas").encode_batch(xs, tols)
    for field in ("payload", "emax", "nplanes"):
        assert np.array_equal(np.asarray(getattr(cf_j, field)),
                              np.asarray(getattr(cf_p, field))), field
    for i in range(shape[0]):                   # flattening samples is exact
        cf1 = encode_fixed_accuracy(xs[i], tols[i])
        assert np.array_equal(np.asarray(cf1.payload),
                              np.asarray(cf_p.payload[i]))
        assert np.array_equal(np.asarray(cf1.nplanes),
                              np.asarray(cf_p.nplanes[i]))


def test_nbytes_header_billing_is_mode_explicit(rng):
    """Header billing follows the declared mode, never the data.

    A fixed-accuracy stream whose plane counts happen to be uniform must
    still be billed the 2-byte fixed-accuracy header (the decoder ships
    per-block counts regardless); the old data-dependent detection
    (``all(nplanes == nplanes[0])``) silently collapsed such batches to
    fixed-rate billing.
    """
    from repro.compression import compressed_nbytes, compressed_nbytes_batch
    block = rng.standard_normal((4, 4)).astype(np.float32)
    xs = jnp.asarray(np.tile(block, (3, 2, 2)))          # identical blocks
    cf = encode_fixed_accuracy_batch(xs, jnp.full((3,), 1e-3, jnp.float32))
    npl = np.asarray(cf.nplanes)
    assert (npl == npl.flat[0]).all()                    # uniform on purpose
    nb = npl.shape[1]
    expect = 2 * nb + 2 * npl.sum(axis=1)
    got = np.asarray(compressed_nbytes_batch(cf, mode="fixed_accuracy"))
    assert np.array_equal(got, expect)
    got_fr = np.asarray(compressed_nbytes_batch(cf, mode="fixed_rate"))
    assert np.array_equal(got_fr, expect - nb)           # 1-byte headers
    with pytest.raises(ValueError):
        compressed_nbytes_batch(cf, mode="adaptive")
    with pytest.raises(ValueError):
        compressed_nbytes(cf, mode="adaptive")


def test_trim_to_nplanes_bit_identity(rng):
    """Trimming payload words past ceil(max(nplanes)/2) decodes identically."""
    from repro.compression import decode_batch, trim_to_nplanes
    from repro.kernels import ops
    xs = jnp.asarray(rng.standard_normal((4, 12, 20)).astype(np.float32))
    cf = encode_fixed_accuracy_batch(xs, jnp.full((4,), 0.05, jnp.float32))
    cft = trim_to_nplanes(cf)
    w = int(np.ceil(np.asarray(cf.nplanes).max() / 2))
    assert cft.payload.shape[-1] == max(w, 1) < cf.payload.shape[-1]
    assert np.array_equal(np.asarray(decode_batch(cft)),
                          np.asarray(decode_batch(cf)))
    # kernel decode at the trimmed width matches the untrimmed stream too
    n, nb = cf.nplanes.shape
    full = ops.zfp_decode_blocks_fa(cf.payload.reshape(n * nb, -1),
                                    cf.emax.reshape(-1),
                                    cf.nplanes.reshape(-1))
    trimmed = ops.zfp_decode_blocks_fa(cft.payload.reshape(n * nb, -1),
                                       cft.emax.reshape(-1),
                                       cft.nplanes.reshape(-1))
    assert np.array_equal(np.asarray(full), np.asarray(trimmed))
