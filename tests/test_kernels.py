"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.compression import transform as T
from repro.kernels import ops, ref, zfp_codec


def _blocks_from(rng, n_blocks, kind="smooth"):
    if kind == "smooth":
        t = np.linspace(0, 3, n_blocks * 16)
        x = np.sin(t) * np.exp(-0.1 * t)
    else:
        x = rng.standard_normal(n_blocks * 16) * 10.0 ** rng.integers(-3, 3)
    return jnp.asarray(x.reshape(n_blocks, 16).astype(np.float32))


# ---------------------------------------------------------------------------
# ZFP codec kernels: bit-exact vs oracle across shapes and rates
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [1, 2, 5, 8, 15, 23, 30])
@pytest.mark.parametrize("n_blocks", [1, 7, 256, 300])
def test_zfp_encode_matches_ref(rng, bits, n_blocks):
    blocks = _blocks_from(rng, n_blocks, "rough")
    p_ref, e_ref = ref.zfp_encode_blocks_ref(blocks, bits)
    p_k, e_k = zfp_codec.zfp_encode_blocks(blocks, bits, interpret=True)
    assert np.array_equal(np.asarray(p_ref), np.asarray(p_k))
    assert np.array_equal(np.asarray(e_ref), np.asarray(e_k))


@pytest.mark.parametrize("bits", [2, 8, 16, 30])
@pytest.mark.parametrize("n_blocks", [3, 256, 511])
def test_zfp_decode_matches_ref(rng, bits, n_blocks):
    blocks = _blocks_from(rng, n_blocks, "smooth")
    payload, emax = ref.zfp_encode_blocks_ref(blocks, bits)
    d_ref = ref.zfp_decode_blocks_ref(payload, emax, bits)
    d_k = zfp_codec.zfp_decode_blocks(payload, emax, bits, interpret=True)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_ref),
                               rtol=0, atol=0)


# ---------------------------------------------------------------------------
# fixed-accuracy decode kernel: per-block variable plane counts
# ---------------------------------------------------------------------------

def _fa_payload(rng, n_blocks, tol):
    """Encode a mixed-scale field -> (payload, emax, nplanes, expected blocks)."""
    from repro.compression import encode_fixed_accuracy, decode
    from repro.compression import transform as T
    side = int(np.ceil(np.sqrt(n_blocks)))
    x = (np.sin(np.linspace(0, 5, side * side * 16))
         * np.logspace(-2, 1, side * side * 16)).astype(np.float32)
    x = x.reshape(side * 4, side * 4)
    cf = encode_fixed_accuracy(jnp.asarray(x), tol)
    expect = T.blockify(T.pad_to_blocks(decode(cf)))
    return cf, expect


@pytest.mark.parametrize("n_blocks", [1, 7, 256, 300])
@pytest.mark.parametrize("tol", [1e-4, 1e-2, 0.5])
def test_zfp_decode_fa_matches_ref(rng, n_blocks, tol):
    cf, expect = _fa_payload(rng, n_blocks, tol)
    d_ref = ref.zfp_decode_blocks_fa_ref(cf.payload, cf.emax, cf.nplanes)
    d_k = zfp_codec.zfp_decode_blocks_fa(cf.payload, cf.emax, cf.nplanes,
                                         interpret=True)
    d_f = ops.zfp_decode_blocks_fa(cf.payload, cf.emax, cf.nplanes)
    assert np.array_equal(np.asarray(d_k), np.asarray(d_ref))
    assert np.array_equal(np.asarray(d_f), np.asarray(d_ref))
    assert np.array_equal(np.asarray(d_k), np.asarray(expect))


def test_zfp_decode_fa_zero_plane_blocks(rng):
    """nplanes == 0 blocks (all-zero input) must decode to exact zeros even
    when the shared payload width carries other blocks' words."""
    from repro.compression import encode_fixed_accuracy
    x = rng.standard_normal((16, 16)).astype(np.float32)
    x[:4, :] = 0.0                       # first row of 4x4 blocks -> zeros
    cf = encode_fixed_accuracy(jnp.asarray(x), 1e-3)
    assert int(cf.nplanes.min()) == 0 and int(cf.nplanes.max()) > 0
    out = np.asarray(zfp_codec.zfp_decode_blocks_fa(
        cf.payload, cf.emax, cf.nplanes, interpret=True))
    zero_rows = np.asarray(cf.nplanes) == 0
    assert np.all(out[zero_rows] == 0.0)
    assert np.array_equal(
        out, np.asarray(ref.zfp_decode_blocks_fa_ref(cf.payload, cf.emax,
                                                     cf.nplanes)))


def test_zfp_decode_fa_full_plane_blocks(rng):
    """nplanes == TOTAL_PLANES (tolerance far below representable detail)
    keeps every stored plane: the FA kernel must match the plain decode."""
    from repro.compression import decode, encode_fixed_accuracy
    from repro.compression import transform as T
    x = (10.0 * rng.standard_normal((8, 8))).astype(np.float32)
    cf = encode_fixed_accuracy(jnp.asarray(x), 1e-12)
    assert int(cf.nplanes.max()) == T.TOTAL_PLANES
    blocks = np.asarray(zfp_codec.zfp_decode_blocks_fa(
        cf.payload, cf.emax, cf.nplanes, interpret=True))
    expect = np.asarray(T.blockify(T.pad_to_blocks(decode(cf))))
    assert np.array_equal(blocks, expect)


def test_zfp_decode_fa_masks_planes_below_count(rng):
    """Unlike the fixed-rate kernel, the FA kernel must actively ZERO planes
    beyond each block's count -- feed payloads carrying deeper planes and
    check the mask (per-block widths varying within one call)."""
    blocks = _blocks_from(rng, 64, "rough")
    payload, emax = ref.zfp_encode_blocks_ref(blocks, 30)   # full-depth words
    nplanes = jnp.asarray((np.arange(64) % 31).astype(np.int32))
    got = zfp_codec.zfp_decode_blocks_fa(payload, emax, nplanes,
                                         interpret=True)
    want = ref.zfp_decode_blocks_fa_ref(payload, emax, nplanes)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # and the masked result genuinely differs from the unmasked decode
    unmasked = ref.zfp_decode_blocks_ref(payload, emax, 30)
    assert not np.array_equal(np.asarray(got), np.asarray(unmasked))


def test_zfp_fast_path_identical(rng):
    """Off the TPU the ops entries run the compiled oracle: it must equal
    the fixed-rate kernels run in interpret mode."""
    blocks = _blocks_from(rng, 64, "rough")
    payload, emax = zfp_codec.zfp_encode_blocks(blocks, 12, interpret=True)
    for k, o in zip((payload, emax), ops.zfp_encode_blocks(blocks, 12)):
        assert np.array_equal(np.asarray(k), np.asarray(o))
    a = zfp_codec.zfp_decode_blocks(payload, emax, 12, interpret=True)
    b = ops.zfp_decode_blocks(payload, emax, 12)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=0)


def test_encode_decode_field_roundtrip(rng):
    from repro.compression import get_codec
    x = jnp.asarray(rng.standard_normal((6, 33, 18)).astype(np.float32))
    codec = get_codec("fixed_rate", bits_per_value=20)
    out = codec.decode_batch(codec.encode_batch(x[None]))[0]
    assert out.shape == x.shape
    assert float(jnp.max(jnp.abs(out - x))) < 1e-3


# ---------------------------------------------------------------------------
# coefficient-major fixed-accuracy encode kernel: bit identity with the oracle
# ---------------------------------------------------------------------------

FA_TILE = zfp_codec.FA_TILE_ROWS * 128          # blocks per grid step


def _assert_fa_encode_matches_ref(blocks, tols):
    want = ref.zfp_encode_blocks_fa_ref(blocks, tols)
    got = zfp_codec.zfp_encode_blocks_fa(blocks.T, tols, interpret=True)
    for name, w, g in zip(("payload", "emax", "nplanes"), want, got):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    return want


@pytest.mark.parametrize("n_blocks", [1, 127, 129, FA_TILE, FA_TILE + 1])
def test_zfp_encode_fa_block_counts_bit_identical(rng, n_blocks):
    """Part rows of 128 lanes, one whole tile and a tile's spill-over."""
    blocks = _blocks_from(rng, n_blocks, "rough")
    tols = jnp.asarray(10.0 ** rng.uniform(-5, 0, n_blocks), jnp.float32)
    _assert_fa_encode_matches_ref(blocks, tols)


def _fa_special_case(rng, case):
    """(256 blocks, 256 tolerances) for one of the edge cases below."""
    x = np.array(_blocks_from(rng, 256, "rough"))
    tols = np.full((256,), 1e-3, np.float32)
    if case == "zero_blocks":
        x[::2] = 0.0
        x[1::4] = 1e-40                          # below the 2^-120 flush
    elif case == "all_fix_steps":
        # a tolerance no plane count meets: every block takes all
        # MAX_FIX_ITERS steps, two planes each, from a guess of emax + 2
        x = rng.standard_normal((256, 16)).astype(np.float32) * 0.1
        tols[:] = -1.0
    elif case == "pow2_tolerance":
        tols[:] = 2.0 ** -7
        x[::3, 5] = 2.0 ** rng.integers(-8, 8, x[::3].shape[0])
    elif case == "subnormal_and_large":
        x[:64] = rng.choice([1e-39, -3e-42, 0.0, 5e-45], (64, 16))
        x[64:96, ::2] = 2e-38
        x[96:160] = rng.choice([-1.0, 1.0], (64, 16)) * 2e38
        x[160:192] = rng.standard_normal((32, 16)) * 1e30
        tols[96:192] = 1e25
    elif case == "tolerances_change_in_tile":
        tols = np.repeat(np.float32([1e-4, 0.3, 2.0 ** -3, 1e-2]), 64)
    return jnp.asarray(x, jnp.float32), jnp.asarray(tols)


@pytest.mark.parametrize("case", ["zero_blocks", "all_fix_steps",
                                  "pow2_tolerance", "subnormal_and_large",
                                  "tolerances_change_in_tile"])
def test_zfp_encode_fa_edge_cases_bit_identical(rng, case):
    from repro.compression.transform import GUARD_BITS, MAX_FIX_ITERS
    blocks, tols = _fa_special_case(rng, case)
    _, emax, npl = _assert_fa_encode_matches_ref(blocks, tols)
    if case == "all_fix_steps":
        guess = np.clip(np.asarray(emax) + GUARD_BITS, 0, T.TOTAL_PLANES)
        assert np.array_equal(np.asarray(npl),
                              np.minimum(guess + 2 * MAX_FIX_ITERS,
                                         T.TOTAL_PLANES))
        assert (guess + 2 * MAX_FIX_ITERS <= T.TOTAL_PLANES).all()
    if case == "zero_blocks":
        assert not np.asarray(npl)[::2].any()
        assert not np.asarray(npl)[1::4].any()


@pytest.mark.parametrize("shape", [(2, 6, 32, 32), (2, 6, 48, 16),
                                   (2, 3, 30, 18)],
                         ids=["pchip", "rt", "ragged"])
def test_encode_fixed_accuracy_batch_pallas_matches_jnp(rng, shape):
    """Small stand-ins of the PCHIP (square) and RT (3:1) stacks, and one
    whose H and W are not multiples of 4: the codec's kernel backend equals
    the jnp encoder, and so does the kernel on the same coefficient-major
    stack."""
    from repro.compression import get_codec
    xs = jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                     * 10.0 ** rng.uniform(-2, 2, shape[:2] + (1, 1)))
    tols = jnp.asarray(10.0 ** rng.uniform(-4, -1, shape[0]), jnp.float32)
    want = get_codec("fixed_accuracy", backend="jnp").encode_batch(xs, tols)
    got = get_codec("fixed_accuracy", backend="pallas").encode_batch(xs, tols)
    assert (got.shape, got.padded_shape) == (want.shape, want.padded_shape)
    n, nb = want.emax.shape
    coefs = T.blockify_coef_major(T.pad_to_blocks(xs))
    kernel = zfp_codec.zfp_encode_blocks_fa(coefs, jnp.repeat(tols, nb),
                                            interpret=True)
    for name, k in zip(("payload", "emax", "nplanes"), kernel):
        w = np.asarray(getattr(want, name))
        assert np.array_equal(np.asarray(getattr(got, name)), w), name
        assert np.array_equal(np.asarray(k).reshape(w.shape), w), name


# ---------------------------------------------------------------------------
# flash attention kernel vs oracle
# ---------------------------------------------------------------------------

CASES = [
    # b, hq, hkv, sq, sk, d, causal, window, dtype
    (2, 4, 2, 64, 64, 32, True, None, jnp.float32),
    (1, 8, 2, 1, 128, 64, True, None, jnp.float32),      # decode shape
    (1, 4, 4, 96, 96, 16, False, None, jnp.float32),     # encoder (full)
    (2, 2, 1, 128, 128, 32, True, 48, jnp.float32),      # sliding window
    (1, 4, 2, 256, 256, 64, True, None, jnp.bfloat16),   # bf16
    (1, 2, 2, 80, 80, 24, True, None, jnp.float32),      # pad-needing shape
]


@pytest.mark.parametrize("case", CASES)
def test_flash_attention_matches_ref(rng, case):
    b, hq, hkv, sq, sk, d, causal, window, dtype = case
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    o_k = ops.flash_attention(q, k, v, causal=causal, window=window)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_ref, np.float32), atol=atol)


def test_flash_attention_small_blocks(rng):
    """Block sizes smaller than defaults exercise the online-softmax carry."""
    from repro.kernels.flash_attention import flash_attention
    q = jnp.asarray(rng.standard_normal((1, 2, 64, 16)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, 64, 16)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, 64, 16)).astype(np.float32))
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    o_k = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref), atol=2e-5)


# ---------------------------------------------------------------------------
# fixed-accuracy encode kernel: bit-exact vs oracle (Algorithm 1's hot path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tol", [1e-4, 1e-2, 0.25, 0.5])
@pytest.mark.parametrize("n_blocks", [1, 7, 256, 300])
def test_zfp_encode_fa_matches_ref(rng, n_blocks, tol):
    blocks = _blocks_from(rng, n_blocks, "rough")
    tols = jnp.full((n_blocks,), tol, jnp.float32)
    p_ref, e_ref, n_ref = ref.zfp_encode_blocks_fa_ref(blocks, tols)
    p_k, e_k, n_k = zfp_codec.zfp_encode_blocks_fa(blocks.T, tols,
                                                   interpret=True)
    assert np.array_equal(np.asarray(p_k), np.asarray(p_ref))
    assert np.array_equal(np.asarray(e_k), np.asarray(e_ref))
    assert np.array_equal(np.asarray(n_k), np.asarray(n_ref))


def test_zfp_encode_fa_mixed_tolerances(rng):
    """Per-block tolerances (the batched encode repeats a sample's tolerance
    across its blocks -- the kernel must honor each row independently)."""
    blocks = _blocks_from(rng, 192, "rough")
    tols = jnp.asarray(10.0 ** rng.uniform(-5, 0, 192), jnp.float32)
    p_ref, e_ref, n_ref = ref.zfp_encode_blocks_fa_ref(blocks, tols)
    p_k, e_k, n_k = zfp_codec.zfp_encode_blocks_fa(blocks.T, tols,
                                                   interpret=True)
    assert np.array_equal(np.asarray(p_k), np.asarray(p_ref))
    assert np.array_equal(np.asarray(e_k), np.asarray(e_ref))
    assert np.array_equal(np.asarray(n_k), np.asarray(n_ref))


def test_zfp_encode_fa_zero_blocks(rng):
    """All-zero (and sub-flush-threshold) blocks keep zero planes."""
    blocks = jnp.zeros((40, 16), jnp.float32)
    blocks = blocks.at[7].set(1e-40)            # below the 2^-120 flush
    p, e, n = zfp_codec.zfp_encode_blocks_fa(blocks.T, jnp.full((40,), 1e-3),
                                             interpret=True)
    assert not np.asarray(p).any()
    assert not np.asarray(e).any()
    assert not np.asarray(n).any()


@pytest.mark.parametrize("tol", [1e-3, 1e-1])
def test_zfp_encode_fa_roundtrip_honors_bound(rng, tol):
    """Kernel encode -> kernel decode stays within the L-inf tolerance."""
    blocks = _blocks_from(rng, 128, "smooth")
    p, e, n = zfp_codec.zfp_encode_blocks_fa(
        blocks.T, jnp.full((128,), tol), interpret=True)
    dec = zfp_codec.zfp_decode_blocks_fa(p, e, n, interpret=True)
    assert float(jnp.max(jnp.abs(dec - blocks))) <= tol


def test_zfp_encode_fa_fast_path_identical(rng):
    """Off the TPU the ops entry runs the compiled oracle: it is
    bit-identical to the kernel run in interpret mode."""
    blocks = _blocks_from(rng, 96, "rough")
    tols = jnp.asarray(10.0 ** rng.uniform(-4, -1, 96), jnp.float32)
    for a, b in zip(zfp_codec.zfp_encode_blocks_fa(blocks.T, tols,
                                                   interpret=True),
                    ops.zfp_encode_blocks_fa(blocks.T, tols)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
