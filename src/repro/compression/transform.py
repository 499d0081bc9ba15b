"""ZFP block transform primitives, vectorized over blocks (pure jnp, int32).

The 2D codec operates on 4x4 blocks.  Per ZFP (Lindstrom 2014):
  * forward/inverse lifted decorrelation transform (integer, non-orthogonal,
    near-inverse pair -- integer shifts round, error is a few ulps and is
    absorbed in the loss budget),
  * negabinary mapping so bit planes carry sign,
  * bit-plane extraction/packing (two 16-bit planes per int32 word,
    most-significant plane first).

All functions are shape-polymorphic over a leading block axis and are used by
the public codec (compression/zfp.py), the kernel oracle (kernels/ref.py) and
the Pallas kernels themselves (kernels/zfp_*.py run the same arithmetic on
VMEM tiles).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Fixed-point scale: |x| / 2^emax < 1 maps to |i| <= 2^Q.  The 2D forward
# transform contracts range (measured growth < 0.77), so coefficients stay
# below 2^Q and their negabinary image below 2^(Q+2).
Q_FIXED_POINT = 28
# Bit planes stored, MSB-first: planes TOTAL_PLANES-1 .. 0.
TOTAL_PLANES = 30
# int32 words per block at full precision (2 planes of 16 lanes per word).
MAX_WORDS = (TOTAL_PLANES + 1) // 2
# Fixed-accuracy plane count: an optimistic guess of emax - floor(log2(tol))
# + GUARD_BITS, then at most MAX_FIX_ITERS correction steps of +2 planes.
GUARD_BITS = 2
MAX_FIX_ITERS = 6

_NEG_MASK = jnp.int32(-1431655766)  # 0xAAAAAAAA as int32 bit pattern


# ---------------------------------------------------------------------------
# blockify / deblockify
# ---------------------------------------------------------------------------

def pad_to_blocks(x: jnp.ndarray) -> jnp.ndarray:
    """Edge-pad the trailing two dims of ``x`` up to multiples of 4."""
    h, w = x.shape[-2], x.shape[-1]
    ph, pw = (-h) % 4, (-w) % 4
    if ph or pw:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, ph), (0, pw)]
        x = jnp.pad(x, pad, mode="edge")
    return x


def blockify(x: jnp.ndarray) -> jnp.ndarray:
    """(..., H, W) -> (nb, 16) row-major 4x4 blocks. H, W divisible by 4."""
    *lead, h, w = x.shape
    x = x.reshape(*lead, h // 4, 4, w // 4, 4)
    x = jnp.moveaxis(x, -3, -2)            # (..., h//4, w//4, 4, 4)
    return x.reshape(-1, 16)


def blockify_coef_major(x: jnp.ndarray) -> jnp.ndarray:
    """(..., H, W) -> (16, nb): ``blockify(x).T``.

    Row k = 4r + c holds coefficient (r, c) of every block, blocks in
    ``blockify``'s order, so a kernel can lay the blocks along its lanes.
    Columns c move out first, then rows r: taken in one transpose, XLA on
    the TPU materialises the (..., 4) split with its minor 4 padded to 128
    lanes, 32 times the field's bytes.
    """
    *lead, h, w = x.shape
    x = jnp.moveaxis(x.reshape(-1, h, w // 4, 4), -1, 0)     # (4c, n, H, W/4)
    x = x.reshape(4, -1, h // 4, 4, w // 4)
    return jnp.transpose(x, (3, 0, 1, 2, 4)).reshape(16, -1)


def deblockify(blocks: jnp.ndarray, shape) -> jnp.ndarray:
    """(nb, 16) -> (..., H, W), inverse of :func:`blockify`."""
    *lead, h, w = shape
    x = blocks.reshape(*lead, h // 4, w // 4, 4, 4)
    x = jnp.moveaxis(x, -2, -3)
    return x.reshape(*shape)


# ---------------------------------------------------------------------------
# lifted decorrelation transform
# ---------------------------------------------------------------------------

def _fwd_lift4(x, y, z, w):
    x = x + w
    x = x >> 1
    w = w - x
    z = z + y
    z = z >> 1
    y = y - z
    x = x + z
    x = x >> 1
    z = z - x
    w = w + y
    w = w >> 1
    y = y - w
    w = w + (y >> 1)
    y = y - (w >> 1)
    return x, y, z, w


def _inv_lift4(x, y, z, w):
    y = y + (w >> 1)
    w = w - (y >> 1)
    y = y + w
    w = (w << 1) - y
    z = z + x
    x = (x << 1) - z
    y = y + z
    z = (z << 1) - y
    w = w + x
    x = (x << 1) - w
    return x, y, z, w


def fwd_transform_2d(blocks: jnp.ndarray) -> jnp.ndarray:
    """Forward 2D lift on (nb, 16) int32 blocks (rows then columns)."""
    b = blocks
    # along x (within each row r: lanes 4r..4r+3)
    cols = [b[:, 0::4], b[:, 1::4], b[:, 2::4], b[:, 3::4]]  # each (nb, 4) = per-row lanes
    x, y, z, w = _fwd_lift4(*cols)
    b = jnp.stack([x, y, z, w], axis=-1).reshape(b.shape[0], 16)
    # along y (within each column c: lanes c, c+4, c+8, c+12)
    rows = [b[:, 0:4], b[:, 4:8], b[:, 8:12], b[:, 12:16]]
    x, y, z, w = _fwd_lift4(*rows)
    return jnp.concatenate([x, y, z, w], axis=-1)


def inv_transform_2d(blocks: jnp.ndarray) -> jnp.ndarray:
    """Inverse 2D lift on (nb, 16) int32 blocks (columns then rows)."""
    b = blocks
    rows = [b[:, 0:4], b[:, 4:8], b[:, 8:12], b[:, 12:16]]
    x, y, z, w = _inv_lift4(*rows)
    b = jnp.concatenate([x, y, z, w], axis=-1)
    cols = [b[:, 0::4], b[:, 1::4], b[:, 2::4], b[:, 3::4]]
    x, y, z, w = _inv_lift4(*cols)
    return jnp.stack([x, y, z, w], axis=-1).reshape(b.shape[0], 16)


# ---------------------------------------------------------------------------
# negabinary
# ---------------------------------------------------------------------------

def int2nb(i: jnp.ndarray) -> jnp.ndarray:
    """Two's-complement int32 -> negabinary bit pattern (int32 container)."""
    return (i + _NEG_MASK) ^ _NEG_MASK


def nb2int(u: jnp.ndarray) -> jnp.ndarray:
    """Negabinary bit pattern -> two's-complement int32."""
    return (u ^ _NEG_MASK) - _NEG_MASK


# ---------------------------------------------------------------------------
# bit-plane packing (MSB-first, 2 planes / word)
# ---------------------------------------------------------------------------

_LANES = jnp.arange(16, dtype=jnp.int32)[None, :]        # (1, 16)


def pack_planes(u: jnp.ndarray, num_words: int) -> jnp.ndarray:
    """Pack (nb, 16) negabinary patterns into (nb, num_words) int32 words.

    Word k holds plane TOTAL_PLANES-1-2k in bits 0..15 and plane
    TOTAL_PLANES-2-2k in bits 16..31.
    """
    words = []
    for k in range(num_words):
        p_hi = TOTAL_PLANES - 1 - 2 * k
        p_lo = TOTAL_PLANES - 2 - 2 * k
        plane_hi = jnp.sum(((u >> p_hi) & 1) << _LANES, axis=-1, dtype=jnp.int32)
        if p_lo >= 0:
            plane_lo = jnp.sum(((u >> p_lo) & 1) << _LANES, axis=-1, dtype=jnp.int32)
        else:
            plane_lo = jnp.zeros_like(plane_hi)
        words.append(plane_hi | (plane_lo << 16))
    return jnp.stack(words, axis=-1)


def unpack_planes(payload: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_planes`: (nb, W) int32 -> (nb, 16) negabinary."""
    nb, num_words = payload.shape
    u = jnp.zeros((nb, 16), dtype=jnp.int32)
    for k in range(num_words):
        word = payload[:, k][:, None]                    # (nb, 1)
        p_hi = TOTAL_PLANES - 1 - 2 * k
        p_lo = TOTAL_PLANES - 2 - 2 * k
        u = u | (((word >> _LANES) & 1) << p_hi)
        if p_lo >= 0:
            u = u | (((word >> (_LANES + 16)) & 1) << p_lo)
    return u


# ---------------------------------------------------------------------------
# exponent / quantization helpers
# ---------------------------------------------------------------------------

def block_emax(blocks_f: jnp.ndarray) -> jnp.ndarray:
    """frexp-style exponent of max |value| per block: max|x| = m 2^emax, m in [0.5,1).

    Blocks whose max magnitude is below 2^-120 flush to zero (emax = 0, all
    fixed-point values round to 0) -- keeps the scale factors finite in f32.
    """
    maxabs = jnp.max(jnp.abs(blocks_f), axis=-1)
    _, e = jnp.frexp(maxabs)
    return jnp.where(maxabs >= 2.0 ** -120, e.astype(jnp.int32), jnp.int32(0))


def pow2_factors(e: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Split 2^e (int32 e) into two exact f32 power-of-two factors.

    XLA's ``exp2`` is a polynomial approximation and lands ~1 ulp off a true
    power of two at most integer arguments.  That inexactness makes every
    downstream multiply inexact, so results depend on whether the compiler
    contracts mul+sub into an FMA -- i.e. on fusion decisions that differ
    between graphs.  Building the scale in the exponent field instead makes
    ``x * 2^e`` exact, hence bit-identical across jit graphs, Pallas
    interpret mode, and compiled TPU kernels.

    The exponent is split into halves so each factor stays in the normal
    f32 range (the codec's exponents span [-147, 147], past the single-
    factor limit of +-126/127).
    """
    e = e.astype(jnp.int32)
    e1 = e >> 1                      # floor(e/2); e1, e-e1 in [-74, 74]
    f1 = jax.lax.bitcast_convert_type((e1 + 127) << 23, jnp.float32)
    f2 = jax.lax.bitcast_convert_type((e - e1 + 127) << 23, jnp.float32)
    return f1, f2


def floor_log2(x: jnp.ndarray) -> jnp.ndarray:
    """``floor(log2(x))`` as int32, read from the f32 exponent field.

    Exact for positive normal ``x``.  XLA's ``log2`` is an approximation
    whose floor flips at and just below powers of two, and differently on
    each backend; the exponent field is the same bits everywhere, so plane
    counts derived from a tolerance agree between the host encoder and the
    TPU kernel.  Zero and subnormal ``x`` read as -127.
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return ((bits >> 23) & 0xFF) - 127


def scale_by_pow2(x: jnp.ndarray, e: jnp.ndarray) -> jnp.ndarray:
    """``x * 2^e`` via two exact power-of-two multiplies (see pow2_factors)."""
    f1, f2 = pow2_factors(e)
    return (x * f1) * f2


def quantize_blocks(blocks_f: jnp.ndarray, emax: jnp.ndarray) -> jnp.ndarray:
    """float (nb,16) -> fixed-point int32 with per-block scale 2^(Q-emax)."""
    return jnp.round(
        scale_by_pow2(blocks_f, (Q_FIXED_POINT - emax)[:, None])
    ).astype(jnp.int32)


def dequantize_blocks(blocks_i: jnp.ndarray, emax: jnp.ndarray,
                      dtype=jnp.float32) -> jnp.ndarray:
    return scale_by_pow2(blocks_i.astype(dtype),
                         (emax - Q_FIXED_POINT)[:, None])


def truncate_planes(u: jnp.ndarray, nplanes: jnp.ndarray) -> jnp.ndarray:
    """Zero all bit planes below the top ``nplanes`` (ZFP-style truncation)."""
    shift = jnp.clip(TOTAL_PLANES - nplanes, 0, 31).astype(jnp.int32)
    if shift.ndim == 1:
        shift = shift[:, None]
    keep_mask = (jnp.int32(-1) << shift)
    return u & keep_mask
