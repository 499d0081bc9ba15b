"""Plain reference of the fixed-accuracy ZFP block codec and of Algorithm 1.

Written from the format's definition, for the benchmark's ``correct``
comparison; it imports nothing of the program.

Format, per 4x4 block of a (..., H, W) float32 array (H, W multiples of 4,
blocks in row-major order, lane ``4 r + c`` holds row ``r``, column ``c``):

* ``emax``: the exponent ``e`` with ``max|x| = m 2^e``, ``m`` in [0.5, 1);
  0 where ``max|x| < 2^-120``.
* Values are scaled by ``2^(28 - emax)`` and rounded to the nearest integer
  (ties to even).
* A lifted integer transform decorrelates along each row, then along each
  column.  The inverse undoes the columns first.
* Coefficients are stored in negabinary, 30 bit planes, most significant
  first.  A block keeps its top ``nplanes`` planes.
* Plane counts start at ``emax - floor(log2(tol)) + 2`` (clipped to [0, 30];
  0 for an all-zero block) and grow by two, up to six times, while the
  decoded block misses the tolerance.
* Word ``k`` of a block's payload carries plane ``29 - 2k`` in bits 0..15
  and plane ``28 - 2k`` in bits 16..31, lane ``j`` in bit ``j``.
* Logical size: 2 header bytes per block plus 2 bytes per kept plane.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PLANES = 30
WORDS = 15
SHIFT = 28
GUESS = 2
FIX_ROUNDS = 6
C2 = 1.089           # Fox & Lindstrom's expected-L1 calibration for 2D


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def to_blocks(x):
    """(N, C, H, W) -> (N, C * H/4 * W/4, 4, 4) blocks (row, column)."""
    n, c, h, w = x.shape
    b = x.reshape(n, c, h // 4, 4, w // 4, 4).transpose(0, 1, 2, 4, 3, 5)
    return b.reshape(n, c * (h // 4) * (w // 4), 4, 4)


def from_blocks(b, shape):
    """Inverse of :func:`to_blocks` for a (C, H, W) sample shape."""
    c, h, w = shape
    n = b.shape[0]
    x = b.reshape(n, c, h // 4, w // 4, 4, 4).transpose(0, 1, 2, 4, 3, 5)
    return x.reshape(n, c, h, w)


def _pow2(k):
    """2^k as float32 for integer ``k`` in [-252, 252], exact."""
    return jnp.ldexp(jnp.ones(k.shape, jnp.float32), k)


# ---------------------------------------------------------------------------
# lifted transform along one axis of the (.., 4, 4) block
# ---------------------------------------------------------------------------

def _lift_fwd(v):
    x, y, z, w = v
    x = (x + w) >> 1
    w = w - x
    z = (z + y) >> 1
    y = y - z
    x = (x + z) >> 1
    z = z - x
    w = (w + y) >> 1
    y = y - w
    w = w + (y >> 1)
    y = y - (w >> 1)
    return [x, y, z, w]


def _lift_inv(v):
    x, y, z, w = v
    y = y + (w >> 1)
    w = w - (y >> 1)
    y = y + w
    w = 2 * w - y
    z = z + x
    x = 2 * x - z
    y = y + z
    z = 2 * z - y
    w = w + x
    x = 2 * x - w
    return [x, y, z, w]


def _along(fn, b, axis):
    parts = fn([jnp.take(b, i, axis=axis) for i in range(4)])
    return jnp.stack(parts, axis=axis)


def forward_transform(q):
    """Integer (..., 4, 4) blocks: rows first (along columns), then columns."""
    return _along(_lift_fwd, _along(_lift_fwd, q, -1), -2)


def inverse_transform(c):
    return _along(_lift_inv, _along(_lift_inv, c, -2), -1)


# ---------------------------------------------------------------------------
# negabinary planes
# ---------------------------------------------------------------------------

_NB = np.int32(-1431655766)         # bit pattern 0xAAAAAAAA


def to_negabinary(i):
    return (i + _NB) ^ _NB


def plane_bits(u):
    """(..., 4, 4) negabinary -> (..., 16, PLANES) bits, plane p at index p."""
    lanes = u.reshape(u.shape[:-2] + (16,))
    p = jnp.arange(PLANES, dtype=jnp.int32)
    return (lanes[..., None] >> p) & 1


def from_plane_bits(bits):
    """(..., 16, PLANES) bits -> (..., 4, 4) two's-complement integers.

    A negabinary digit string is ``sum_p bit_p (-2)^p``.
    """
    weights = jnp.asarray([(-2) ** p for p in range(PLANES)], jnp.int32)
    v = jnp.sum(bits * weights, axis=-1, dtype=jnp.int32)
    return v.reshape(v.shape[:-1] + (4, 4))


def keep_top(bits, nplanes):
    """Zero every plane below the top ``nplanes`` of each block."""
    p = jnp.arange(PLANES, dtype=jnp.int32)
    return bits * (p >= PLANES - nplanes[..., None, None]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# encode / decode of blocks
# ---------------------------------------------------------------------------

def block_exponent(b):
    amax = jnp.max(jnp.abs(b), axis=(-2, -1))
    _, e = jnp.frexp(amax)
    return jnp.where(amax >= 2.0 ** -120, e.astype(jnp.int32), 0)


def dequantize(ints, emax):
    return ints.astype(jnp.float32) * _pow2(emax - SHIFT)[..., None, None]


def decode_bits(bits, emax):
    return dequantize(inverse_transform(from_plane_bits(bits)), emax)


def encode_blocks(b, tol):
    """Fixed-accuracy encode of (..., 4, 4) f32 blocks at per-block ``tol``.

    Returns ``(bits (..., 16, PLANES), emax, nplanes)`` with the planes below
    each block's count already zeroed.
    """
    emax = block_exponent(b)
    q = jnp.round(b * _pow2(SHIFT - emax)[..., None, None]).astype(jnp.int32)
    full = plane_bits(to_negabinary(forward_transform(q)))
    _, te = jnp.frexp(tol)
    npl = jnp.clip(emax - (te.astype(jnp.int32) - 1) + GUESS, 0, PLANES)
    npl = jnp.where(jnp.any(full != 0, axis=(-2, -1)), npl, 0)
    for _ in range(FIX_ROUNDS):
        dec = decode_bits(keep_top(full, npl), emax)
        err = jnp.max(jnp.abs(dec - b), axis=(-2, -1))
        npl = jnp.where(err > tol, jnp.minimum(npl + 2, PLANES), npl)
    return keep_top(full, npl), emax, npl


def pack(bits):
    """(..., 16, PLANES) -> (..., WORDS) int32 payload words."""
    lane = jnp.arange(16, dtype=jnp.int32)[:, None]
    words = []
    for k in range(WORDS):
        hi = bits[..., :, PLANES - 1 - 2 * k]
        lo = bits[..., :, PLANES - 2 - 2 * k]
        words.append(jnp.sum(hi << lane[:, 0], axis=-1, dtype=jnp.int32)
                     | jnp.sum(lo << (lane[:, 0] + 16), axis=-1,
                               dtype=jnp.int32))
    return jnp.stack(words, axis=-1)


def unpack(words):
    """(..., W) int32 payload words -> (..., 16, PLANES) bits."""
    lane = jnp.arange(16, dtype=jnp.int32)
    planes = [jnp.zeros(words.shape[:-1] + (16,), jnp.int32)] * PLANES
    for k in range(words.shape[-1]):
        wd = words[..., k:k + 1]
        planes[PLANES - 1 - 2 * k] = (wd >> lane) & 1
        if PLANES - 2 - 2 * k >= 0:
            planes[PLANES - 2 - 2 * k] = (wd >> (lane + 16)) & 1
    return jnp.stack(planes, axis=-1)


# ---------------------------------------------------------------------------
# whole samples
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("shape",))
def decode(payload, emax, nplanes, shape):
    """(N, nb, W) payload, (N, nb) emax and nplanes -> (N, C, H, W) f32."""
    bits = keep_top(unpack(payload), nplanes)
    return from_blocks(decode_bits(bits, emax), shape)


@jax.jit
def encode(xs, tols):
    """(N, C, H, W) f32, (N,) tolerances -> (payload, emax, nplanes)."""
    b = to_blocks(xs)
    bits, emax, npl = encode_blocks(b, tols[:, None])
    return pack(bits), emax, npl


@jax.jit
def roundtrip_stats(xs, tols):
    """Per-sample (L1 error, compression ratio) of an encode at ``tols``."""
    b = to_blocks(xs)
    bits, emax, npl = encode_blocks(b, tols[:, None])
    dec = decode_bits(bits, emax)
    l1 = jnp.mean(jnp.abs(from_blocks(dec, xs.shape[1:]) - xs),
                  axis=(1, 2, 3))
    nbytes = 2 * npl.shape[1] + 2 * jnp.sum(npl, axis=1)
    size = xs.shape[1] * xs.shape[2] * xs.shape[3]
    return l1, size * 4.0 / nbytes


@jax.jit
def _first_guess(es):
    return (4.0 ** 2) * es / C2


def search(xs, model_l1, max_iters: int = 8) -> np.ndarray:
    """Algorithm 1 per sample: (N,) tolerances.

    Start at ``16 e / c(2)``; double while the L1 error of the roundtrip stays
    at or below ``e``, stopping early once the ratio grows by no more than
    1%; if the first guess already misses ``e``, halve until it meets it.
    A sample that never meets ``e`` keeps its last evaluated tolerance.
    """
    n = xs.shape[0]
    es = np.full((n,), model_l1, np.float32)
    t = np.array(_first_guess(jnp.asarray(es)), np.float32)
    best_t = np.zeros(n, np.float32)
    best_ratio = np.ones(n, np.float32)
    have = np.zeros(n, bool)
    down = np.zeros(n, bool)
    done = np.zeros(n, bool)
    t_eval = t.copy()
    for _ in range(max_iters):
        if done.all():
            break
        l1, ratio = (np.asarray(a) for a in roundtrip_stats(
            xs, jnp.asarray(t)))
        t_eval = np.where(done, t_eval, t)
        for i in np.flatnonzero(~done):
            if l1[i] <= es[i]:
                saturated = have[i] and ratio[i] <= best_ratio[i] * np.float32(1.01)
                best_t[i], best_ratio[i], have[i] = t[i], ratio[i], True
                if saturated or down[i]:
                    done[i] = True
                else:
                    t[i] = t[i] * 2
            elif have[i]:
                done[i] = True
            else:
                down[i] = True
                t[i] = t[i] / 2
    # a sample stopped by the iteration limit reports its last evaluated t
    return np.where(have, best_t, t_eval).astype(np.float32)
