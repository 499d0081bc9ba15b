"""Pallas TPU kernels for the ZFP block codec (fixed-rate + fixed-accuracy).

Layout.  The stored format is block-major: payload (nb, W) int32 with two
16-lane bit planes per word, MSB plane first, and (nb,) emax / nplanes.  The
kernels lay the blocks out in one of two ways:

  * block-major (fixed-rate encode, both decodes): one 4x4 block per row of
    16 lanes; the grid tiles the block axis at BLOCK_TILE rows in VMEM.
      decode:  payload (BT, W) int32 + emax (BT, 1) int32 -> (BT, 16) f32
      encode:  (BT, 16) f32 -> payload (BT, W) int32 + emax (BT, 1) int32
    16 of a vreg's 128 lanes do work, the transform's other direction needs
    lane shuffles, and the (nb, 16) / (nb, 1) operands are padded to 128
    lanes in HBM.
  * coefficient-major (fixed-accuracy encode, Algorithm 1's stats pass):
    16 slabs, one per coefficient k = 4r + c, each (R, 128) with one block
    per (row, lane); the grid tiles R at FA_TILE_ROWS (``fa_slab_rows``).
    Every step is elementwise across slabs, the 4x4 transposes are a
    re-indexing of the slab list, and both kernels take their plane counts
    from one helper (``_fa_planes``).
      encode: (16, TR, 128) f32 + tol (TR, 128) f32 -> payload
              (MAX_WORDS, TR, 128) int32 + emax, nplanes (TR, 128) int32
      stats:  (16, TR, 128) f32 + tol (TR, 128) f32 + valid-pixel bits
              (TR, 128) int32 -> nplanes (TR, 128) int32 + L1 sum
              (TR, 128) f32

All arithmetic is bitwise/elementwise on int32 lanes plus small static
loops: pure VPU work.

The kernel bodies re-implement the transform with TPU idioms (2D broadcasted
iota, no 1D arrays); tests validate against the independent pure-jnp oracle
in ref.py over shape sweeps (interpret mode on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compression.transform import (
    GUARD_BITS,
    MAX_FIX_ITERS,
    MAX_WORDS,
    Q_FIXED_POINT,
    TOTAL_PLANES,
    floor_log2,
    pow2_factors,
    scale_by_pow2,
)

BLOCK_TILE = 256          # blocks per VMEM tile: 256*16*4B = 16 KiB out tile
FA_TILE_ROWS = 128        # fixed-accuracy encode: 128-block rows per grid step
FA_SUB_ROWS = 8           # rows per inner step: one (8, 128) vreg per slab
_NEG = -1431655766  # 0xAAAAAAAA as int32 (python int: kernels may not capture jax arrays)


def _lanes16():
    return jax.lax.broadcasted_iota(jnp.int32, (1, 16), 1)


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


def _emax_slab(maxabs):
    """Per-block max |x| -> int32 frexp exponent (``transform.block_emax``,
    read from the exponent field); under 2^-120 flushes to 0."""
    return jnp.where(maxabs >= 2.0 ** -120, floor_log2(maxabs) + 1, 0)


def _tile_emax(x):
    """(BT, 16) f32 -> (BT, 1) int32 frexp exponent of each block's max |x|."""
    return _emax_slab(jnp.max(jnp.abs(x), axis=-1, keepdims=True))


def _transpose_blocks(a):
    """(BT, 16) row-major 4x4 blocks -> their transposes (lane 4r+c <-> 4c+r).

    Built from one-lane static slices: Mosaic lowers a lane-strided slice
    (``a[:, 0::4]``) or a ``(BT, 4, 4)`` reshape as a gather it refuses.
    """
    return jnp.concatenate([a[:, 4 * c + r:4 * c + r + 1]
                            for r in range(4) for c in range(4)], axis=-1)


def _lift_y(a, lift):
    """Apply ``lift`` along y: across the four rows of each block, which are
    its contiguous 4-lane groups."""
    x, y, z, w = lift(*[a[:, 4 * k:4 * k + 4] for k in range(4)])
    return jnp.concatenate([x, y, z, w], axis=-1)


def _inv_transform_tile(coef):
    """(BT, 16) int32 inverse 2D lift: columns, then rows (via transposes)."""
    b = _lift_y(coef, _inv_lift4)
    return _transpose_blocks(_lift_y(_transpose_blocks(b), _inv_lift4))


def _fwd_transform_tile(qi):
    """(BT, 16) int32 forward 2D lift: rows (via transposes), then columns."""
    b = _transpose_blocks(_lift_y(_transpose_blocks(qi), _fwd_lift4))
    return _lift_y(b, _fwd_lift4)


def _unpack_tile(payload, num_words):
    """(BT, W) plane words -> (BT, 16) negabinary; inverse of ``_pack_tile``."""
    lanes = _lanes16()
    u = jnp.zeros((payload.shape[0], 16), jnp.int32)
    for k in range(num_words):                        # static unroll
        word = payload[:, k:k + 1]                    # (BT, 1)
        p_hi = TOTAL_PLANES - 1 - 2 * k
        p_lo = TOTAL_PLANES - 2 - 2 * k
        u = u | (((word >> lanes) & 1) << p_hi)
        if p_lo >= 0:
            u = u | (((word >> (lanes + 16)) & 1) << p_lo)
    return u


def _pack_tile(u, num_words):
    """(BT, 16) negabinary -> (BT, num_words) plane words, MSB plane first.

    Each word is a keepdims lane reduction, and the tile is stored once:
    Mosaic refuses 1-D column stores into the payload ref.
    """
    lanes = _lanes16()
    words = []
    for k in range(num_words):
        p_hi = TOTAL_PLANES - 1 - 2 * k
        p_lo = TOTAL_PLANES - 2 - 2 * k
        word = jnp.sum(((u >> p_hi) & 1) << lanes, axis=-1, keepdims=True,
                       dtype=jnp.int32)
        if p_lo >= 0:
            word = word | (jnp.sum(((u >> p_lo) & 1) << lanes, axis=-1,
                                   keepdims=True, dtype=jnp.int32) << 16)
        words.append(word)
    return jnp.concatenate(words, axis=-1)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode_kernel(payload_ref, emax_ref, out_ref, *, num_words):
    payload = payload_ref[...]                        # (BT, W) int32
    emax = emax_ref[...]                              # (BT, 1) int32
    u = _unpack_tile(payload, num_words)
    neg = jnp.int32(_NEG)
    coef = (u ^ neg) - neg                            # negabinary -> int
    qi = _inv_transform_tile(coef)
    out_ref[...] = scale_by_pow2(qi.astype(jnp.float32), emax - Q_FIXED_POINT)


@functools.partial(jax.jit, static_argnames=("bits_per_value", "interpret"))
def zfp_decode_blocks(payload: jnp.ndarray, emax: jnp.ndarray,
                      bits_per_value: int, interpret: bool = False) -> jnp.ndarray:
    """Pallas fixed-rate decode: ((nb, W) int32, (nb,) int32) -> (nb, 16) f32."""
    nb, num_words = payload.shape
    assert num_words == (bits_per_value + 1) // 2
    pad = (-nb) % BLOCK_TILE
    if pad:
        payload = jnp.pad(payload, ((0, pad), (0, 0)))
        emax = jnp.pad(emax, ((0, pad),))
    nbp = payload.shape[0]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, num_words=num_words),
        grid=(nbp // BLOCK_TILE,),
        in_specs=[
            pl.BlockSpec((BLOCK_TILE, num_words), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_TILE, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_TILE, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, 16), jnp.float32),
        interpret=interpret,
    )(payload, emax[:, None])
    return out[:nb]


def _decode_fa_kernel(payload_ref, emax_ref, nplanes_ref, out_ref, *,
                      num_words):
    """Fixed-accuracy decode tile: per-block variable plane counts.

    Identical unpack arithmetic to ``_decode_kernel`` plus an in-register
    truncation mask derived from the per-block ``nplanes`` — the stored
    stream keeps only the top ``nplanes[b]`` planes of block ``b``, so any
    bits unpacked below that boundary (payloads are padded to a common word
    width when batched) are zeroed before the inverse transform.
    """
    payload = payload_ref[...]                        # (BT, W) int32
    emax = emax_ref[...]                              # (BT, 1) int32
    npl = nplanes_ref[...]                            # (BT, 1) int32
    u = _unpack_tile(payload, num_words)
    shift = jnp.clip(TOTAL_PLANES - npl, 0, 31)       # (BT, 1), broadcasts
    u = u & (jnp.int32(-1) << shift)                  # zero dropped planes
    neg = jnp.int32(_NEG)
    coef = (u ^ neg) - neg                            # negabinary -> int
    qi = _inv_transform_tile(coef)
    out_ref[...] = scale_by_pow2(qi.astype(jnp.float32), emax - Q_FIXED_POINT)


@functools.partial(jax.jit, static_argnames=("interpret",))
def zfp_decode_blocks_fa(payload: jnp.ndarray, emax: jnp.ndarray,
                         nplanes: jnp.ndarray,
                         interpret: bool = False) -> jnp.ndarray:
    """Pallas fixed-accuracy decode with per-block plane counts.

    ((nb, W) int32, (nb,) int32, (nb,) int32) -> (nb, 16) f32.  This is the
    paper's actual training-time workload: error-bounded streams whose kept
    plane count varies block to block (``encode_fixed_accuracy``), batched
    at a common payload width.  The word count is taken from the payload
    shape; blocks whose ``nplanes`` is smaller simply mask deeper planes off.
    """
    nb, num_words = payload.shape
    pad = (-nb) % BLOCK_TILE
    if pad:
        payload = jnp.pad(payload, ((0, pad), (0, 0)))
        emax = jnp.pad(emax, ((0, pad),))
        nplanes = jnp.pad(nplanes, ((0, pad),))
    nbp = payload.shape[0]
    out = pl.pallas_call(
        functools.partial(_decode_fa_kernel, num_words=num_words),
        grid=(nbp // BLOCK_TILE,),
        in_specs=[
            pl.BlockSpec((BLOCK_TILE, num_words), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_TILE, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_TILE, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, 16), jnp.float32),
        interpret=interpret,
    )(payload, emax[:, None], nplanes[:, None].astype(jnp.int32))
    return out[:nb]


# ---------------------------------------------------------------------------
# encode
# ---------------------------------------------------------------------------

def _encode_kernel(blocks_ref, payload_ref, emax_ref, *, num_words, bits):
    x = blocks_ref[...]                               # (BT, 16) f32
    emax = _tile_emax(x)                              # (BT, 1) int32
    qi = jnp.round(scale_by_pow2(x, Q_FIXED_POINT - emax)).astype(jnp.int32)
    coef = _fwd_transform_tile(qi)
    neg = jnp.int32(_NEG)
    u = (coef + neg) ^ neg                            # int -> negabinary
    shift = TOTAL_PLANES - bits
    u = u & (jnp.int32(-1) << shift)                  # truncate planes
    payload_ref[...] = _pack_tile(u, num_words)
    emax_ref[...] = emax


def _fwd_transform_slabs(s):
    """Forward 2D lift on 16 coefficient slabs (``s[4r + c]``): along x
    within each row r, then along y within each column c."""
    s = list(s)
    for r in range(4):
        s[4 * r:4 * r + 4] = _fwd_lift4(*s[4 * r:4 * r + 4])
    for c in range(4):
        s[c::4] = _fwd_lift4(*s[c::4])
    return s


def _inv_transform_slabs(s):
    """Inverse of ``_fwd_transform_slabs``: along y, then along x."""
    s = list(s)
    for c in range(4):
        s[c::4] = _inv_lift4(*s[c::4])
    for r in range(4):
        s[4 * r:4 * r + 4] = _inv_lift4(*s[4 * r:4 * r + 4])
    return s


def fa_slab_rows(nb: int):
    """(rows, tile) of the coefficient-major kernels for ``nb`` blocks.

    The blocks lie along the 128 lanes, ``rows`` rows of them: enough for
    ``nb``, rounded up to whole grid tiles of ``tile`` rows (``FA_TILE_ROWS``,
    or fewer, a multiple of ``FA_SUB_ROWS``, for a short stack).
    """
    rows = -(-nb // 128)
    tile = min(FA_TILE_ROWS, -(-rows // FA_SUB_ROWS) * FA_SUB_ROWS)
    return -(-rows // tile) * tile, tile


def _fa_planes(x, tol, valid=None):
    """Fixed-accuracy plane counts of one sub-tile, coefficient-major.

    ``x`` is the 16 (FA_SUB_ROWS, 128) f32 slabs (slab k holds coefficient
    k = 4r + c of one block per (row, lane)), ``tol`` the blocks' L-inf
    tolerances.  Every step is elementwise across the slabs: quantize,
    forward lift, negabinary, the plane guess (``emax - floor(log2(tol)) +
    GUARD_BITS``, the floor read from the exponent field), zero blocks to 0
    planes, then the bound-verification correction: two more planes for
    every block whose decode misses its tolerance, until a step grows no
    block of the sub-tile or after ``MAX_FIX_ITERS`` steps.  The jnp
    encoder's while_loop runs the same step at most that many times, and
    after a step that grows nothing every later step is a no-op, so stopping
    there is bit-exact.

    Returns ``(emax, u_full, npl, l1)``: the blocks' exponents, their
    full-precision negabinary slabs and their plane counts.  Given
    ``valid``, the blocks' valid-pixel bits (bit k for coefficient k), ``l1``
    is each block's ``sum |decode - x|`` at those plane counts over its
    valid pixels, added in coefficient order 0..15: the correction's last
    decode where that step grew nothing, one more decode where it stopped
    at ``MAX_FIX_ITERS``.  Without ``valid`` it is None.
    """
    neg = jnp.int32(_NEG)
    emax = _emax_slab(functools.reduce(jnp.maximum, map(jnp.abs, x)))
    f1, f2 = pow2_factors(Q_FIXED_POINT - emax)
    qi = [jnp.round((v * f1) * f2).astype(jnp.int32) for v in x]
    u_full = [(c + neg) ^ neg for c in _fwd_transform_slabs(qi)]

    npl = jnp.clip(emax - floor_log2(tol) + GUARD_BITS, 0, TOTAL_PLANES)
    npl = jnp.where(functools.reduce(jnp.bitwise_or, u_full) == 0, 0, npl)
    g1, g2 = pow2_factors(emax - Q_FIXED_POINT)

    def errors(npl):
        """|decode - x| of each coefficient at ``npl`` planes."""
        keep = jnp.int32(-1) << jnp.clip(TOTAL_PLANES - npl, 0, 31)
        deci = _inv_transform_slabs([((u & keep) ^ neg) - neg for u in u_full])
        return [jnp.abs((d.astype(jnp.float32) * g1) * g2 - v)
                for d, v in zip(deci, x)]

    def l1_of(err):
        return functools.reduce(jnp.add, [
            jnp.where(((valid >> k) & 1) != 0, e, 0.0)
            for k, e in enumerate(err)])

    def more(state):
        it, _, grew, _ = state
        return (it < MAX_FIX_ITERS) & grew

    def fix(state):
        it, npl, _, l1 = state
        err = errors(npl)
        grow = ((functools.reduce(jnp.maximum, err) > tol)
                & (npl < TOTAL_PLANES))
        if valid is not None:
            l1 = l1_of(err)
        return (it + 1, jnp.where(grow, jnp.minimum(npl + 2, TOTAL_PLANES),
                                  npl),
                jnp.max(grow.astype(jnp.int32)) > 0, l1)

    l1 = None if valid is None else jnp.zeros(tol.shape, jnp.float32)
    _, npl, grew, l1 = jax.lax.while_loop(more, fix, (0, npl, True, l1))
    if valid is not None:
        l1 = jax.lax.cond(grew, lambda: l1_of(errors(npl)), lambda: l1)
    return emax, u_full, npl, l1


def _encode_fa_kernel(x_ref, tol_ref, payload_ref, emax_ref, nplanes_ref):
    """Fixed-accuracy encode of one tile, coefficient-major.

    ``x_ref`` is (16, TR, 128): slab k holds coefficient k = 4r + c of
    TR x 128 blocks, one block per (row, lane).  The tile is walked
    ``FA_SUB_ROWS`` rows at a time, so every slab is one vreg: the plane
    counts come from ``_fa_planes``, then the truncated pack: word w is
    plane 29 - 2w in bits 0..15 and plane 28 - 2w in bits 16..31, bit k
    from coefficient k.
    """
    def sub_tile(j, carry):
        rows = pl.ds(pl.multiple_of(j * FA_SUB_ROWS, FA_SUB_ROWS), FA_SUB_ROWS)
        emax, u_full, npl, _ = _fa_planes(
            [x_ref[k, rows, :] for k in range(16)], tol_ref[rows, :])
        keep = jnp.int32(-1) << jnp.clip(TOTAL_PLANES - npl, 0, 31)
        u = [v & keep for v in u_full]                # truncate kept planes
        for w in range(MAX_WORDS):
            p_hi, p_lo = TOTAL_PLANES - 1 - 2 * w, TOTAL_PLANES - 2 - 2 * w
            payload_ref[w, rows, :] = functools.reduce(jnp.bitwise_or, [
                (((u[k] >> p_hi) & 1) << k) | (((u[k] >> p_lo) & 1) << k + 16)
                for k in range(16)])
        emax_ref[rows, :] = emax
        nplanes_ref[rows, :] = npl
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // FA_SUB_ROWS, sub_tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def zfp_encode_blocks_fa(coefs: jnp.ndarray, tols: jnp.ndarray,
                         interpret: bool = False):
    """Pallas fixed-accuracy encode with per-block L-inf tolerances.

    ((16, nb) f32 coefficient-major blocks, (nb,) f32) -> ((nb, MAX_WORDS)
    int32 payload, (nb,) int32 emax, (nb,) int32 nplanes), bit-identical per
    block to ``compression/zfp.py::encode_fixed_accuracy``.  Row k of
    ``coefs`` is coefficient k = 4r + c of every block
    (``transform.blockify_coef_major``); the blocks are laid along the 128
    lanes as (16, R, 128) slabs, and the word-major payload is turned back
    into the stored (nb, MAX_WORDS) layout.  Batch callers repeat a
    sample's tolerance across its blocks; the arithmetic never couples
    blocks, so flattening sample stacks is exact.
    """
    nb = coefs.shape[1]
    rows, tile = fa_slab_rows(nb)
    pad = rows * 128 - nb
    x = jnp.pad(coefs.astype(jnp.float32), ((0, 0), (0, pad)))
    tols = jnp.pad(jnp.asarray(tols, jnp.float32), ((0, pad),),
                   constant_values=1.0)
    slab = pl.BlockSpec((tile, 128), lambda i: (i, 0))
    payload, emax, nplanes = pl.pallas_call(
        _encode_fa_kernel,
        grid=(rows // tile,),
        in_specs=[pl.BlockSpec((16, tile, 128), lambda i: (0, i, 0)), slab],
        out_specs=[pl.BlockSpec((MAX_WORDS, tile, 128), lambda i: (0, i, 0)),
                   slab, slab],
        out_shape=[
            jax.ShapeDtypeStruct((MAX_WORDS, rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        ],
        interpret=interpret,
    )(x.reshape(16, rows, 128), tols.reshape(rows, 128))
    return (payload.reshape(MAX_WORDS, -1)[:, :nb].T,
            emax.reshape(-1)[:nb], nplanes.reshape(-1)[:nb])


# ---------------------------------------------------------------------------
# Algorithm 1's stats pass
# ---------------------------------------------------------------------------

def _search_stats_fa_kernel(x_ref, tol_ref, valid_ref, nplanes_ref, l1_ref):
    """Plane counts and L1 error of one tile at its blocks' tolerances.

    The tile is the encode kernel's: (16, TR, 128) coefficient slabs walked
    ``FA_SUB_ROWS`` rows at a time, planes from the shared ``_fa_planes``.
    In place of the pack, each block's ``sum |dec - x|`` at its final plane
    counts, over the pixels whose bit is set in ``valid_ref`` (the edge
    padding of a field whose H or W is not a multiple of 4 is left out).
    """
    def sub_tile(j, carry):
        rows = pl.ds(pl.multiple_of(j * FA_SUB_ROWS, FA_SUB_ROWS), FA_SUB_ROWS)
        _, _, npl, l1 = _fa_planes([x_ref[k, rows, :] for k in range(16)],
                                   tol_ref[rows, :], valid_ref[rows, :])
        nplanes_ref[rows, :] = npl
        l1_ref[rows, :] = l1
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // FA_SUB_ROWS, sub_tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def zfp_search_stats_fa(slabs: jnp.ndarray, tols: jnp.ndarray,
                        valid: jnp.ndarray, interpret: bool = False):
    """Pallas stats pass of Algorithm 1: what a fixed-accuracy encode at
    ``tols`` would keep, and what it would lose, with no payload.

    ((16, R, 128) f32 coefficient slabs, (R, 128) f32 tolerances, (R, 128)
    int32 valid-pixel masks) -> ((R, 128) int32 plane counts, (R, 128) f32
    per-block ``sum |decode - x|`` over the valid pixels).  Block b is at
    ``[:, b // 128, b % 128]``; ``R`` is ``fa_slab_rows(nb)[0]``, so the
    caller lays the stack out once and every pass reads it as it lies.  The
    plane counts are ``zfp_encode_blocks_fa``'s, by the same code
    (``_fa_planes``); pad blocks of zeros read 0 planes and, with a mask of
    0, an L1 of 0.
    """
    rows = slabs.shape[1]
    tile = min(FA_TILE_ROWS, rows)
    assert rows % tile == 0 and tile % FA_SUB_ROWS == 0, rows
    slab = pl.BlockSpec((tile, 128), lambda i: (i, 0))
    return pl.pallas_call(
        _search_stats_fa_kernel,
        grid=(rows // tile,),
        in_specs=[pl.BlockSpec((16, tile, 128), lambda i: (0, i, 0)),
                  slab, slab],
        out_specs=[slab, slab],
        out_shape=[jax.ShapeDtypeStruct((rows, 128), jnp.int32),
                   jax.ShapeDtypeStruct((rows, 128), jnp.float32)],
        interpret=interpret,
    )(slabs.astype(jnp.float32), tols.astype(jnp.float32),
      valid.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bits_per_value", "interpret"))
def zfp_encode_blocks(blocks: jnp.ndarray, bits_per_value: int,
                      interpret: bool = False):
    """Pallas fixed-rate encode: (nb, 16) f32 -> ((nb, W) int32, (nb,) int32)."""
    nb = blocks.shape[0]
    num_words = (bits_per_value + 1) // 2
    pad = (-nb) % BLOCK_TILE
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0)))
    nbp = blocks.shape[0]
    payload, emax = pl.pallas_call(
        functools.partial(_encode_kernel, num_words=num_words, bits=bits_per_value),
        grid=(nbp // BLOCK_TILE,),
        in_specs=[pl.BlockSpec((BLOCK_TILE, 16), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((BLOCK_TILE, num_words), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_TILE, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, num_words), jnp.int32),
            jax.ShapeDtypeStruct((nbp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(blocks)
    return payload[:nb], emax[:nb, 0]
