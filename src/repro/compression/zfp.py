"""Pure-jnp reference ZFP codec: fixed-rate and error-bounded fixed-accuracy.

``backend="jnp"`` codecs (compression/api.py) run it, and tests hold the
Pallas kernels in repro.kernels bit-identical to it.

Layout differences vs CPU ZFP (see DESIGN.md §3): bit planes are packed two
per int32 word at deterministic per-block offsets (no group testing, no
variable-length bitstream), so decode is fully lane-parallel.  Fixed-accuracy
mode keeps a per-block plane count and *verifies* the L-inf bound with a
vectorized correction loop, giving a true error-bounded guarantee.

Logical storage (what would hit disk/network with the two-level layout):
  fixed-rate:      nb * (1 byte emax + 2 * bits_per_16values... see nbytes)
  fixed-accuracy:  nb * (2 bytes header) + sum_b 2 * nplanes_b bytes
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression import transform as T


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedField:
    """Pytree container for one compressed array.

    payload : (nb, W) int32  -- packed bit planes (W static; planes beyond
                                 nplanes[b] are zero for fixed-accuracy)
    emax    : (nb,)  int32   -- per-block shared exponent
    nplanes : (nb,)  int32   -- per-block kept planes (uniform for fixed-rate)
    shape   : original array shape (static)
    padded_shape : shape after padding trailing dims to multiples of 4 (static)
    """
    payload: jnp.ndarray
    emax: jnp.ndarray
    nplanes: jnp.ndarray
    shape: Tuple[int, ...]
    padded_shape: Tuple[int, ...]

    def tree_flatten(self):
        return (self.payload, self.emax, self.nplanes), (self.shape, self.padded_shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        payload, emax, nplanes = children
        return cls(payload, emax, nplanes, aux[0], aux[1])


# ---------------------------------------------------------------------------
# fixed-rate
# ---------------------------------------------------------------------------

def _encode_blocks(blocks_f: jnp.ndarray):
    emax = T.block_emax(blocks_f)
    qi = T.quantize_blocks(blocks_f, emax)
    coef = T.fwd_transform_2d(qi)
    u = T.int2nb(coef)
    return u, emax


def _decode_blocks(u: jnp.ndarray, emax: jnp.ndarray, dtype=jnp.float32):
    coef = T.nb2int(u)
    qi = T.inv_transform_2d(coef)
    return T.dequantize_blocks(qi, emax, dtype)


@partial(jax.jit, static_argnames=("bits_per_value",))
def encode_fixed_rate(x: jnp.ndarray, bits_per_value: int) -> CompressedField:
    """Compress with a uniform per-value plane count (dense payload layout)."""
    assert 0 < bits_per_value <= T.TOTAL_PLANES
    shape = x.shape
    xp = T.pad_to_blocks(x.astype(jnp.float32))
    blocks = T.blockify(xp)
    u, emax = _encode_blocks(blocks)
    nplanes = jnp.full((blocks.shape[0],), bits_per_value, dtype=jnp.int32)
    u = T.truncate_planes(u, nplanes)
    num_words = (bits_per_value + 1) // 2
    payload = T.pack_planes(u, num_words)
    return CompressedField(payload, emax, nplanes, shape, xp.shape)


@jax.jit
def decode_fixed_rate(cf: CompressedField) -> jnp.ndarray:
    u = T.unpack_planes(cf.payload)
    blocks = _decode_blocks(u, cf.emax)
    xp = T.deblockify(blocks, cf.padded_shape)
    return _crop(xp, cf.shape)


@partial(jax.jit, static_argnames=("bits_per_value",))
def encode_fixed_rate_batch(xs: jnp.ndarray,
                            bits_per_value: int) -> CompressedField:
    """Batched fixed-rate encode: one compiled call for a whole (N, ...) stack.

    Returns a CompressedField whose array leaves carry a leading batch axis
    (payload (N, nb, W), emax/nplanes (N, nb)); ``shape``/``padded_shape``
    describe a single sample, matching ``encode_fixed_accuracy_batch``.
    """
    assert 0 < bits_per_value <= T.TOTAL_PLANES
    return jax.vmap(lambda x: encode_fixed_rate(x, bits_per_value))(
        xs.astype(jnp.float32))


# ---------------------------------------------------------------------------
# fixed-accuracy (error-bounded)
# ---------------------------------------------------------------------------

def _planes_for_tolerance(emax: jnp.ndarray, tol: jnp.ndarray) -> jnp.ndarray:
    b = emax - T.floor_log2(tol) + T.GUARD_BITS
    return jnp.clip(b, 0, T.TOTAL_PLANES).astype(jnp.int32)


@jax.jit
def encode_fixed_accuracy(x: jnp.ndarray, tol: float) -> CompressedField:
    """Error-bounded compression: max |x - decode| <= tol, verified per block.

    A vectorized correction loop re-checks the realized per-block L-inf error
    and adds planes where violated (ZFP-style guarantees without the
    variable-length stream).
    """
    shape = x.shape
    xp = T.pad_to_blocks(x.astype(jnp.float32))
    blocks = T.blockify(xp)
    u_full, emax = _encode_blocks(blocks)
    tol = jnp.asarray(tol, jnp.float32)
    nplanes = _planes_for_tolerance(emax, tol)
    # all-zero blocks (flushed emax=0) need no planes at all
    nplanes = jnp.where(jnp.all(u_full == 0, axis=-1), 0, nplanes)

    def block_err(npl):
        u = T.truncate_planes(u_full, npl)
        dec = _decode_blocks(u, emax)
        return jnp.max(jnp.abs(dec - blocks), axis=-1)

    def cond(state):
        npl, it = state
        bad = (block_err(npl) > tol) & (npl < T.TOTAL_PLANES)
        return jnp.any(bad) & (it < T.MAX_FIX_ITERS)

    def body(state):
        npl, it = state
        bad = block_err(npl) > tol
        npl = jnp.where(bad, jnp.minimum(npl + 2, T.TOTAL_PLANES), npl)
        return npl, it + 1

    nplanes, _ = jax.lax.while_loop(cond, body, (nplanes, jnp.int32(0)))
    u = T.truncate_planes(u_full, nplanes)
    payload = T.pack_planes(u, T.MAX_WORDS)
    return CompressedField(payload, emax, nplanes, shape, xp.shape)


@jax.jit
def encode_fixed_accuracy_batch(xs: jnp.ndarray,
                                tols: jnp.ndarray) -> CompressedField:
    """Batched error-bounded encode: one compiled call for a whole stack.

    xs   : (N, ...) float array, compression over the trailing two dims
    tols : (N,) per-sample L-inf tolerances

    Returns a CompressedField whose array leaves carry a leading batch axis
    (payload (N, nb, MAX_WORDS), emax/nplanes (N, nb)); ``shape`` and
    ``padded_shape`` describe a single sample.  Per-sample results are
    bit-identical to :func:`encode_fixed_accuracy` — the vmapped while_loop
    runs the same correction arithmetic under a per-sample active mask.
    """
    tols = jnp.asarray(tols, jnp.float32)
    return jax.vmap(encode_fixed_accuracy)(xs.astype(jnp.float32), tols)


@jax.jit
def decode_batch(cf: CompressedField) -> jnp.ndarray:
    """Decode a batched CompressedField (from encode_fixed_accuracy_batch)."""
    return jax.vmap(decode)(cf)


@jax.jit
def decode(cf: CompressedField) -> jnp.ndarray:
    """Decode either mode (payload planes beyond nplanes are already zero)."""
    u = T.unpack_planes(cf.payload)
    u = T.truncate_planes(u, cf.nplanes)
    blocks = _decode_blocks(u, cf.emax)
    xp = T.deblockify(blocks, cf.padded_shape)
    return _crop(xp, cf.shape)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def _header_bytes_per_block(mode: str) -> int:
    """Per-block stream header: 1 byte emax always; fixed-accuracy adds a
    1-byte plane count (the decoder needs per-block counts to mask planes).

    ``mode`` is explicit, never inferred from the data: a fixed-accuracy
    stream whose plane counts *happen* to be uniform still ships per-block
    counts — the decoder cannot know they are uniform without reading them.
    """
    if mode == "fixed_accuracy":
        return 2
    if mode == "fixed_rate":
        return 1
    raise ValueError(f"unknown codec mode {mode!r}")


def compressed_nbytes(cf: CompressedField,
                      mode: str = "fixed_accuracy") -> jnp.ndarray:
    """Logical compressed size in bytes (two-level packed layout on disk).

    ``mode`` selects the header billing (see :func:`_header_bytes_per_block`);
    payload cost is 2 bytes per kept plane (16 lanes) either way.
    """
    nb = cf.nplanes.shape[0]
    return _header_bytes_per_block(mode) * nb + 2 * jnp.sum(cf.nplanes)


def compressed_nbytes_batch(cf: CompressedField,
                            mode: str = "fixed_accuracy") -> jnp.ndarray:
    """Per-sample logical bytes for a batched CompressedField: (N,) int."""
    nb = cf.nplanes.shape[-1]
    return (_header_bytes_per_block(mode) * nb
            + 2 * jnp.sum(cf.nplanes, axis=-1))


def compression_ratio(cf: CompressedField,
                      mode: str = "fixed_accuracy") -> jnp.ndarray:
    raw = int(np.prod(cf.shape)) * 4
    return raw / compressed_nbytes(cf, mode)


def trim_to_nplanes(cf: CompressedField) -> CompressedField:
    """Drop payload words beyond ``ceil(max(nplanes) / 2)`` (host-side).

    Words past a block's kept planes are zero by construction and both
    decode backends accept any width covering the deepest kept plane, so
    trimming is bit-exact while cutting device-resident HBM bytes and the
    decode kernel's static word-loop trips.  Concretizes ``nplanes`` (not
    jit-traceable) — call at store build/finalize time.
    """
    npl = np.asarray(cf.nplanes)
    w = max(int(np.ceil(int(npl.max(initial=0)) / 2)), 1)
    return CompressedField(cf.payload[..., :w], cf.emax, cf.nplanes,
                           cf.shape, cf.padded_shape)


def _crop(xp: jnp.ndarray, shape) -> jnp.ndarray:
    if tuple(xp.shape) == tuple(shape):
        return xp
    slices = tuple(slice(0, s) for s in shape)
    return xp[slices]
