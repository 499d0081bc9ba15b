"""Unified Codec layer: one interface over every compression path.

Every consumer of the ZFP codec (sharded stores, the streaming producer,
Algorithm-1 tolerance search, the device-resident training path) used to
call mode-specific free functions (``encode_fixed_accuracy_batch``,
``encode_fixed_rate_batch``, ``decode_stacked_payloads``...).  This module
is the single seam instead:

  Codec.encode_batch(xs[, tolerances]) -> CompressedField   (batched)
  Codec.decode_batch(cf)               -> (N, ...) float32
  Codec.nbytes(cf)                     -> (N,) logical bytes

Codecs behind one registry:

  get_codec("fixed_accuracy", tolerance=1e-3)      # error-bounded
  get_codec("fixed_rate", bits_per_value=12)       # uniform rate

Codec instances are frozen dataclasses — hashable, so they can ride through
``jax.jit`` static arguments — and every method is jit-traceable: the fused
gather→decode train step (repro.train.source) traces ``decode_stacked_payloads``
directly into the compiled step.

This is the only module that routes to the kernels.  The default
``backend="pallas"`` runs the entries of ``repro.kernels.ops``, which pick
the compiled Pallas kernel on a TPU and its jitted jnp oracle anywhere else.
``backend="jnp"`` runs the pure-jnp reference codec of compression/zfp.py,
which tests and the benchmark's reference checks compare the kernels
against, bit for bit.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Mapping, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression import transform as T
from repro.compression.zfp import (
    CompressedField, _header_bytes_per_block, compressed_nbytes_batch,
    decode_batch as _decode_batch_jnp, encode_fixed_accuracy_batch,
    encode_fixed_rate_batch, trim_to_nplanes,
)
from repro.obs import trace as obs_trace

BACKENDS = ("jnp", "pallas")


@runtime_checkable
class Codec(Protocol):
    """What the data/datagen/train layers require of a compression codec.

    ``field_to_arrays`` / ``field_from_arrays`` are the persistence hooks:
    they turn a codec's compressed-field container into named plain arrays
    (and back), so manifest-writing consumers (checkpoints, stores) never
    need to know which container class a codec returns.
    """
    backend: str

    @property
    def name(self) -> str: ...

    def encode_batch(self, xs, tolerances=None) -> CompressedField: ...

    def decode_batch(self, cf: CompressedField) -> jnp.ndarray: ...

    def nbytes(self, cf: CompressedField) -> jnp.ndarray: ...

    def field_to_arrays(self, cf) -> Dict[str, np.ndarray]: ...

    def field_from_arrays(self, arrays: Mapping[str, Any], shape2d): ...


def decode_stacked_payloads(payload, emax, padded_shape, shape,
                            nplanes=None) -> jnp.ndarray:
    """One-kernel decode of a stacked batch of packed ZFP streams.

    payload: (B, nb, wmax) int32 plane words, emax: (B, nb) int32.  Samples
    narrower than wmax are zero-padded (zero words decode as zero planes),
    so the result is exact per sample.  With ``nplanes`` (B, nb) the
    fixed-accuracy kernel masks each block's dropped planes explicitly —
    required when payloads may carry nonzero bits beyond a block's kept
    planes (e.g. a fixed-rate stream reinterpreted at a lower rate), and the
    path the device-resident store traces into the jitted train step.

    The single implementation of the batch-decode tail, shared by
    CompressedArrayStore / ShardedCompressedStore / DeviceResidentStore —
    their bit-exactness contract rides on this being one function.  Accepts
    numpy or jax arrays and is jit-traceable.
    """
    from repro.kernels import ops        # lazy: kernels rank above compression
    b, nb, wmax = payload.shape
    flat_p = jnp.reshape(jnp.asarray(payload), (b * nb, wmax))
    flat_e = jnp.reshape(jnp.asarray(emax), (b * nb,))
    if nplanes is None:
        blocks = ops.zfp_decode_blocks(flat_p, flat_e, 2 * wmax)
    else:
        flat_n = jnp.reshape(jnp.asarray(nplanes), (b * nb,))
        blocks = ops.zfp_decode_blocks_fa(flat_p, flat_e, flat_n)
    batch = T.deblockify(blocks, (b,) + tuple(padded_shape))
    return batch[(slice(None),) + tuple(slice(0, s) for s in shape)]


def _decode_batch_kernel(cf: CompressedField) -> jnp.ndarray:
    """Kernel-path batched decode of a (N, ...)-leaved CompressedField."""
    return decode_stacked_payloads(cf.payload, cf.emax, cf.padded_shape,
                                   cf.shape, nplanes=cf.nplanes)


@jax.jit
def _encode_fa_kernel(xs: jnp.ndarray, tols: jnp.ndarray) -> CompressedField:
    """Kernel-path fixed-accuracy encode of a (N, ...) stack at (N,) L-inf
    tolerances.  The padded stack goes to the kernel coefficient-major,
    (16, N*nb) from one transpose, so it lays the blocks along its 128
    lanes; no (nb, 16) array is built."""
    from repro.kernels import ops        # lazy: kernels rank above compression
    tols = jnp.asarray(tols, jnp.float32)
    n = xs.shape[0]
    xp = T.pad_to_blocks(xs.astype(jnp.float32))
    coefs = T.blockify_coef_major(xp)                # (16, N * nb)
    nb = coefs.shape[1] // n
    payload, emax, nplanes = ops.zfp_encode_blocks_fa(
        coefs, jnp.repeat(tols, nb))
    return CompressedField(payload.reshape(n, nb, -1), emax.reshape(n, nb),
                           nplanes.reshape(n, nb), xs.shape[1:], xp.shape[1:])


@partial(jax.jit, static_argnames=("bits_per_value",))
def _encode_fr_kernel(xs: jnp.ndarray, bits_per_value: int) -> CompressedField:
    """Kernel-path fixed-rate encode: all N samples' blocks go to the kernel
    as one (N*nb, 16) grid, so it tiles a single long block axis."""
    assert 0 < bits_per_value <= T.TOTAL_PLANES
    from repro.kernels import ops        # lazy: kernels rank above compression
    n = xs.shape[0]
    xp = T.pad_to_blocks(xs.astype(jnp.float32))
    blocks = T.blockify(xp)                          # (N * nb, 16)
    payload, emax = ops.zfp_encode_blocks(blocks, bits_per_value)
    nb = blocks.shape[0] // n
    nplanes = jnp.full((n, nb), bits_per_value, dtype=jnp.int32)
    return CompressedField(payload.reshape(n, nb, -1), emax.reshape(n, nb),
                           nplanes, xs.shape[1:], xp.shape[1:])


# ---------------------------------------------------------------------------
# Algorithm 1's stats pass (core/tolerance.py's search body)
# ---------------------------------------------------------------------------
# The tolerance search evaluates many tolerances against the SAME sample
# stack and needs, per tolerance, only each sample's L1 error and logical
# bytes.  ``fa_precompute_batch`` lays the padded stack out once, as the
# coefficient-major (16, R, 128) slabs of the fixed-accuracy kernels with a
# per-block valid-pixel mask beside it; each search iteration is then one
# ``ops.zfp_search_stats_fa`` pass over the slabs (quantize, lift, plane
# guess and correction as the encode kernel runs them, then the truncated
# decode reduced to a per-block L1 sum) and two small per-sample sums.  No
# payload is packed: packing waits for the accepted tolerance.  The plane
# counts are the encode's by construction, and the L1 is the roundtrip's up
# to the order of its sum.


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FAEncodeState:
    """A (N, ...) sample stack laid out for the search's stats passes.

    slabs : (16, R, 128) float32 coefficient-major padded blocks
    valid : (R, 128) int32 per block, bit 4r + c set where pixel (r, c)
            lies in the field, not in its edge padding; 0 past the stack
    n, nb : samples, and blocks per sample
    sample_size : values per (unpadded) sample
    """
    slabs: jnp.ndarray
    valid: jnp.ndarray
    n: int
    nb: int
    sample_size: int

    def tree_flatten(self):
        return ((self.slabs, self.valid),
                (self.n, self.nb, self.sample_size))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _valid_bits(rows: int, n_blocks: int, h: int, w: int) -> jnp.ndarray:
    """(rows, 128) valid-pixel bits of the blocks of an (..., h, w) stack
    padded to multiples of 4, in ``blockify`` order."""
    hb, wb = -(-h // 4), -(-w // 4)
    b = (jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 0) * 128
         + jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1))
    p = b % (hb * wb)
    n_rows = jnp.clip(h - 4 * (p // wb), 0, 4)
    row_bits = (1 << jnp.clip(w - 4 * (p % wb), 0, 4)) - 1
    bits = sum(jnp.where(r < n_rows, row_bits << 4 * r, 0) for r in range(4))
    return jnp.where(b < n_blocks, bits, 0)


@jax.jit
def fa_precompute_batch(xs: jnp.ndarray) -> FAEncodeState:
    """Lay a (N, ...) stack out once for the stats passes of a search."""
    from repro.kernels import ops        # lazy: kernels rank above compression
    n = xs.shape[0]
    xp = T.pad_to_blocks(xs.astype(jnp.float32))
    coefs = T.blockify_coef_major(xp)                # (16, N * nb)
    rows, _ = ops.fa_slab_rows(coefs.shape[1])
    slabs = jnp.pad(coefs, ((0, 0), (0, rows * 128 - coefs.shape[1])))
    valid = _valid_bits(rows, coefs.shape[1], *xs.shape[-2:])
    return FAEncodeState(slabs.reshape(16, rows, 128), valid, n,
                         coefs.shape[1] // n, int(np.prod(xs.shape[1:])))


def fa_stats_batch(state: FAEncodeState, tols: jnp.ndarray):
    """Stats-only roundtrip: per-sample ``(l1, nbytes)`` at tolerances ``tols``.

    ``nbytes`` is the fixed-accuracy encode's at ``tols``, exactly; ``l1``
    is ``mean |decode(encode(xs, tols)) - xs|`` summed per block first.
    """
    from repro.kernels import ops        # lazy: kernels rank above compression
    n, nb = state.n, state.nb
    rows = state.valid.shape[0]
    tol = jnp.pad(jnp.repeat(jnp.asarray(tols, jnp.float32), nb),
                  (0, rows * 128 - n * nb), constant_values=1.0)
    npl, l1 = ops.zfp_search_stats_fa(state.slabs, tol.reshape(rows, 128),
                                      state.valid)
    npl = npl.reshape(-1)[:n * nb].reshape(n, nb)
    l1 = jnp.sum(l1.reshape(-1)[:n * nb].reshape(n, nb), axis=1)
    nbytes = (_header_bytes_per_block("fixed_accuracy") * nb
              + 2 * jnp.sum(npl, axis=1))
    return l1 / state.sample_size, nbytes


def _pad4(shape2d) -> Tuple[int, ...]:
    r, c = shape2d
    return (r + (-r) % 4, c + (-c) % 4)


def _cf_to_arrays(cf: CompressedField) -> Dict[str, np.ndarray]:
    """Batched CompressedField -> named plain arrays, payload trimmed to the
    width its kept planes actually need (``trim_to_nplanes``; dropped words
    are zero by construction and both decode backends accept any narrower
    static width)."""
    cf = trim_to_nplanes(cf)
    return {"payload": np.asarray(cf.payload),
            "emax": np.asarray(cf.emax),
            "nplanes": np.asarray(cf.nplanes)}


def _cf_from_arrays(arrays: Mapping[str, Any], shape2d) -> CompressedField:
    shape2d = tuple(int(s) for s in shape2d)
    return CompressedField(jnp.asarray(arrays["payload"]),
                           jnp.asarray(arrays["emax"]),
                           jnp.asarray(arrays["nplanes"]),
                           shape2d, _pad4(shape2d))


@dataclasses.dataclass(frozen=True)
class FixedAccuracyCodec:
    """Error-bounded mode: per-sample L-inf tolerances, per-block plane counts.

    ``tolerance`` is the default when ``encode_batch`` is called without
    per-sample tolerances (Algorithm 1 supplies per-sample ones).
    """
    tolerance: Optional[float] = None
    backend: str = "pallas"

    @property
    def name(self) -> str:
        return "fixed_accuracy"

    def encode_batch(self, xs, tolerances=None) -> CompressedField:
        """Encode ``xs`` at per-sample tolerances; the ``codec.encode_batch``
        span times the host's side of it (the dispatch, or the trace under
        ``jit``), not the device's."""
        if tolerances is None:
            if self.tolerance is None:
                raise ValueError("fixed_accuracy encode needs per-sample "
                                 "tolerances or a codec-level default")
            tolerances = jnp.full((xs.shape[0],), self.tolerance, jnp.float32)
        encode = (_encode_fa_kernel if self.backend == "pallas"
                  else encode_fixed_accuracy_batch)
        with obs_trace.span("codec.encode_batch", cat="codec",
                            samples=int(xs.shape[0])):
            return encode(xs, jnp.asarray(tolerances, jnp.float32))

    def decode_batch(self, cf: CompressedField) -> jnp.ndarray:
        if self.backend == "pallas":
            return _decode_batch_kernel(cf)
        return _decode_batch_jnp(cf)

    def nbytes(self, cf: CompressedField) -> jnp.ndarray:
        return compressed_nbytes_batch(cf, mode="fixed_accuracy")

    field_to_arrays = staticmethod(_cf_to_arrays)
    field_from_arrays = staticmethod(_cf_from_arrays)


@dataclasses.dataclass(frozen=True)
class FixedRateCodec:
    """Uniform bits-per-value mode (dense payload, no per-block headers)."""
    bits_per_value: int = 12
    backend: str = "pallas"

    @property
    def name(self) -> str:
        return "fixed_rate"

    def encode_batch(self, xs, tolerances=None) -> CompressedField:
        del tolerances                   # rate is fixed; no error bound
        encode = (_encode_fr_kernel if self.backend == "pallas"
                  else encode_fixed_rate_batch)
        return encode(xs, self.bits_per_value)

    def decode_batch(self, cf: CompressedField) -> jnp.ndarray:
        if self.backend == "pallas":
            return _decode_batch_kernel(cf)
        return _decode_batch_jnp(cf)

    def nbytes(self, cf: CompressedField) -> jnp.ndarray:
        return compressed_nbytes_batch(cf, mode="fixed_rate")

    field_to_arrays = staticmethod(_cf_to_arrays)
    field_from_arrays = staticmethod(_cf_from_arrays)


# ---------------------------------------------------------------------------
# NeurLZ-style learned residual correction
# ---------------------------------------------------------------------------

_CORR_K = 6          # corrector features: bias, center, 4-neighborhood


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ResidualCorrectedField:
    """A fixed-accuracy stream plus a tiny per-sample learned corrector.

    ``weights`` ((N, K) float32) are closed-form ridge-regression
    coefficients mapping local features of the *decoded* field to the
    encode-time residual; ``tols`` ((N,) float32) is each sample's L-inf
    tolerance, which also clips the correction so the certified bound
    degrades at most to 2*tol while the realized L1 error only ever shrinks
    (samples where correction does not help are gated to zero weights at
    encode time).
    """
    base: CompressedField
    weights: jnp.ndarray
    tols: jnp.ndarray

    def tree_flatten(self):
        return (self.base, self.weights, self.tols), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def _corrector_features(dec: jnp.ndarray) -> jnp.ndarray:
    """(N, ..., H, W) decoded batch -> (N, P, K) per-pixel feature rows."""
    feats = [jnp.ones_like(dec), dec,
             jnp.roll(dec, 1, axis=-2), jnp.roll(dec, -1, axis=-2),
             jnp.roll(dec, 1, axis=-1), jnp.roll(dec, -1, axis=-1)]
    f = jnp.stack(feats, axis=-1)
    return f.reshape(dec.shape[0], -1, _CORR_K)


def _fit_corrector(dec: jnp.ndarray, residual: jnp.ndarray) -> jnp.ndarray:
    """Per-sample ridge solve of features(dec) @ w ~= residual: (N, K)."""
    a = _corrector_features(dec)                          # (N, P, K)
    r = residual.reshape(residual.shape[0], -1)           # (N, P)
    ata = jnp.einsum("npk,npl->nkl", a, a)
    atr = jnp.einsum("npk,np->nk", a, r)
    lam = 1e-6 * a.shape[1]
    return jax.vmap(jnp.linalg.solve)(
        ata + lam * jnp.eye(_CORR_K, dtype=ata.dtype)[None], atr)


def _apply_corrector(dec: jnp.ndarray, weights: jnp.ndarray,
                     tols: jnp.ndarray) -> jnp.ndarray:
    a = _corrector_features(dec)                          # (N, P, K)
    corr = jnp.einsum("npk,nk->np", a, weights).reshape(dec.shape)
    clip = tols.reshape((-1,) + (1,) * (dec.ndim - 1))
    return dec + jnp.clip(corr, -clip, clip)


@dataclasses.dataclass(frozen=True)
class ResidualCorrectedCodec:
    """Fixed-accuracy codec + NeurLZ-style learned residual correction.

    Encode compresses with the error-bounded codec, fits a K=6 closed-form
    linear corrector on the decoded field's local neighborhood per sample,
    and keeps the weights only where they reduce the realized L1 error --
    so at any tolerance the corrected stream is at least as accurate as the
    plain one, letting an Algorithm-1-style search accept strictly larger
    tolerances (higher ratios) for the same model-error budget.  The
    correction is clipped to +/-tol, bounding worst-case L-inf error by
    2*tol.  Weight storage costs (K+1) floats per sample (counted in
    ``nbytes``).  Registered as ``get_codec("fixed_accuracy+residual", ...)``
    and usable by every consumer of the seam.
    """
    tolerance: Optional[float] = None
    backend: str = "pallas"

    @property
    def name(self) -> str:
        return "fixed_accuracy+residual"

    @property
    def _inner(self) -> FixedAccuracyCodec:
        return FixedAccuracyCodec(self.tolerance, self.backend)

    def encode_batch(self, xs, tolerances=None) -> ResidualCorrectedField:
        if tolerances is None:
            if self.tolerance is None:
                raise ValueError("fixed_accuracy+residual encode needs "
                                 "per-sample tolerances or a codec default")
            tolerances = jnp.full((xs.shape[0],), self.tolerance, jnp.float32)
        tols = jnp.asarray(tolerances, jnp.float32)
        xs = jnp.asarray(xs, jnp.float32)
        cf = self._inner.encode_batch(xs, tols)
        dec = self._inner.decode_batch(cf)
        w = _fit_corrector(dec, xs - dec)
        axes = tuple(range(1, xs.ndim))
        l1_plain = jnp.mean(jnp.abs(dec - xs), axis=axes)
        l1_corr = jnp.mean(jnp.abs(_apply_corrector(dec, w, tols) - xs),
                           axis=axes)
        w = jnp.where((l1_corr < l1_plain)[:, None], w, jnp.zeros_like(w))
        return ResidualCorrectedField(cf, w, tols)

    def decode_batch(self, rcf: ResidualCorrectedField) -> jnp.ndarray:
        dec = self._inner.decode_batch(rcf.base)
        return _apply_corrector(dec, rcf.weights, rcf.tols)

    def nbytes(self, rcf: ResidualCorrectedField) -> jnp.ndarray:
        return (compressed_nbytes_batch(rcf.base, mode="fixed_accuracy")
                + 4 * (rcf.weights.shape[-1] + 1))

    def field_to_arrays(self, rcf: ResidualCorrectedField) -> Dict[str, np.ndarray]:
        out = _cf_to_arrays(rcf.base)
        out["weights"] = np.asarray(rcf.weights)
        out["tols"] = np.asarray(rcf.tols)
        return out

    def field_from_arrays(self, arrays: Mapping[str, Any], shape2d):
        return ResidualCorrectedField(_cf_from_arrays(arrays, shape2d),
                                      jnp.asarray(arrays["weights"]),
                                      jnp.asarray(arrays["tols"]))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register_codec(name: str, factory) -> None:
    """Register a codec factory under ``name`` (``get_codec`` instantiates
    it with the caller's keyword parameters)."""
    if not callable(factory):
        raise TypeError(f"codec factory for {name!r} must be callable")
    _REGISTRY[name] = factory


def codec_names() -> list:
    return sorted(_REGISTRY)


def get_codec(name: str, *, backend: str = "pallas", **params) -> Codec:
    """Instantiate a registered codec: ``get_codec("fixed_accuracy",
    tolerance=1e-3)``.  ``backend`` selects "pallas" (the kernels; their
    compiled jnp oracles off the TPU) or "jnp" (the pure-jnp reference)."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown codec {name!r}; registered: {codec_names()}")
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    return _REGISTRY[name](backend=backend, **params)


register_codec("fixed_accuracy", FixedAccuracyCodec)
register_codec("fixed_rate", FixedRateCodec)
register_codec("fixed_accuracy+residual", ResidualCorrectedCodec)


def codec_spec(codec: Codec) -> dict:
    """JSON-able ``{name, backend, params}`` reconstructing ``codec`` via
    :func:`codec_from_spec` -- the form manifests record."""
    params = dataclasses.asdict(codec)
    backend = params.pop("backend")
    return {"name": codec.name, "backend": backend, "params": params}


def codec_from_spec(spec: Mapping[str, Any]) -> Codec:
    """Inverse of :func:`codec_spec`."""
    return get_codec(spec["name"], backend=spec["backend"], **spec["params"])


def codec_from_plan(codec_plan) -> Codec:
    """Codec for a datagen ``CodecPlan``-shaped object (duck-typed: ``mode``
    plus the mode's parameters)."""
    if codec_plan.mode == "fixed_accuracy":
        return get_codec("fixed_accuracy", tolerance=codec_plan.tolerance)
    if codec_plan.mode == "fixed_rate":
        return get_codec("fixed_rate", bits_per_value=codec_plan.bits_per_value)
    raise ValueError(f"unknown codec mode {codec_plan.mode!r}")


# ---------------------------------------------------------------------------
# tree codec: the seam grown upward to whole pytrees
# ---------------------------------------------------------------------------
# Gradients and checkpoints compress *pytrees* of tensors, not stacks of
# same-shape samples.  encode_tree/decode_tree view every eligible leaf as
# the 2D block layout the codec expects and run each through the batched
# codec (N=1), so every backend, mode and wrapper behind get_codec applies
# to trees unchanged.  TreeCodecMeta is the per-tree sidecar: hashable (it
# can ride through jax.jit static arguments), derived purely from static
# leaf shapes (so encode_tree/decode_tree trace into jitted steps), and
# JSON-round-trippable for manifests.

def leaf_2d_shape(shape) -> Tuple[int, int]:
    """Canonical 2D block view of an arbitrary leaf shape: trailing dim is
    kept as the fast axis; 1D leaves fold into 64 rows when divisible (vector
    leaves pad 4x otherwise); scalars become (1, 1)."""
    shape = tuple(int(s) for s in shape)
    if len(shape) >= 2:
        rows = 1
        for s in shape[:-1]:
            rows *= s
        return (rows, shape[-1])
    if len(shape) == 1 and shape[0] % 64 == 0:
        return (64, shape[0] // 64)
    return (1, shape[0] if shape else 1)


def tree_leaf_keys(tree) -> list:
    """Stable '/'-joined path key per leaf, in tree_flatten order (the same
    naming the checkpoint manifest uses)."""
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            for path, _ in paths]


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Static per-leaf record: path key, original shape/dtype, whether the
    leaf went through the codec (False = carried raw)."""
    key: str
    shape: Tuple[int, ...]
    dtype: str
    compressed: bool

    @property
    def shape2d(self) -> Tuple[int, int]:
        return leaf_2d_shape(self.shape)


@dataclasses.dataclass(frozen=True)
class TreeCodecMeta:
    """Hashable + JSON-serializable sidecar for one encoded tree.

    ``codec`` is the flattened ``codec_spec`` (name, backend, sorted param
    pairs); ``leaves`` one LeafSpec per flattened leaf.  Static throughout --
    safe as a jit static argument and cheap to embed in manifests.
    """
    codec: Tuple
    leaves: Tuple[LeafSpec, ...]

    def make_codec(self) -> Codec:
        name, backend, params = self.codec
        return get_codec(name, backend=backend, **dict(params))

    def to_json(self) -> dict:
        name, backend, params = self.codec
        return {"codec": {"name": name, "backend": backend,
                          "params": dict(params)},
                "leaves": [{"key": l.key, "shape": list(l.shape),
                            "dtype": l.dtype, "compressed": l.compressed}
                           for l in self.leaves]}

    @classmethod
    def from_json(cls, obj: Mapping[str, Any]) -> "TreeCodecMeta":
        c = obj["codec"]
        return cls((c["name"], c["backend"],
                    tuple(sorted(c["params"].items()))),
                   tuple(LeafSpec(l["key"], tuple(int(s) for s in l["shape"]),
                                  l["dtype"], bool(l["compressed"]))
                         for l in obj["leaves"]))


def _codec_key(codec: Codec) -> Tuple:
    spec = codec_spec(codec)
    return (spec["name"], spec["backend"],
            tuple(sorted(spec["params"].items())))


def encode_tree(codec: Codec, tree, *, min_size: int = 0, tolerances=None):
    """Compress every eligible float leaf of ``tree`` through ``codec``.

    tolerances : None (codec default), a scalar applied to every leaf, or a
        ``{leaf_key: tol}`` mapping (keys as in :func:`tree_leaf_keys`; a
        fixed-accuracy leaf with no entry and no codec default is carried
        raw -- the checkpoint path uses this for certified per-leaf
        tolerances).  Ignored by fixed-rate codecs.
    min_size : leaves smaller than this (or non-float) are carried raw.

    Returns ``(encoded, meta)``: ``encoded`` is a list in tree_flatten order
    whose entries are batched (N=1) compressed fields for compressed leaves
    and the original leaves otherwise; ``meta`` is the :class:`TreeCodecMeta`
    needed to invert.  Fully jit-traceable (the Python loop is over static
    leaves).
    """
    flat, _ = jax.tree_util.tree_flatten(tree)
    keys = tree_leaf_keys(tree)
    needs_tol = (getattr(codec, "tolerance", 0) is None
                 and codec.name.startswith("fixed_accuracy"))
    encoded, specs = [], []
    for key, leaf in zip(keys, flat):
        x = jnp.asarray(leaf)
        if isinstance(tolerances, Mapping):
            tol = tolerances.get(key)
        else:
            tol = tolerances
        eligible = (jnp.issubdtype(x.dtype, jnp.floating)
                    and x.size >= max(min_size, 1)
                    and not (needs_tol and tol is None))
        spec = LeafSpec(key, tuple(int(s) for s in x.shape),
                        jnp.dtype(x.dtype).name, bool(eligible))
        specs.append(spec)
        if not eligible:
            encoded.append(leaf)
            continue
        x2 = x.astype(jnp.float32).reshape(spec.shape2d)
        tols = None if tol is None else jnp.asarray([tol], jnp.float32)
        encoded.append(codec.encode_batch(x2[None], tols))
    return encoded, TreeCodecMeta(_codec_key(codec), tuple(specs))


def decode_tree(encoded, meta: TreeCodecMeta, codec: Optional[Codec] = None,
                treedef=None):
    """Invert :func:`encode_tree`: decode every compressed entry back to its
    original shape and dtype (raw entries pass through).  Returns a list in
    leaf order, or the unflattened pytree when ``treedef`` is given.
    ``codec`` defaults to the one recorded in ``meta`` (pass one explicitly
    to pin the decode backend)."""
    if codec is None:
        codec = meta.make_codec()
    out = []
    for enc, spec in zip(encoded, meta.leaves):
        if not spec.compressed:
            out.append(enc)
            continue
        x = codec.decode_batch(enc)[0].reshape(spec.shape)
        out.append(x.astype(spec.dtype))
    if treedef is not None:
        return jax.tree_util.tree_unflatten(treedef, out)
    return out


def tree_nbytes(codec: Codec, encoded, meta: TreeCodecMeta) -> Tuple[int, int]:
    """(raw_bytes, stored_bytes) for one encoded tree: logical codec bytes
    for compressed leaves, array nbytes for raw ones.  Host-side accounting
    (not traceable) -- manifests and collective-bytes analysis use this."""
    raw = stored = 0
    for enc, spec in zip(encoded, meta.leaves):
        size = 1
        for s in spec.shape:
            size *= s
        leaf_bytes = size * np.dtype(spec.dtype).itemsize
        raw += leaf_bytes
        if spec.compressed:
            stored += int(np.sum(np.asarray(codec.nbytes(enc))))
        else:
            stored += leaf_bytes
    return raw, stored
