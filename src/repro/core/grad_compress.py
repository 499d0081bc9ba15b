"""Beyond-paper: error-bounded gradient compression for data parallelism.

The paper compresses *training data* because the model cannot learn detail
below its own error floor.  The same argument applies one level down: SGD
cannot exploit gradient detail below the gradient-noise floor (the
mini-batch sampling noise -- the "training variability" of the gradient
itself).  We therefore compress DP gradients through the unified Codec seam
before the slow cross-pod collective, with error feedback so the truncation
residual re-enters the next step (bias-free in expectation).  Any registered
codec applies: fixed-rate for a guaranteed wire ratio, fixed-accuracy for an
explicit error bound chosen by the same Algorithm-1 machinery the data path
uses.

Collective mechanics (shard_map): sum-of-codes != code-of-sum, so instead of
all-reduce we reduce-scatter raw shards *within* a pod (fast ICI) and
compress only the *cross-pod* all-gather of the reduced shards: payload
bytes cross the slow link at bits/32 of the raw volume.  HLO collective
bytes shrink accordingly (visible in the roofline table; see §Perf).
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.compression import (
    Codec,
    decode_tree,
    encode_tree,
    get_codec,
    tree_nbytes,
)

CodecLike = Union[Codec, int]


def as_codec(codec: CodecLike) -> Codec:
    """Resolve the historical ``bits`` shorthand: an int means the fixed-rate
    codec at that many bit planes; anything else must already be a Codec."""
    if isinstance(codec, int):
        return get_codec("fixed_rate", bits_per_value=codec)
    return codec


def compress_decompress(g: jnp.ndarray, codec: CodecLike) -> jnp.ndarray:
    """Round-trip one gradient tensor through the codec (error-feedback math).

    ``codec`` is a Codec or an int (fixed-rate bits, the pre-seam calling
    convention).  Traceable; shape and dtype are preserved.
    """
    codec = as_codec(codec)
    enc, meta = encode_tree(codec, g)
    return decode_tree(enc, meta, codec=codec)[0]


def compressed_psum_tree(grads, axis_name: str, codec: CodecLike,
                         residuals=None, tolerances=None):
    """Error-feedback compressed mean over ``axis_name`` inside shard_map.

    grads: local gradient pytree.  codec: any registered Codec (or int bits
    for fixed-rate).  residuals: previous step's pytree (or None to start
    from zero).  tolerances: optional per-leaf error bounds forwarded to
    :func:`encode_tree` -- scalar or ``{leaf_key: tol}`` -- enabling
    fixed-accuracy gradient compression.  Returns ``(mean_grads,
    new_residuals)`` as two trees with the structure of ``grads``.

    Each device adds its carried residual, compresses, and the *compressed*
    tensors cross the collective; the local truncation error becomes the new
    residual.  Leaves the codec skips (non-float, or no tolerance resolvable
    for a default-free fixed-accuracy codec) pass through the pmean raw with
    a zero residual.
    """
    codec = as_codec(codec)
    if residuals is None:
        residuals = jax.tree.map(jnp.zeros_like, grads)

    g_fb = jax.tree.map(lambda g, r: g + r, grads, residuals)
    treedef = jax.tree_util.tree_structure(g_fb)
    enc, meta = encode_tree(codec, g_fb, tolerances=tolerances)
    g_hat = decode_tree(enc, meta, codec=codec, treedef=treedef)
    new_res = jax.tree.map(lambda f, h: f - h, g_fb, g_hat)
    mean = jax.tree.map(lambda h: jax.lax.pmean(h, axis_name), g_hat)
    return mean, new_res


def tree_collective_bytes(grads, codec: Optional[CodecLike]) -> Tuple[int, int]:
    """(raw_bytes, compressed_bytes) one gradient exchange would move across
    the slow link.  Host-side accounting for rooflines and dryrun reports;
    ``codec=None`` means the uncompressed baseline (raw == compressed)."""
    if codec is None:
        raw = sum(jnp.asarray(l).size * jnp.asarray(l).dtype.itemsize
                  for l in jax.tree_util.tree_leaves(grads))
        return raw, raw
    codec = as_codec(codec)
    enc, meta = encode_tree(codec, grads)
    return tree_nbytes(codec, enc, meta)
