"""Set-up shared by the loops: normalisation statistics and the compressed
training store, built through the code under test.

The per-field statistics are the benchmark's own (the plain reference
normalises with the same numbers); the program's ``FieldNormalizer`` applies
them.  The store is built as the paper's pipeline builds it, one simulation
member at a time so that Algorithm 1's state for one member is all that is
live: normalise, channels first, Algorithm 1 at the configuration's model
error (``find_tolerance_batch``), the fixed-accuracy codec's Pallas encode,
then ``DeviceResidentCompressedStore.from_compressed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# The encode runs on a third of a member's 51 snapshots at a time, as the
# program's datagen encodes shard-sized chunks: its fixed-accuracy encode
# takes about 37 times its input in temporary device memory, so a whole
# 512x512 member (12 GB) does not fit beside the certification pool.
ENCODE_ROWS = 17


@jax.jit
def _moments(x, mean):
    axes = tuple(range(x.ndim - 1))
    return jnp.sum(x, axis=axes), jnp.sum(jnp.square(x - mean), axis=axes)


def norm_stats(members):
    """Per-field mean and standard deviation (+1e-6) over device arrays of
    shape (T, H, W, F), summed per member and combined in float64."""
    count = sum(int(np.prod(m.shape[:-1])) for m in members)
    fields = members[0].shape[-1]
    zero = jnp.zeros((fields,), jnp.float32)
    mean = sum(np.asarray(_moments(m, zero)[0], np.float64)
               for m in members) / count
    mean32 = jnp.asarray(mean, jnp.float32)
    var = sum(np.asarray(_moments(m, mean32)[1], np.float64)
              for m in members) / count
    return mean.astype(np.float32), (np.sqrt(var) + 1e-6).astype(np.float32)


def normalizer(mean, std):
    from repro.models.surrogate import FieldNormalizer
    return FieldNormalizer(mean=jnp.asarray(mean), std=jnp.asarray(std))


def certify_member(norm, member, model_l1: float, codec):
    """Normalise one member, search its tolerances, encode it in chunks of
    ``ENCODE_ROWS`` snapshots.

    Returns ``(tolerances (T,) np.float32, CompressedField)``.
    """
    from repro.compression import CompressedField
    from repro.core import find_tolerance_batch
    xs = jnp.transpose(norm.normalize(member), (0, 3, 1, 2))
    es = np.full((xs.shape[0],), model_l1, np.float32)
    res = find_tolerance_batch(xs, es)
    tols = np.asarray(res.tolerance, np.float32)
    parts = [codec.encode_batch(xs[s:s + ENCODE_ROWS],
                                jnp.asarray(tols[s:s + ENCODE_ROWS]))
             for s in range(0, xs.shape[0], ENCODE_ROWS)]
    if len(parts) == 1:
        return tols, parts[0]
    return tols, CompressedField(
        *(jnp.concatenate([getattr(p, k) for p in parts])
          for k in ("payload", "emax", "nplanes")),
        parts[0].shape, parts[0].padded_shape)


def compressed_store(members, norm, model_l1: float):
    """A ``DeviceResidentCompressedStore`` of every snapshot of ``members``.

    Each member's payload is cut to the words its deepest block keeps as
    soon as it is encoded, so the full-width payloads are never all live.
    """
    from repro.compression import CompressedField, get_codec
    from repro.data import DeviceResidentCompressedStore
    codec = get_codec("fixed_accuracy")
    tols, parts = [], []
    for m in members:
        t, cf = certify_member(norm, m, model_l1, codec)
        words = max(int(np.ceil(int(jnp.max(cf.nplanes)) / 2)), 1)
        tols.append(t)
        parts.append(CompressedField(cf.payload[..., :words], cf.emax,
                                     cf.nplanes, cf.shape, cf.padded_shape))
        del cf
    width = max(p.payload.shape[-1] for p in parts)
    payload = jnp.concatenate([
        jnp.pad(p.payload, ((0, 0), (0, 0), (0, width - p.payload.shape[-1])))
        for p in parts])
    cf = CompressedField(payload, jnp.concatenate([p.emax for p in parts]),
                         jnp.concatenate([p.nplanes for p in parts]),
                         parts[0].shape, parts[0].padded_shape)
    del parts
    return DeviceResidentCompressedStore.from_compressed(
        cf, np.concatenate(tols))
