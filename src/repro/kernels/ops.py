"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: every wrapper chooses its path by the platform it is
lowered for (``jax.lax.platform_dependent``), not by the process's default
backend, so a program compiled for a TPU carries the compiled kernel even
when the compile runs in a CPU process.

  * plain wrappers (``zfp_decode_blocks`` ...): compiled Pallas on TPU,
    the same kernel in interpret mode elsewhere -- the correctness path the
    tests validate against ref.py;
  * ``*_fast`` wrappers: compiled Pallas on TPU, the jitted jnp oracle
    elsewhere (interpret mode runs the kernel body in Python, far too slow
    for the training and datagen hot paths).  The oracle is bit-identical
    to the kernel (tests assert so).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.compression import transform as T
from repro.compression.zfp import CompressedField
from repro.kernels import ref
from repro.kernels import zfp_codec
from repro.kernels import flash_attention as _fa


def _kernel_on_tpu(kernel, other, *args):
    """``kernel(*args)`` when lowered for a TPU, ``other(*args)`` otherwise."""
    return jax.lax.platform_dependent(*args, tpu=kernel, default=other)


def zfp_decode_blocks(payload, emax, bits_per_value):
    kernel = partial(zfp_codec.zfp_decode_blocks, bits_per_value=bits_per_value)
    return _kernel_on_tpu(kernel, partial(kernel, interpret=True),
                          payload, emax)


def zfp_decode_blocks_fast(payload, emax, bits_per_value):
    """Fixed-rate decode for throughput: kernel on TPU, oracle elsewhere."""
    return _kernel_on_tpu(
        partial(zfp_codec.zfp_decode_blocks, bits_per_value=bits_per_value),
        _ref_decode_jit, payload, emax)


@jax.jit
def _ref_decode_jit(payload, emax):
    return ref.zfp_decode_blocks_ref(payload, emax, payload.shape[1] * 2)


def zfp_decode_blocks_fa(payload, emax, nplanes):
    """Fixed-accuracy decode (per-block variable plane counts), kernel path."""
    return _kernel_on_tpu(
        zfp_codec.zfp_decode_blocks_fa,
        partial(zfp_codec.zfp_decode_blocks_fa, interpret=True),
        payload, emax, nplanes)


def zfp_decode_blocks_fa_fast(payload, emax, nplanes):
    """Fixed-accuracy decode for throughput: kernel on TPU, oracle elsewhere.

    This is what the fused gather -> decode train step traces through.
    """
    return _kernel_on_tpu(zfp_codec.zfp_decode_blocks_fa, _ref_decode_fa_jit,
                          payload, emax, nplanes)


@jax.jit
def _ref_decode_fa_jit(payload, emax, nplanes):
    return ref.zfp_decode_blocks_fa_ref(payload, emax, nplanes)


def zfp_encode_blocks(blocks, bits_per_value):
    kernel = partial(zfp_codec.zfp_encode_blocks, bits_per_value=bits_per_value)
    return _kernel_on_tpu(kernel, partial(kernel, interpret=True), blocks)


def zfp_encode_blocks_fast(blocks, bits_per_value):
    """Fixed-rate encode for throughput: kernel on TPU, oracle elsewhere."""
    return _kernel_on_tpu(
        partial(zfp_codec.zfp_encode_blocks, bits_per_value=bits_per_value),
        partial(_ref_encode_jit, bits_per_value=bits_per_value), blocks)


@partial(jax.jit, static_argnames=("bits_per_value",))
def _ref_encode_jit(blocks, bits_per_value):
    return ref.zfp_encode_blocks_ref(blocks, bits_per_value)


def zfp_encode_blocks_fa(blocks, tols):
    """Fixed-accuracy encode of (nb, 16) blocks (per-block L-inf
    tolerances), kernel path."""
    return _kernel_on_tpu(
        zfp_codec.zfp_encode_blocks_fa,
        partial(zfp_codec.zfp_encode_blocks_fa, interpret=True),
        blocks.T, tols)


def zfp_encode_blocks_fa_fast(blocks, tols):
    """Fixed-accuracy encode of (nb, 16) blocks for throughput: kernel on
    TPU, oracle elsewhere (``zfp_encode_coefs_fa_fast`` of ``blocks.T``)."""
    return zfp_encode_coefs_fa_fast(blocks.T, tols)


def zfp_encode_coefs_fa_fast(coefs, tols):
    """Fixed-accuracy encode of coefficient-major (16, nb) blocks
    (``transform.blockify_coef_major``): kernel on TPU, oracle elsewhere.

    Bit-identical to the kernel path (tests assert payload/emax/nplanes
    equality), so the codec seam's ``backend="pallas"`` encode and the
    datagen encode-on-device path use it unconditionally.
    """
    return _kernel_on_tpu(zfp_codec.zfp_encode_blocks_fa, _ref_encode_fa_jit,
                          coefs, tols)


@jax.jit
def _ref_encode_fa_jit(coefs, tols):
    return ref.zfp_encode_blocks_fa_ref(coefs.T, tols)


def decode_field(cf: CompressedField) -> jnp.ndarray:
    """Kernel-path decode of a fixed-rate CompressedField."""
    bits = int(cf.payload.shape[1]) * 2
    blocks = zfp_decode_blocks(cf.payload, cf.emax, bits)
    xp = T.deblockify(blocks, cf.padded_shape)
    slices = tuple(slice(0, s) for s in cf.shape)
    return xp[slices]


def encode_field(x: jnp.ndarray, bits_per_value: int) -> CompressedField:
    """Kernel-path fixed-rate encode of an array (trailing 2 dims blocked)."""
    shape = x.shape
    xp = T.pad_to_blocks(x.astype(jnp.float32))
    blocks = T.blockify(xp)
    payload, emax = zfp_encode_blocks(blocks, bits_per_value)
    nplanes = jnp.full((blocks.shape[0],), bits_per_value, jnp.int32)
    return CompressedField(payload, emax, nplanes, shape, xp.shape)


def flash_attention(q, k, v, *, causal=True, sm_scale=None, window=None):
    kernel = partial(_fa.flash_attention, causal=causal, sm_scale=sm_scale,
                     window=window)
    return _kernel_on_tpu(kernel, partial(kernel, interpret=True), q, k, v)
