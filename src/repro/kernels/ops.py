"""Jit'd public entries of the Pallas kernels: one per kernel.

Dispatch policy: every entry chooses its path by the platform it is lowered
for (``jax.lax.platform_dependent``), not by the process's default backend,
so a program compiled for a TPU carries the compiled kernel even when the
compile runs in a CPU process.  On a TPU an entry runs the compiled Pallas
kernel; anywhere else it runs the kernel's jitted jnp oracle from ref.py
(interpret mode runs the kernel body in Python, far too slow for the
training and datagen hot paths).  The oracle is bit-identical to the
kernel: tests run the kernel bodies in interpret mode
(``zfp_codec.<kernel>(..., interpret=True)``) against it.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ref
from repro.kernels import zfp_codec
from repro.kernels import flash_attention as _fa


def _kernel_on_tpu(kernel, other, *args):
    """``kernel(*args)`` when lowered for a TPU, ``other(*args)`` otherwise."""
    return jax.lax.platform_dependent(*args, tpu=kernel, default=other)


def zfp_decode_blocks(payload, emax, bits_per_value):
    """Fixed-rate decode of (nb, W) payload words -> (nb, 16) f32 blocks."""
    return _kernel_on_tpu(
        partial(zfp_codec.zfp_decode_blocks, bits_per_value=bits_per_value),
        _ref_decode_jit, payload, emax)


@jax.jit
def _ref_decode_jit(payload, emax):
    return ref.zfp_decode_blocks_ref(payload, emax, payload.shape[1] * 2)


def zfp_decode_blocks_fa(payload, emax, nplanes):
    """Fixed-accuracy decode (per-block plane counts) -> (nb, 16) f32.

    This is what the fused gather -> decode train step traces through.
    """
    return _kernel_on_tpu(zfp_codec.zfp_decode_blocks_fa, _ref_decode_fa_jit,
                          payload, emax, nplanes)


@jax.jit
def _ref_decode_fa_jit(payload, emax, nplanes):
    return ref.zfp_decode_blocks_fa_ref(payload, emax, nplanes)


def zfp_encode_blocks(blocks, bits_per_value):
    """Fixed-rate encode of (nb, 16) f32 blocks -> (payload, emax)."""
    return _kernel_on_tpu(
        partial(zfp_codec.zfp_encode_blocks, bits_per_value=bits_per_value),
        partial(_ref_encode_jit, bits_per_value=bits_per_value), blocks)


@partial(jax.jit, static_argnames=("bits_per_value",))
def _ref_encode_jit(blocks, bits_per_value):
    return ref.zfp_encode_blocks_ref(blocks, bits_per_value)


def zfp_encode_blocks_fa(coefs, tols):
    """Fixed-accuracy encode of coefficient-major (16, nb) blocks
    (``transform.blockify_coef_major``) at (nb,) L-inf tolerances ->
    (payload, emax, nplanes)."""
    return _kernel_on_tpu(zfp_codec.zfp_encode_blocks_fa, _ref_encode_fa_jit,
                          coefs, tols)


@jax.jit
def _ref_encode_fa_jit(coefs, tols):
    return ref.zfp_encode_blocks_fa_ref(coefs.T, tols)


def zfp_search_stats_fa(slabs, tols, valid):
    """Algorithm 1's stats pass on coefficient-major (16, R, 128) slabs at
    (R, 128) per-block tolerances and valid-pixel bits -> ((R, 128) int32
    plane counts, (R, 128) f32 per-block L1 sums)."""
    return _kernel_on_tpu(zfp_codec.zfp_search_stats_fa, _ref_search_stats_jit,
                          slabs, tols, valid)


@jax.jit
def _ref_search_stats_jit(slabs, tols, valid):
    npl, l1 = ref.zfp_search_stats_fa_ref(
        slabs.reshape(16, -1).T, tols.reshape(-1), valid.reshape(-1))
    return npl.reshape(tols.shape), l1.reshape(tols.shape)


# rows of 128 blocks that the coefficient-major kernels take for nb blocks
fa_slab_rows = zfp_codec.fa_slab_rows


def flash_attention(q, k, v, *, causal=True, sm_scale=None, window=None):
    kernel = partial(_fa.flash_attention, causal=causal, sm_scale=sm_scale,
                     window=window)
    return _kernel_on_tpu(kernel, partial(kernel, interpret=True), q, k, v)
