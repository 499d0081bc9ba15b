"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (shape/dtype
sweeps in tests/test_kernels.py).  They are built on the shared
``repro.compression.transform`` arithmetic but use the plain vectorized code
path, whereas the kernels re-implement the arithmetic with TPU idioms
(2D iota, tile loops) -- so the allclose comparison exercises genuinely
different code.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.compression import transform as T


# ---------------------------------------------------------------------------
# ZFP fixed-rate block codec oracles
# ---------------------------------------------------------------------------

def zfp_encode_blocks_ref(blocks_f: jnp.ndarray, bits_per_value: int):
    """(nb, 16) f32 -> ((nb, W) int32 payload, (nb,) int32 emax)."""
    emax = T.block_emax(blocks_f)
    qi = T.quantize_blocks(blocks_f, emax)
    coef = T.fwd_transform_2d(qi)
    u = T.int2nb(coef)
    nplanes = jnp.full((blocks_f.shape[0],), bits_per_value, jnp.int32)
    u = T.truncate_planes(u, nplanes)
    payload = T.pack_planes(u, (bits_per_value + 1) // 2)
    return payload, emax


def zfp_decode_blocks_ref(payload: jnp.ndarray, emax: jnp.ndarray,
                          bits_per_value: int) -> jnp.ndarray:
    """((nb, W) int32, (nb,) int32) -> (nb, 16) f32."""
    del bits_per_value  # planes beyond the stored words are simply absent
    u = T.unpack_planes(payload)
    coef = T.nb2int(u)
    qi = T.inv_transform_2d(coef)
    return T.dequantize_blocks(qi, emax)


def zfp_encode_blocks_fa_ref(blocks_f: jnp.ndarray, tols: jnp.ndarray):
    """Fixed-accuracy encode oracle with per-block L-inf tolerances.

    (nb, 16) f32 blocks, (nb,) f32 tols -> ((nb, MAX_WORDS) int32 payload,
    (nb,) int32 emax, (nb,) int32 nplanes).  Mirrors
    ``compression/zfp.py::encode_fixed_accuracy`` block-for-block: plane
    guess from ``emax - floor(log2(tol)) + GUARD_BITS``, zero-block
    short-circuit, then the bound-verification correction run a static
    ``MAX_FIX_ITERS`` times (the while_loop's body is a no-op once a block's
    realized error is within tolerance, so the unroll reaches the identical
    fixpoint).
    """
    emax = T.block_emax(blocks_f)
    qi = T.quantize_blocks(blocks_f, emax)
    u_full = T.int2nb(T.fwd_transform_2d(qi))
    tols = jnp.asarray(tols, jnp.float32)
    npl = jnp.clip(emax - T.floor_log2(tols) + T.GUARD_BITS, 0,
                   T.TOTAL_PLANES).astype(jnp.int32)
    npl = jnp.where(jnp.all(u_full == 0, axis=-1), 0, npl)

    def block_err(npl):
        u = T.truncate_planes(u_full, npl)
        dec = T.dequantize_blocks(T.inv_transform_2d(T.nb2int(u)), emax)
        return jnp.max(jnp.abs(dec - blocks_f), axis=-1)

    for _ in range(T.MAX_FIX_ITERS):
        bad = block_err(npl) > tols
        npl = jnp.where(bad, jnp.minimum(npl + 2, T.TOTAL_PLANES), npl)
    payload = T.pack_planes(T.truncate_planes(u_full, npl), T.MAX_WORDS)
    return payload, emax, npl


def zfp_decode_blocks_fa_ref(payload: jnp.ndarray, emax: jnp.ndarray,
                             nplanes: jnp.ndarray) -> jnp.ndarray:
    """Fixed-accuracy oracle: per-block plane counts mask the unpacked stream.

    payload: (nb, W) int32, emax/nplanes: (nb,) int32.  Planes at or below
    ``TOTAL_PLANES - nplanes[b]`` are zeroed before the inverse transform, so
    a payload padded with words beyond a block's kept planes decodes exactly
    as the truncated stream ``encode_fixed_accuracy`` produced.
    """
    u = T.unpack_planes(payload)
    u = T.truncate_planes(u, nplanes.astype(jnp.int32))
    coef = T.nb2int(u)
    qi = T.inv_transform_2d(coef)
    return T.dequantize_blocks(qi, emax)


# ---------------------------------------------------------------------------
# Flash-attention oracle (GQA, causal or full)
# ---------------------------------------------------------------------------

def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, sm_scale: float | None = None,
                        window: int | None = None) -> jnp.ndarray:
    """Naive reference attention.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) with Hq % Hkv == 0 (GQA).
    ``window``: optional sliding-window size (tokens attend to the previous
    ``window`` positions, inclusive of self).
    Returns (B, Hq, Sq, D) in q.dtype; accumulation in f32.
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32).reshape(b, hkv, group, sq, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf) * sm_scale
    sk = k.shape[2]
    qpos = jnp.arange(sq)[:, None] + (sk - sq)   # align ends (decode: sq << sk)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jnp.exp(logits - jnp.max(logits, -1, keepdims=True))
    probs = probs / jnp.sum(probs, -1, keepdims=True)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vf)
    return out.reshape(b, hq, sq, d).astype(q.dtype)
