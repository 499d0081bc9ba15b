"""Unified LM-family model zoo: dense / GQA / MoE / SSM (Mamba2 SSD) /
hybrid (Hymba) / encoder-decoder (Seamless) / VLM+audio frontends.

Design choices that matter at 512 devices:
  * scan-over-layers with stacked (L, ...) params -> O(1) HLO in depth,
    fast .lower().compile() even for 48L archs on a 1-core container;
  * memory-efficient chunked attention (scan over q chunks) -> no S x S
    materialization at 32k;
  * chunked cross-entropy (scan over sequence chunks) -> no (tokens, vocab)
    logits tensor at 152k vocab;
  * grouped dense MoE dispatch (einsum per token group, E sharded = EP);
  * per-layer global/local flags flow through scan as data, keeping hybrid
    stacks (hymba) homogeneous for scan.

Everything is pure functions over pytrees; `init_lm` is eval_shape-able so
the dry-run can derive shardings without allocating 480B parameters.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

Pytree = Any


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


# ===========================================================================
# parameter init (per-layer, vmapped into stacked (L, ...) leaves)
# ===========================================================================

def _dense_init(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _layer_param_shapes(cfg: ArchConfig, cross_attn: bool = False) -> Dict[str, Tuple]:
    d, hd = cfg.d_model, cfg.hdim
    h, hkv, f = cfg.num_heads, cfg.num_kv_heads, cfg.d_ff
    shapes: Dict[str, Tuple] = {"ln1": (d,), "ln2": (d,)}
    attn = cfg.family != "ssm"
    if attn:
        shapes.update(wq=(d, h, hd), wk=(d, hkv, hd), wv=(d, hkv, hd),
                      wo=(h, hd, d))
        if cfg.qkv_bias:
            shapes.update(bq=(h, hd), bk=(hkv, hd), bv=(hkv, hd))
    if cross_attn:
        shapes.update(ln_x=(d,), xwq=(d, h, hd), xwk=(d, hkv, hd),
                      xwv=(d, hkv, hd), xwo=(h, hd, d))
    if cfg.num_experts:
        e, ef = cfg.num_experts, cfg.d_ff
        shapes.update(router=(d, e), e_gate=(e, d, ef), e_up=(e, d, ef),
                      e_down=(e, ef, d))
        if cfg.moe_dense_ff:
            fd = cfg.moe_dense_ff
            shapes.update(w_gate=(d, fd), w_up=(d, fd), w_down=(fd, d))
    elif cfg.family != "ssm" or cfg.hybrid:
        shapes.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    if cfg.family == "ssm" or cfg.hybrid:
        nh, p, k = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_conv
        di, gn = nh * p, cfg.ssm_groups * cfg.ssm_state
        shapes.update(ssm_in=(d, 2 * di + 2 * gn + nh),
                      ssm_conv_w=(k, di + 2 * gn),
                      ssm_A=(nh,), ssm_D=(nh,), ssm_dt_bias=(nh,),
                      ssm_norm=(di,), ssm_out=(di, d))
        if cfg.ssm_conv_bias:
            shapes["ssm_conv_b"] = (di + 2 * gn,)
        if cfg.family == "ssm":
            shapes["w_gate"] = (d, max(f, 1)) if f else None
            shapes.pop("w_gate")                # pure mamba2 has no MLP block
    return {k: v for k, v in shapes.items() if v is not None}


def _init_one_layer(key, cfg: ArchConfig, cross_attn: bool = False):
    shapes = _layer_param_shapes(cfg, cross_attn)
    dt = _dtype(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.startswith("ln") or name in ("ssm_norm",):
            params[name] = jnp.ones(shape, dt)
        elif name == "ssm_A":
            params[name] = jnp.log(jnp.linspace(1.0, 16.0, shape[0])).astype(jnp.float32)
        elif name == "ssm_dt_bias":
            params[name] = jnp.full(shape, -4.0, jnp.float32)
        elif name == "ssm_D":
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.startswith("b"):
            params[name] = jnp.zeros(shape, dt)
        else:
            # contraction dims: (h, hd) for output projections, else dim 0
            fan_in = shape[0] * shape[1] if name in ("wo", "xwo") else shape[0]
            params[name] = _dense_init(k, shape, fan_in, dt)
    return params


def init_lm(key, cfg: ArchConfig) -> Pytree:
    d, v = cfg.d_model, cfg.vocab_size
    dt = _dtype(cfg)
    k_embed, k_head, k_layers, k_enc, k_front = jax.random.split(key, 5)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(k_embed, (v, d), jnp.float32) * 0.02).astype(dt),
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(k_head, (d, v), d, dt)
    lkeys = jax.random.split(k_layers, cfg.num_layers)
    cross = cfg.encoder_layers > 0
    params["layers"] = jax.vmap(lambda k: _init_one_layer(k, cfg, cross))(lkeys)
    if cfg.encoder_layers:
        ekeys = jax.random.split(k_enc, cfg.encoder_layers)
        params["enc_layers"] = jax.vmap(lambda k: _init_one_layer(k, cfg, False))(ekeys)
        params["enc_norm"] = jnp.ones((d,), dt)
    if cfg.frontend != "none":
        params["frontend_proj"] = _dense_init(k_front, (cfg.frontend_dim, d),
                                              cfg.frontend_dim, dt)
    return params


# ===========================================================================
# primitives
# ===========================================================================

def rmsnorm(g, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)) * g


def _scale(x, m: float):
    """``x * m`` in x's dtype; a multiplier of 1 adds no op."""
    return x if m == 1.0 else x * m


def rope(x, positions, theta):
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs          # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


_CONSTRAINT_MESH = None
_CONSTRAINT_EXCLUDE = ()


def set_constraint_exclude(axes):
    """Axes to strip from constraints (e.g. 'pod' inside a shard_map that
    handles the pod axis manually)."""
    global _CONSTRAINT_EXCLUDE
    _CONSTRAINT_EXCLUDE = tuple(axes)


def set_constraint_mesh(mesh):
    """Register the mesh activation constraints should target (None = off).

    Explicit registration (rather than the ambient-context API) keeps the
    model code working identically on single-device smoke tests and across
    jax context-API versions.  dryrun/train/serve call this before lowering.
    """
    global _CONSTRAINT_MESH
    _CONSTRAINT_MESH = mesh


def _constrain(x, *spec):
    """Best-effort with_sharding_constraint: silently skips axes absent from
    the registered mesh, manual (shard_map-owned) axes, and axes not
    dividing the dim."""
    mesh = _CONSTRAINT_MESH
    if mesh is None:
        return x
    manual = set(_CONSTRAINT_EXCLUDE)
    target = mesh
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is not None and am.axis_names:
            target = am            # inside shard_map: typed context mesh
            manual |= {n for n, t in zip(am.axis_names, am.axis_types)
                       if "Manual" in str(t)}
    except Exception:
        pass
    sizes = dict(target.shape)
    cleaned = []
    for i, s in enumerate(spec):
        axes = s if isinstance(s, tuple) else (s,) if s else ()
        axes = tuple(a for a in axes if a in sizes and a not in manual)
        total = math.prod(sizes[a] for a in axes) if axes else 1
        if axes and i < x.ndim and x.shape[i] % total == 0:
            cleaned.append(axes if len(axes) > 1 else axes[0])
        else:
            cleaned.append(None)
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(target, P(*cleaned)))


DP = ("pod", "data")     # batch axes (filtered against the ambient mesh)


# TP partial products pass through ``jax.lax.optimization_barrier`` before
# any consumer upcasts them: XLA's SPMD partitioner may otherwise hoist the
# f32 upcast above the GSPMD-inserted all-reduce, doubling wire bytes.  The
# barrier pins the collective to bf16.

# Per-layer gathered-weight specs: weights arrive FSDP-sharded over "data";
# constraining them to their TP-only spec forces GSPMD into the ZeRO-3
# pattern (forward all-gather of the weight shard, backward reduce-scatter
# of the weight grad) instead of the catastrophic alternative it otherwise
# picks on some backends: all-gathering *activations* and all-reducing a
# full-batch partial product over the data axis.
_GATHERED_W = {
    "wq": (None, "model", None), "wk": (None, "model", None),
    "wv": (None, "model", None), "wo": ("model", None, None),
    "xwq": (None, "model", None), "xwk": (None, "model", None),
    "xwv": (None, "model", None), "xwo": ("model", None, None),
    "w_gate": (None, "model"), "w_up": (None, "model"),
    "w_down": ("model", None),
    "e_gate": ("model", None, None), "e_up": ("model", None, None),
    "e_down": ("model", None, None),
    "router": (None, None),
    "ssm_in": (None, "model"), "ssm_out": ("model", None),
}


def _gather_weights(lp):
    return {k: (_constrain(v, *_GATHERED_W[k]) if k in _GATHERED_W else v)
            for k, v in lp.items()}


def attention(q, k, v, qpos, kpos, *, causal=True, window=None, chunk=1024,
              window_dyn=None, seq_sharded=False):
    """Memory-efficient attention: scan over q chunks; no S x S tensor.

    q: (B, Sq, H, Dh); k/v: (B, Sk, Hkv, Dh); positions (B, Sq)/(B, Sk).
    GQA: query head h reads KV head h // (H / Hkv); the query heads are
    grouped by their KV head, so the KV heads are never repeated.
    ``seq_sharded``: decode path -- the KV cache is sequence-sharded over
    "model"; scores are constrained over their Sk dim instead of heads
    (flash-decoding style sharded softmax; GSPMD inserts the reductions).
    """
    b, sq, h, dh = q.shape
    _, sk, hkv, _ = k.shape
    rep = h // hkv
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    scale = 1.0 / math.sqrt(dh)
    score_spec = (DP, None, None, "model") if seq_sharded \
        else (DP, "model", None, None)

    def block(q_blk, qpos_blk):
        # q_blk: (B, c, H, Dh) -> scores (B, H, c, Sk)
        c = q_blk.shape[1]
        qg = q_blk.astype(jnp.float32).reshape(b, c, hkv, rep, dh)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).reshape(
            b, h, c, sk) * scale
        m = kpos[:, None, None, :] <= qpos_blk[:, None, :, None] \
            if causal else jnp.ones_like(s, bool)
        w = window_dyn if window_dyn is not None else window
        if w is not None:
            m &= kpos[:, None, None, :] > qpos_blk[:, None, :, None] - w
        s = _constrain(jnp.where(m, s, -1e30), *score_spec)
        p = jax.nn.softmax(s, axis=-1).reshape(b, hkv, rep, c, sk)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(b, c, h, dh)
        return _constrain(o, DP, None, "model", None)

    if sq <= chunk:
        out = block(q, qpos)
    else:
        pad = (-sq) % chunk
        if pad:
            q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
            qpos = jnp.pad(qpos, ((0, 0), (0, pad)), constant_values=-1)
        sqp = q.shape[1]
        nc = sqp // chunk
        qc = q.reshape(b, nc, chunk, h, dh)
        pc = qpos.reshape(b, nc, chunk)

        def step(_, xs):
            qb, pb = xs
            return None, block(qb, pb)

        _, out = jax.lax.scan(step, None,
                              (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(pc, 1, 0)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, sqp, h, dh)[:, :sq]
    return out.astype(q.dtype)


def swiglu(x, w_gate, w_up, w_down, multipliers=(1.0, 1.0)):
    """``m_down * W_down(silu(m_gate * W_gate x) * W_up x)``."""
    g = _constrain(jnp.einsum("bsd,df->bsf", x, w_gate), DP, None, "model")
    g = _scale(g, multipliers[0])
    u = _constrain(jnp.einsum("bsd,df->bsf", x, w_up), DP, None, "model")
    return _scale(jax.lax.optimization_barrier(
        jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, w_down)), multipliers[1])


# ===========================================================================
# MoE (grouped dense dispatch, EP over the expert axis)
# ===========================================================================

def moe_block(lp, x, cfg: ArchConfig):
    """x: (B, S, D) -> (B, S, D), plus load-balance aux loss."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    n = b * s
    g_sz = min(cfg.moe_group, n)
    ng = n // g_sz
    cap = max(int(math.ceil(g_sz * k / e * cfg.capacity_factor)), 4)
    xt = _constrain(x.reshape(ng, g_sz, d), DP, None, None)

    logits = jnp.einsum("gnd,de->gne", xt, lp["router"]).astype(jnp.float32)
    # decode/prefill consistency: top-k expert selection must not flip on
    # sub-bf16 numerical noise between the chunked-prefill and step-decode
    # attention paths (a near-tie flip is a discontinuity the cache-match
    # tests would see as divergence).  Snapping scores to the bf16 grid
    # makes selection invariant to such noise; routing weights were already
    # bf16 downstream, so no precision is lost.
    logits = logits.astype(jnp.bfloat16).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_ids = jax.lax.top_k(probs, k)                    # (G, N, K)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    # exact int32 queue positions (bf16 cumsum would break past 256 tokens)
    eoh_i = jax.nn.one_hot(top_ids, e, dtype=jnp.int32)          # (G, N, K, E)
    pos_e = (jnp.cumsum(eoh_i.reshape(ng, g_sz * k, e), axis=1)
             .reshape(ng, g_sz, k, e) - eoh_i)
    pos_k = jnp.sum(pos_e * eoh_i, axis=-1)                      # (G, N, K)
    keep = (pos_k < cap).astype(jnp.bfloat16)
    eoh = eoh_i.astype(jnp.bfloat16)
    poh = jax.nn.one_hot(pos_k, cap, dtype=jnp.bfloat16)         # (G, N, K, C)
    dispatch = jnp.einsum("gnke,gnkc,gnk->gnec", eoh, poh, keep)
    combine = jnp.einsum("gnke,gnkc,gnk->gnec", eoh, poh,
                         keep * top_p.astype(jnp.bfloat16))

    xe = _constrain(jnp.einsum("gnec,gnd->gecd", dispatch,
                               xt.astype(jnp.bfloat16)),
                    DP, "model", None, None)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, lp["e_gate"])) \
        * jnp.einsum("gecd,edf->gecf", xe, lp["e_up"])
    h = _constrain(h, DP, "model", None, None)
    ye = _constrain(jnp.einsum("gecf,efd->gecd", h, lp["e_down"]),
                    DP, "model", None, None)
    y = jax.lax.optimization_barrier(
        jnp.einsum("gnec,gecd->gnd", combine, ye)).reshape(b, s, d)

    # load-balance loss (Switch): e * sum_e f_e * p_e
    frac = jnp.mean(eoh_i.astype(jnp.float32).sum(2), axis=(0, 1))    # (E,)
    pmean = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac * pmean)
    if cfg.moe_dense_ff:                                 # arctic dense residual
        y = y + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    return y.astype(x.dtype), aux


# ===========================================================================
# Mamba2 SSD (chunked, sequential inter-chunk state scan)
# ===========================================================================

def _segsum(dA):
    """dA: (..., L) -> (..., L, L) lower-tri segment sums."""
    l = dA.shape[-1]
    cs = jnp.cumsum(dA, -1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_scan(xh, dt, A_log, Bm, Cm, chunk=256, init_state=None):
    """Chunked SSD.  xh: (B, S, H, P); dt: (B, S, H) (post-softplus);
    A_log: (H,); Bm/Cm: (B, S, G, N), head h reading group h // (H / G).
    Returns (y, final_state (B, H, P, N))."""
    b, s, h, p = xh.shape
    g, n = Bm.shape[-2:]
    hg = h // g
    c = min(chunk, s)
    nc = s // c
    a = -jnp.exp(A_log.astype(jnp.float32))                     # (H,) negative
    dA = (dt * a).reshape(b, nc, c, g, hg)                      # (B, NC, c, G, Hg)
    xc = xh.reshape(b, nc, c, g, hg, p)
    bc = Bm.reshape(b, nc, c, g, n)
    cc = Cm.reshape(b, nc, c, g, n)
    dtc = dt.reshape(b, nc, c, g, hg)

    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    def chunk_step(state, xs):
        dA_k, x_k, b_k, c_k, dt_k = xs                          # leading b
        # within-chunk cumulative decays
        cum = jnp.cumsum(dA_k, axis=1)                          # (B, c, G, Hg)
        L = jnp.exp(_segsum(jnp.moveaxis(dA_k, 1, -1)))         # (B, G, Hg, c, c)
        xw = (x_k * dt_k[..., None]).astype(jnp.float32)        # weight by dt
        # diagonal (intra-chunk): y[i] = sum_j<=i C_i.B_j L_ij x_j
        cb = jnp.einsum("bign,bjgn->bgij", c_k, b_k)            # (B, G, c, c)
        y_diag = jnp.einsum("bgij,bgkij,bjgkp->bigkp", cb, L, xw)
        # inter-chunk: contribution of carried state
        decay_in = jnp.exp(cum)                                 # (B, c, G, Hg)
        y_off = jnp.einsum("bign,bgkpn,bigk->bigkp", c_k.astype(jnp.float32),
                           state, decay_in)
        # new state: decay old + gather chunk
        tot = cum[:, -1:]                                       # (B, 1, G, Hg)
        decay_out = jnp.exp(tot - cum)                          # (B, c, G, Hg)
        s_new = jnp.einsum("bign,bigkp,bigk->bgkpn", b_k.astype(jnp.float32),
                           xw, decay_out)
        state = state * jnp.exp(tot[:, 0])[..., None, None] + s_new
        return state, (y_diag + y_off)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (dA, xc, bc, cc, dtc))
    final_state, yc = jax.lax.scan(chunk_step,
                                   init_state.reshape(b, g, hg, p, n), xs)
    y = jnp.moveaxis(yc, 0, 1).reshape(b, s, h, p)
    return y.astype(xh.dtype), final_state.reshape(b, h, p, n)


def _causal_conv(x, w, conv_state=None):
    """Depthwise causal conv.  x: (B, S, C); w: (K, C).
    Returns (y, new_state (B, K-1, C))."""
    k = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(k))
    return y, xp[:, -(k - 1):, :]


def _mup_vector(cfg: ArchConfig, dtype):
    """``in_proj``'s output multipliers over its z, x, B, C, dt segments."""
    import numpy as np
    di, gn = cfg.ssm_heads * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state
    widths = (di, di, gn, gn, cfg.ssm_heads)
    return jnp.asarray(np.repeat(np.asarray(cfg.ssm_multipliers), widths),
                       dtype)


def _group_rmsnorm(w, x, groups: int, eps):
    """RMSNorm over each of ``groups`` equal slices of the last axis."""
    if groups == 1:
        return rmsnorm(w, x, eps)
    shape = x.shape
    xg = x.reshape(*shape[:-1], groups, shape[-1] // groups)
    return rmsnorm(w.reshape(groups, -1), xg, eps).reshape(shape)


def ssm_block(lp, x, cfg: ArchConfig, conv_state=None, ssm_state=None,
              chunk=256, pad_mask=None):
    """Mamba2 block.  x: (B, S, D).  Returns (y, (conv_state, ssm_state)).

    B and C come in ``cfg.ssm_groups`` groups (head h reads group
    h // (heads / groups)); the gated RMSNorm normalises each group's
    channels on their own.

    ``pad_mask`` (B, S) bool, True = real token: padding positions contribute
    nothing to the recurrent state (conv input zeroed, dt zeroed so the SSM
    state neither decays nor updates across pads) -- required for serving
    right-padded mixed-length prompt batches.
    """
    nh, p, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    di, gn = nh * p, g * n
    x = _scale(x, cfg.ssm_in_multiplier)
    zxbcdt = jnp.einsum("bsd,de->bse", x, lp["ssm_in"])
    if any(m != 1.0 for m in cfg.ssm_multipliers):
        zxbcdt = zxbcdt * _mup_vector(cfg, zxbcdt.dtype)
    z, xin, bm, cm, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + gn, 2 * di + 2 * gn], axis=-1)
    xbc = jnp.concatenate([xin, bm, cm], -1)
    if pad_mask is not None:
        xbc = jnp.where(pad_mask[..., None], xbc, 0)
    xbc_in = xbc
    xbc, new_conv = _causal_conv(xbc, lp["ssm_conv_w"], conv_state)
    if cfg.ssm_conv_bias:
        xbc = xbc + lp["ssm_conv_b"]
    if pad_mask is not None:
        # the cached conv window must end at each slot's LAST REAL token,
        # not at the right-pad zeros: gather the per-slot (K-1)-wide window
        # [len-K+1, len) from the left-extended input, which is exactly the
        # state a solo unpadded prefill of that prompt would leave
        kk = lp["ssm_conv_w"].shape[0]
        lens = jnp.sum(pad_mask.astype(jnp.int32), axis=1)
        prefix = (jnp.zeros_like(xbc_in[:, :kk - 1]) if conv_state is None
                  else conv_state.astype(xbc_in.dtype))
        xp = jnp.concatenate([prefix, xbc_in], 1)
        cols = lens[:, None] + jnp.arange(kk - 1, dtype=jnp.int32)[None]
        new_conv = jnp.take_along_axis(xp, cols[:, :, None], axis=1)
    xbc = jax.nn.silu(xbc)
    xin, bm, cm = jnp.split(xbc, [di, di + gn], axis=-1)
    bm = bm.reshape(*bm.shape[:2], g, n)
    cm = cm.reshape(*cm.shape[:2], g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["ssm_dt_bias"])
    if pad_mask is not None:
        # dt=0 freezes the state through pads: dA = exp(0 * a) = 1 and the
        # update term x*dt vanishes, so state after the last real token is
        # identical to a solo (unpadded) prefill of the same prompt
        dt = jnp.where(pad_mask[..., None], dt, 0.0)
    xh = xin.reshape(*xin.shape[:2], nh, p)
    if x.shape[1] == 1 and ssm_state is not None:
        # single-token decode: direct state update
        a = -jnp.exp(lp["ssm_A"].astype(jnp.float32))
        dA = jnp.exp(dt[:, 0] * a)                                 # (B, H)
        xw = (xh[:, 0] * dt[:, 0, :, None]).astype(jnp.float32)    # (B, H, P)
        bh = jnp.repeat(bm[:, 0], nh // g, axis=1).astype(jnp.float32)
        ch = jnp.repeat(cm[:, 0], nh // g, axis=1).astype(jnp.float32)
        upd = jnp.einsum("bhp,bhn->bhpn", xw, bh)                  # (B, H, N)
        state = ssm_state * dA[:, :, None, None] + upd
        y = jnp.einsum("bhpn,bhn->bhp", state, ch)
        y = y[:, None].reshape(x.shape[0], 1, nh, p)
        final_state = state
    else:
        y, final_state = ssd_scan(xh, dt, lp["ssm_A"], bm, cm, chunk,
                                  init_state=ssm_state)
    y = y + xh.astype(jnp.float32) * lp["ssm_D"][None, None, :, None]
    y = y.reshape(*x.shape[:2], di).astype(x.dtype)
    y = _group_rmsnorm(lp["ssm_norm"], y * jax.nn.silu(z), g, cfg.norm_eps)
    out = jax.lax.optimization_barrier(
        jnp.einsum("bse,ed->bsd", y, lp["ssm_out"]))
    return out, (new_conv, final_state)


# ===========================================================================
# transformer layers
# ===========================================================================

def _project_qkv(lp, x, cfg, prefix=""):
    q = jnp.einsum("bsd,dhe->bshe", x, lp[prefix + "wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, lp[prefix + "wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, lp[prefix + "wv"])
    if cfg.qkv_bias and not prefix:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    return q, k, v


def _layer_of(c, layer):
    """Layer ``layer``'s slice of a stacked cache leaf (``c`` itself when
    ``layer`` is None: the leaf is already one layer's)."""
    return c if layer is None else c[layer]


def _store(c, new, layer):
    """``new`` as layer ``layer``'s slice of cache leaf ``c``: written into
    the stacked leaf at index ``layer``, or ``new`` itself when ``layer`` is
    None.  Cast to the cache's dtype either way."""
    new = new.astype(c.dtype)
    return new if layer is None else c.at[layer].set(new)


def _write_rows(c, new, cache_pos, layer=None):
    """Write ``new`` (B, s, Hkv, Dh) into KV cache ``c`` (B, S, Hkv, Dh), or
    into layer ``layer`` of a stacked (L, B, S, Hkv, Dh) one, at rows
    ``cache_pos`` .. ``cache_pos + s``.  ``cache_pos`` is a scalar (every
    slot writes at one position) or a (B,) vector of per-slot positions
    (continuous batching: slots decode at independent depths).  Only those
    rows change, so a donated cache is updated in place."""
    new = new.astype(c.dtype)
    lead = () if layer is None else (layer,)
    if jnp.ndim(cache_pos) == 0:
        start = lead + (0, cache_pos, 0, 0)
        return jax.lax.dynamic_update_slice(c, new.reshape(
            (1,) * len(lead) + new.shape), start)
    rows = jnp.arange(new.shape[0], dtype=jnp.int32)[:, None]
    cols = cache_pos[:, None] + jnp.arange(new.shape[1], dtype=jnp.int32)[None]
    return c.at[lead + (rows, cols)].set(new)


def attn_block(lp, x, cfg: ArchConfig, positions, *, causal=True,
               window_dyn=None, kv_cache=None, cache_pos=None, layer=None):
    """Self-attention sublayer.  Returns (y, new_kv) where new_kv is the
    (k, v) pair either freshly computed (prefill/train) or cache-updated.

    With ``layer``, ``kv_cache`` holds every layer's (L, B, S, Hkv, Dh)
    stacks: this layer's new rows are written into them and the returned
    stacks differ from the input only there."""
    q, k, v = _project_qkv(lp, x, cfg)
    k = _scale(k, cfg.key_multiplier)
    q = _constrain(rope(q, positions, cfg.rope_theta), DP, None, "model", None)
    k = _constrain(rope(k, positions, cfg.rope_theta), DP, None, "model", None)
    v = _constrain(v, DP, None, "model", None)
    if kv_cache is not None:
        ck, cv = (_write_rows(c, t, cache_pos, layer)
                  for c, t in zip(kv_cache, (k, v)))
        lk, lv = _layer_of(ck, layer), _layer_of(cv, layer)
        sk = lk.shape[1]
        kpos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32)[None],
                                (x.shape[0], sk))
        valid = kpos <= positions[:, -1:]
        y = attention(q, lk.astype(q.dtype), lv.astype(q.dtype), positions,
                      jnp.where(valid, kpos, jnp.int32(2**30)),
                      causal=causal, window=cfg.attn_window or None,
                      window_dyn=window_dyn, chunk=cfg.attn_chunk,
                      seq_sharded=x.shape[1] == 1)
        new_kv = (ck, cv)
    else:
        kpos = positions
        y = attention(q, k, v, positions, kpos, causal=causal,
                      window=cfg.attn_window or None, window_dyn=window_dyn,
                      chunk=cfg.attn_chunk)
        new_kv = (k, v)
    return jax.lax.optimization_barrier(
        jnp.einsum("bshe,hed->bsd", y, lp["wo"])), new_kv


def decoder_layer(lp, x, cfg: ArchConfig, positions, *, is_global=None,
                  enc_out=None, cache=None, cache_pos=None, pad_mask=None,
                  layer=None):
    """One decoder layer.  Returns (x, new_cache, aux_loss).

    ``cache_pos`` may be a scalar (uniform write position, the historical
    prefill/lockstep-decode contract) or a (B,) vector of per-slot positions
    (continuous-batching decode: every slot sits at its own depth).
    ``pad_mask`` (B, S) marks real tokens in a right-padded prefill batch.
    ``layer`` (the decode step): ``cache`` is the whole stacked cache, every
    leaf with its leading L axis, and ``new_cache`` is that cache with this
    layer's new KV rows and SSM/conv state written in at index ``layer``.
    """
    lp = _gather_weights(lp)
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)

    window_dyn = None
    if cfg.hybrid and cfg.attn_window and is_global is not None:
        big = jnp.int32(2**30)
        window_dyn = jnp.where(is_global, big, jnp.int32(cfg.attn_window))

    y_ssm = y_attn = None
    if cfg.family == "ssm" or cfg.hybrid:
        with jax.named_scope("ssm_mixer"):
            y_ssm, (conv_s, ssm_s) = ssm_block(
                lp, h, cfg,
                conv_state=None if cache is None
                else _layer_of(cache["conv"], layer),
                ssm_state=None if cache is None
                else _layer_of(cache["ssm"], layer),
                pad_mask=pad_mask)
        if cache is not None:
            new_cache.update(conv=_store(cache["conv"], conv_s, layer),
                             ssm=_store(cache["ssm"], ssm_s, layer))
        y_ssm = _scale(y_ssm, cfg.ssm_out_multiplier)
    if cfg.family != "ssm":
        with jax.named_scope("attn_mixer"):
            y_attn, kv = attn_block(
                lp, _scale(h, cfg.attn_in_multiplier), cfg, positions,
                window_dyn=window_dyn,
                kv_cache=None if cache is None else (cache["k"], cache["v"]),
                cache_pos=cache_pos, layer=layer)
        if cache is not None:
            new_cache.update(k=kv[0], v=kv[1])
        y_attn = _scale(y_attn, cfg.attn_out_multiplier)
    # hybrid: x + m_ssm_out * SSM(m_ssm_in * h) + m_attn_out * Attn(m_attn_in * h)
    x = x + (y_attn if y_ssm is None else y_ssm if y_attn is None
             else y_ssm + y_attn)

    if enc_out is not None or (cache is not None and "xk" in cache):
        # cross-attention; decode uses the prefill-computed cross-KV cache
        h = rmsnorm(lp["ln_x"], x, cfg.norm_eps)
        q = jnp.einsum("bsd,dhe->bshe", h, lp["xwq"])
        if enc_out is not None:
            k = jnp.einsum("bsd,dhe->bshe", enc_out, lp["xwk"])
            v = jnp.einsum("bsd,dhe->bshe", enc_out, lp["xwv"])
            if cache is not None and "xk" in cache:
                new_cache.update(xk=_store(cache["xk"], k, layer),
                                 xv=_store(cache["xv"], v, layer))
        else:
            k, v = (_layer_of(cache[n], layer).astype(q.dtype)
                    for n in ("xk", "xv"))
            new_cache.update(xk=cache["xk"], xv=cache["xv"])
        epos = jnp.broadcast_to(
            jnp.arange(k.shape[1], dtype=jnp.int32)[None],
            (k.shape[0], k.shape[1]))
        y = attention(q, k, v, positions, epos, causal=False,
                      chunk=cfg.attn_chunk)
        x = x + jax.lax.optimization_barrier(
            jnp.einsum("bshe,hed->bsd", y, lp["xwo"]))

    if cfg.family != "ssm" or cfg.hybrid:
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        with jax.named_scope("mlp"):
            if cfg.num_experts:
                y, aux = moe_block(lp, h, cfg)
            else:
                y = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"],
                           cfg.mlp_multipliers)
        x = x + y
    if cfg.seq_parallel:
        # Megatron-SP: the stored (remat-saved) residual stream is S-sharded
        # over "model"; GSPMD all-gathers S at the qkv/up projections and
        # reduce-scatters after the output projections.
        return _constrain(x, DP, "model", None), new_cache, aux
    return _constrain(x, DP, None, None), new_cache, aux


def encoder_layer(lp, x, cfg: ArchConfig, positions):
    lp = _gather_weights(lp)
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    y, _ = attn_block(lp, h, cfg, positions, causal=False)
    x = x + y
    h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


# ===========================================================================
# full forward passes
# ===========================================================================

def _remat(f, cfg: ArchConfig):
    if cfg.remat == "full":
        return jax.checkpoint(f)
    if cfg.remat == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return f


def _global_flags(cfg: ArchConfig):
    import numpy as np
    flags = np.zeros((cfg.num_layers,), np.bool_)
    for i in cfg.global_attn_layers:
        flags[i] = True
    return jnp.asarray(flags)


def _embed_inputs(params, cfg: ArchConfig, batch):
    """tokens (+ optional frontend embeddings) -> (B, S, D), positions."""
    x = _scale(jnp.take(params["embed"], batch["tokens"], axis=0),
               cfg.embedding_multiplier)
    if cfg.frontend != "none" and "frontend_embeds" in batch:
        fe = jnp.einsum("bsf,fd->bsd", batch["frontend_embeds"].astype(x.dtype),
                        params["frontend_proj"])
        x = jnp.concatenate([fe, x], axis=1)
    b, s, _ = x.shape
    x = _constrain(jax.lax.optimization_barrier(x), DP, None, None)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return x, positions


def run_decoder_stack(params, cfg: ArchConfig, x, positions, enc_out=None):
    """scan over stacked layers; returns (x, total_aux)."""
    flags = _global_flags(cfg)

    def body(carry, xs):
        h, aux = carry
        lp, is_global = xs
        h2, _, a = decoder_layer(lp, h, cfg, positions, is_global=is_global,
                                 enc_out=enc_out)
        return (h2, aux + a), None

    body = _remat(body, cfg)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (params["layers"], flags))
    return x, aux


def lm_forward(params, cfg: ArchConfig, batch):
    """Full causal forward -> final hidden states (B, S, D), aux."""
    x, positions = _embed_inputs(params, cfg, batch)
    enc_out = None
    if cfg.encoder_layers:
        ex = jnp.einsum("bsf,fd->bsd",
                        batch["encoder_embeds"].astype(x.dtype),
                        params["frontend_proj"])
        epos = jnp.broadcast_to(
            jnp.arange(ex.shape[1], dtype=jnp.int32)[None],
            (ex.shape[0], ex.shape[1]))

        def ebody(h, lp):
            return encoder_layer(lp, h, cfg, epos), None

        ebody = _remat(ebody, cfg)
        ex, _ = jax.lax.scan(ebody, ex, params["enc_layers"])
        enc_out = rmsnorm(params["enc_norm"], ex, cfg.norm_eps)
    x, aux = run_decoder_stack(params, cfg, x, positions, enc_out)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def _head_weight(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def _logits(params, cfg: ArchConfig, x):
    """(B, S, D) final hidden states -> float32 (B, S, V) logits."""
    logits = jnp.einsum("bsd,dv->bsv", x, _head_weight(params, cfg))
    return _scale(logits.astype(jnp.float32), cfg.lm_head_multiplier)


def lm_loss(params, cfg: ArchConfig, batch, vocab_chunk_tokens: int = 512):
    """Next-token CE, chunked over the sequence (no (tokens, vocab) tensor)."""
    hidden, aux = lm_forward(params, cfg, batch)
    labels = batch["labels"]
    if hidden.shape[1] != labels.shape[1]:      # frontend prepended tokens
        hidden = hidden[:, hidden.shape[1] - labels.shape[1]:]
    w = _head_weight(params, cfg)
    b, s, d = hidden.shape
    c = min(vocab_chunk_tokens, s)
    nc = s // c
    hc = jnp.moveaxis(hidden[:, :nc * c].reshape(b, nc, c, d), 1, 0)
    lc = jnp.moveaxis(labels[:, :nc * c].reshape(b, nc, c), 1, 0)

    @jax.checkpoint
    def chunk_ce(hx, lx):
        hx = _constrain(hx, DP, None, None)
        lx = _constrain(lx, DP, None)
        logits = _constrain(_scale(
            jnp.einsum("bcd,dv->bcv", hx, w).astype(jnp.float32),
            cfg.lm_head_multiplier), DP, None, "model")
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lx[..., None], -1)[..., 0]
        return jnp.sum(lse - gold)

    def step(acc, xs):
        hx, lx = xs
        return acc + chunk_ce(hx, lx), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (hc, lc))
    loss = total / (b * nc * c)
    return loss + 0.01 * aux


# ===========================================================================
# serving (KV/SSM cache decode)
# ===========================================================================

def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype=jnp.bfloat16,
               enc_seq: int = 0):
    """Stacked per-layer cache pytree with leading L axis."""
    l, hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hdim
    cache: Dict[str, Any] = {}
    if cfg.family != "ssm":
        cache["k"] = jnp.zeros((l, batch, max_seq, hkv, hd), dtype)
        cache["v"] = jnp.zeros((l, batch, max_seq, hkv, hd), dtype)
    if cfg.family == "ssm" or cfg.hybrid:
        nh, p, n, k = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv
        di = nh * p
        cache["conv"] = jnp.zeros(
            (l, batch, k - 1, di + 2 * cfg.ssm_groups * n), dtype)
        cache["ssm"] = jnp.zeros((l, batch, nh, p, n), jnp.float32)
    if cfg.encoder_layers and enc_seq:
        cache["xk"] = jnp.zeros((l, batch, enc_seq, hkv, hd), dtype)
        cache["xv"] = jnp.zeros((l, batch, enc_seq, hkv, hd), dtype)
    return cache


def lm_prefill(params, cfg: ArchConfig, batch, max_seq: int,
               cache_dtype=jnp.bfloat16, prompt_lens=None):
    """Inference prefill: run the full prompt, emit (last-token logits, cache).

    The cache is written in place at position 0 (dynamic_update_slice), so
    the lowered HLO is the real serving prefill, not a training forward.

    ``prompt_lens`` (B,) int32 serves a RIGHT-padded mixed-length prompt
    batch: logits come from each slot's own last real token (not column -1),
    causal masking keeps real queries from attending the trailing pads, and
    SSM/hybrid recurrent state is pad-masked so every slot's cache is
    identical to a solo unpadded prefill of its prompt.  Decode then resumes
    per slot at position ``prompt_lens[b]`` (vector ``pos`` in
    ``serve_step``), overwriting each pad cache entry before the causal mask
    can ever expose it.
    """
    x, positions = _embed_inputs(params, cfg, batch)
    b = x.shape[0]
    pad_mask = None
    if prompt_lens is not None:
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
        pad_mask = (jnp.arange(x.shape[1], dtype=jnp.int32)[None]
                    < prompt_lens[:, None])
        x = jnp.where(pad_mask[..., None], x, 0)
    enc_out = None
    if cfg.encoder_layers:
        ex = jnp.einsum("bsf,fd->bsd",
                        batch["encoder_embeds"].astype(x.dtype),
                        params["frontend_proj"])
        epos = jnp.broadcast_to(
            jnp.arange(ex.shape[1], dtype=jnp.int32)[None],
            (ex.shape[0], ex.shape[1]))
        ex, _ = jax.lax.scan(lambda h, lp: (encoder_layer(lp, h, cfg, epos), None),
                             ex, params["enc_layers"])
        enc_out = rmsnorm(params["enc_norm"], ex, cfg.norm_eps)
    cache = init_cache(cfg, b, max_seq, cache_dtype,
                       enc_seq=enc_out.shape[1] if enc_out is not None else 0)
    flags = _global_flags(cfg)

    def body(h, xs):
        lp, lcache, is_global = xs
        h2, new_cache, _ = decoder_layer(lp, h, cfg, positions,
                                         is_global=is_global, enc_out=enc_out,
                                         cache=lcache, cache_pos=0,
                                         pad_mask=pad_mask)
        return h2, new_cache

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache, flags))
    if prompt_lens is None:
        x = x[:, -1:]
    else:                       # each slot's own last real token
        idx = jnp.broadcast_to((prompt_lens - 1)[:, None, None],
                               (b, 1, x.shape[-1]))
        x = jnp.take_along_axis(x, idx, axis=1)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x)[:, 0], new_cache


def serve_step(params, cfg: ArchConfig, cache, tokens, pos, enc_out=None):
    """One decode step.  tokens: (B,) int32; pos: scalar int32 (current
    length, uniform across the batch) or (B,) int32 vector of PER-SLOT
    lengths -- the continuous-batching contract, where recycled slots sit at
    independent generation depths.  Returns (logits (B, V), new_cache).

    The stacked cache rides in the layer scan's carry: layer l writes only
    its new K/V rows at [l, b, pos[b]] and its SSM/conv state at [l], and
    reads its own slice for attention; the cross-attention K/V pass through
    untouched.  A donated cache is therefore updated in place, with no
    temporary the size of a stack."""
    x = _scale(jnp.take(params["embed"], tokens[:, None], axis=0),
               cfg.embedding_multiplier)
    b = x.shape[0]
    if jnp.ndim(pos) == 0:
        positions = jnp.full((b, 1), pos, jnp.int32)
    else:
        positions = pos.astype(jnp.int32)[:, None]
    flags = _global_flags(cfg)

    def body(carry, xs):
        h, stacked = carry
        lp, is_global, layer = xs
        h2, stacked, _ = decoder_layer(lp, h, cfg, positions,
                                       is_global=is_global, enc_out=enc_out,
                                       cache=stacked, cache_pos=pos,
                                       layer=layer)
        return (h2, stacked), None

    (x, new_cache), _ = jax.lax.scan(
        body, (x, cache),
        (params["layers"], flags, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x)[:, 0], new_cache


def param_count(cfg: ArchConfig) -> int:
    """Analytic parameter count (for 6ND roofline math)."""
    shapes = _layer_param_shapes(cfg, cross_attn=cfg.encoder_layers > 0)
    per_layer = sum(math.prod(s) for s in shapes.values())
    n = per_layer * cfg.num_layers + cfg.d_model        # + final_norm
    if cfg.encoder_layers:
        enc = _layer_param_shapes(cfg, cross_attn=False)
        n += (sum(math.prod(s) for s in enc.values())
              * cfg.encoder_layers + cfg.d_model)       # + enc_norm
    n += cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    if cfg.frontend != "none":
        n += cfg.frontend_dim * cfg.d_model
    return n


def active_param_count(cfg: ArchConfig) -> int:
    """Active params per token (MoE: only routed experts count)."""
    if not cfg.num_experts:
        return param_count(cfg)
    shapes = _layer_param_shapes(cfg)
    expert_names = ("e_gate", "e_up", "e_down")
    per_layer_all = sum(math.prod(s) for s in shapes.values())
    experts = sum(math.prod(shapes[n]) for n in expert_names)
    active_experts = experts * cfg.experts_per_token // cfg.num_experts
    per_layer = per_layer_all - experts + active_experts
    n = per_layer * cfg.num_layers
    n += cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    return n
