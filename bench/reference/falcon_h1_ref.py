"""Plain float32 reference of the Falcon-H1 decoder (``model_type``
``falcon_h1``), for the LM serving cell's ``correct`` comparison.

Written from the published config and layer equations; it imports nothing of
the program.  ``config`` is the model's ``config.json`` as a dict (its key
names), ``tokens`` one sequence, and the pass is the full causal forward with
no cache and no batching.  Each of the ``num_hidden_layers`` identical layers:

    h = rmsnorm(x) * input_layernorm
    x = x + ssm_out_multiplier * Mamba2(ssm_in_multiplier * h)
          + attention_out_multiplier * Attn(attention_in_multiplier * h)
    x = x + down_m * W_down(silu(gate_m * W_gate g) * W_up g),
        g = rmsnorm(x) * pre_ff_layernorm,  (gate_m, down_m) = mlp_multipliers

Attention: ``num_attention_heads`` query heads of ``head_dim`` over
``num_key_value_heads`` shared key/value heads (query head i reads kv head
i // (heads / kv heads)), keys scaled by ``key_multiplier``, rotary
embedding on the two halves of each head at ``rope_theta``, softmax scale
``head_dim ** -0.5``.

Mamba-2: ``in_proj`` gives [z, x, B, C, dt] (widths d_ssm, d_ssm,
groups * d_state twice, heads), each segment scaled by its entry of
``ssm_multipliers``; x, B and C pass a depthwise causal conv of width
``mamba_d_conv`` with bias and a SiLU; ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``; head h of ``mamba_n_heads`` reads B and C of group
h // (heads / groups) and runs the recurrence one token at a time:

    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T,   y_t = s_t C_t + D x_t

then ``y * silu(z)`` is RMS-normalised over each group's d_ssm / groups
channels (``mamba_norm_before_gate`` false) and projected out.

The embedding is scaled by ``embedding_multiplier`` and the logits by
``lm_head_multiplier``.  RMSNorm eps is ``rms_norm_eps``.

Every product runs at the highest matmul precision.  The recurrence holds
its state in ``state_dtype`` between tokens (float32, as the configuration
states); with ``operand_dtype`` set, every operand of a product (weights and
activations) is first rounded to it.  The control is the pass one precision
below the configuration: operands in float8 (the configuration states
bfloat16) and the state in bfloat16 (it states float32).  The caller hands the weights as the
program holds them (rounded to bfloat16 and widened); the pass goes one
layer at a time, so that one layer's float32 weights are resident at once.

Weights, per layer (every projection is (in, out)): ``input_layernorm``,
``pre_ff_layernorm`` (D,); ``q_proj`` (D, H*hd), ``k_proj``, ``v_proj``
(D, Hkv*hd), ``o_proj`` (H*hd, D); ``in_proj`` (D, 2*d_ssm + 2*G*N + nh),
``conv_weight`` (K, d_ssm + 2*G*N), ``conv_bias``, ``dt_bias``, ``A_log``,
``D`` (nh,), ``norm`` (d_ssm,), ``out_proj`` (d_ssm, D); ``gate_proj``,
``up_proj`` (D, F), ``down_proj`` (F, D).  Top level: ``embed_tokens``
(V, D), ``final_layernorm`` (D,), ``lm_head`` (D, V).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512           # query rows of one attention block


def _static(config: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in config.items()
                        if isinstance(v, (int, float, bool, list))))


def _mm(spec, a, b, dt):
    """``einsum(spec, a, b)`` with both operands rounded to ``dt`` first."""
    if dt is not None:
        a, b = (v.astype(dt).astype(jnp.float32) for v in (a, b))
    return jnp.einsum(spec, a, b)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: (S, heads, hd); the rotation pairs channel i with i + hd/2."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(theta)
                                                           / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(c, lp, h, dt):
    s = h.shape[0]
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    pos = jnp.arange(s)
    q = _rope(_mm("sd,de->se", h, lp["q_proj"], dt).reshape(s, nh, hd), pos,
              c["rope_theta"])
    k = _rope(_mm("sd,de->se", h, lp["k_proj"], dt).reshape(s, nkv, hd)
              * c["key_multiplier"], pos, c["rope_theta"])
    v = _mm("sd,de->se", h, lp["v_proj"], dt).reshape(s, nkv, hd)
    rep = nh // nkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    out = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        scores = _mm("qhd,khd->hqk", qb, k, dt) / math.sqrt(hd)
        causal = pos[None, :] <= pos[q0:q0 + Q_BLOCK, None]
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        out.append(_mm("hqk,khd->qhd", p, v, dt))
    return _mm("se,ed->sd", jnp.concatenate(out).reshape(s, nh * hd),
               lp["o_proj"], dt)


def _mamba(c, lp, h, state_dtype, dt_op):
    s = h.shape[0]
    nh, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    g, d_ssm, k = c["mamba_n_groups"], c["mamba_d_ssm"], c["mamba_d_conv"]
    widths = (d_ssm, d_ssm, g * n, g * n, nh)
    mup = np.repeat(np.asarray(c["ssm_multipliers"], np.float32), widths)
    zxbcdt = _mm("sd,de->se", h, lp["in_proj"], dt_op) * mup
    cut = np.cumsum(widths)[:-1]
    z, xbc, dt = (zxbcdt[:, :cut[0]], zxbcdt[:, cut[0]:cut[3]],
                  zxbcdt[:, cut[3]:])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = sum(padded[i:i + s] * lp["conv_weight"][i] for i in range(k))
    xbc = jax.nn.silu(conv + lp["conv_bias"])
    x = xbc[:, :d_ssm].reshape(s, nh, p)
    heads_b = jnp.repeat(xbc[:, d_ssm:d_ssm + g * n].reshape(s, g, n),
                         nh // g, axis=1)
    heads_c = jnp.repeat(xbc[:, d_ssm + g * n:].reshape(s, g, n),
                         nh // g, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                       # (S, nh)
    a = -jnp.exp(lp["A_log"])

    def step(state, xs):
        x_t, b_t, c_t, dt_t = xs
        state = (jnp.exp(dt_t * a)[:, None, None] * state.astype(jnp.float32)
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        state = state.astype(state_dtype)
        y = jnp.einsum("hpn,hn->hp", state.astype(jnp.float32), c_t)
        return state, y

    state0 = jnp.zeros((nh, p, n), state_dtype)
    _, y = jax.lax.scan(step, state0, (x, heads_b, heads_c, dt))
    y = (y + lp["D"][:, None] * x).reshape(s, d_ssm) * jax.nn.silu(z)
    y = _rmsnorm(y.reshape(s, g, d_ssm // g),
                 lp["norm"].reshape(g, d_ssm // g), c["rms_norm_eps"])
    return _mm("se,ed->sd", y.reshape(s, d_ssm), lp["out_proj"], dt_op)


@partial(jax.jit, static_argnames=("cfg", "state_dtype", "operand_dtype"))
def _layer(lp, x, cfg: tuple, state_dtype, operand_dtype):
    c, dt = dict(cfg), operand_dtype
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        h = _rmsnorm(x, lp["input_layernorm"], c["rms_norm_eps"])
        m = _mamba(c, lp, h * c["ssm_in_multiplier"], state_dtype, dt)
        a = _attention(c, lp, h * c["attention_in_multiplier"], dt)
        x = (x + c["ssm_out_multiplier"] * m
             + c["attention_out_multiplier"] * a)
        g = _rmsnorm(x, lp["pre_ff_layernorm"], c["rms_norm_eps"])
        gate_m, down_m = c["mlp_multipliers"]
        y = _mm("sf,fd->sd", jax.nn.silu(gate_m * _mm(
            "sd,df->sf", g, lp["gate_proj"], dt)) * _mm(
            "sd,df->sf", g, lp["up_proj"], dt), lp["down_proj"], dt)
        return x + down_m * y


@partial(jax.jit, static_argnames=("cfg",))
def _embed(embed, tokens, cfg: tuple):
    return embed[tokens].astype(jnp.float32) * dict(cfg)["embedding_multiplier"]


@partial(jax.jit, static_argnames=("cfg", "operand_dtype"))
def _head(final_norm, lm_head, x, cfg: tuple, operand_dtype):
    c = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, final_norm.astype(jnp.float32), c["rms_norm_eps"])
        return (_mm("sd,dv->sv", h, lm_head.astype(jnp.float32),
                    operand_dtype) * c["lm_head_multiplier"])


def forward(config: dict, params: dict, tokens, rows=None,
            state_dtype=jnp.float32, operand_dtype=None):
    """Float32 logits (len(rows), V) at positions ``rows`` (all when None)
    of one sequence ``tokens`` (S,); ``params['layers']`` is a sequence of
    per-layer weight dicts, or a function of the layer index giving one."""
    cfg = _static(config)
    layers = params["layers"]
    layer_at = layers if callable(layers) else layers.__getitem__
    tokens = np.asarray(tokens, np.int32)
    rows = np.arange(len(tokens)) if rows is None else np.asarray(rows)
    # pad to a whole number of query blocks, so that few lengths compile:
    # the pass is causal, and the rows asked for lie before the pad
    tokens = np.pad(tokens, (0, -len(tokens) % Q_BLOCK))
    x = _embed(params["embed_tokens"], jnp.asarray(tokens), cfg)
    for i in range(config["num_hidden_layers"]):
        x = _layer(layer_at(i), x, cfg, state_dtype, operand_dtype)
    x = x[jnp.asarray(rows, jnp.int32)]
    return _head(params["final_layernorm"], params["lm_head"], x, cfg,
                 operand_dtype)
