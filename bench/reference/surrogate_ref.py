"""Plain float32 reference of the Fig. 1 surrogate, its L1 loss and Adam.

Written from the paper's description for the benchmark's ``correct``
comparison; it imports nothing of the program.  The network maps the
conditioning vector (6 simulation parameters and the normalised time) to the
six fields:

    dense -> reshape (H/16, W/16, C) -> layernorm -> leaky ReLU
    4 x [ transposed 4x4 conv, stride 2 -> leaky ReLU
          -> 3x3 conv -> layernorm -> leaky ReLU ]      (channels halve, >= 32)
    -> 3x3 conv to the fields

Layernorm is over channels (eps 1e-5), leaky ReLU has slope 0.2, convs are
'same'-padded, NHWC with HWIO kernels.  The transposed conv inserts a zero
between input pixels, pads two on each side and applies the kernel as
stored (no flip), so each output is twice the input size.  The loss is the
mean absolute error over every value of the batch (paper Eq. 1).  Adam:
b1 0.9, b2 0.999, eps 1e-8, bias-corrected.

Precision.  The configurations state float32 storage and accumulation at
XLA's default matmul precision.  On a TPU that takes the products of every
matmul and convolution from operands rounded to bfloat16, in the forward
pass and in the products of the backward pass alike; on the CPU it keeps
them in float32.  With ``rounded=True`` the reference rounds explicitly
(operands, and in the backward pass the incoming cotangent, rounded to
bfloat16 and kept in float32; products and sums at the highest precision,
so exact in float32 but for the order of the sums).  With ``rounded=False``
it is plain float32 at the highest precision.  ``default_rounds()`` says
which of the two the platform's default precision is.

Parameters are built here too, from a key (He-normal kernels, zero biases,
unit layernorm gains), in the pytree layout the program's step takes: the
benchmark hands the same weights to the program and to this reference.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

SLOPE = 0.2
LN_EPS = 1e-5
B1, B2, EPS = 0.9, 0.999, 1e-8


def widths(base: int):
    """Channel counts in and out of the four upsampling stages."""
    out, ch = [], base
    for _ in range(4):
        nxt = max(ch // 2, 32)
        out.append((ch, nxt))
        ch = nxt
    return out


@partial(jax.jit, static_argnames=("model",))
def init_params(key, model):
    """He-normal weights from ``key``; ``model`` is a tuple of the config's
    (height, width, fields, base_channels, cond_dim)."""
    height, width, fields, base, cond_dim = model
    h0, w0 = height // 16, width // 16
    keys = iter(jax.random.split(key, 16))

    def he(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) \
            * math.sqrt(2.0 / fan_in)

    p = {"proj": {"w": he((cond_dim, h0 * w0 * base), cond_dim),
                  "b": jnp.zeros((h0 * w0 * base,), jnp.float32)},
         "ln_in": {"g": jnp.ones((base,), jnp.float32),
                   "b": jnp.zeros((base,), jnp.float32)}}
    for i, (cin, cout) in enumerate(widths(base)):
        p[f"up{i}_t"] = {"w": he((4, 4, cin, cout), 16 * cin),
                         "b": jnp.zeros((cout,), jnp.float32)}
        p[f"up{i}_c"] = {"w": he((3, 3, cout, cout), 9 * cout),
                         "b": jnp.zeros((cout,), jnp.float32)}
        p[f"up{i}_ln"] = {"g": jnp.ones((cout,), jnp.float32),
                          "b": jnp.zeros((cout,), jnp.float32)}
    cl = widths(base)[-1][1]
    p["out"] = {"w": he((3, 3, cl, fields), 9 * cl),
                "b": jnp.zeros((fields,), jnp.float32)}
    return p


def _leaky(x):
    return jnp.where(x >= 0, x, SLOPE * x)


def _layernorm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["g"] + p["b"]


def default_rounds() -> bool:
    """Whether XLA's default precision rounds matmul and convolution operands
    to bfloat16 here: on a TPU it does, on the CPU it does not."""
    return jax.default_backend() == "tpu"


def _bf16(a):
    """``a`` rounded to bfloat16 (nearest, ties to even), kept in float32."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _rounded(product):
    """``product(x, w)`` with both operands, and in the backward pass the
    cotangent, rounded to bfloat16 (module docstring)."""
    @jax.custom_vjp
    def f(x, w):
        return product(_bf16(x), _bf16(w))

    def fwd(x, w):
        x, w = _bf16(x), _bf16(w)
        return product(x, w), (x, w)

    def bwd(res, ct):
        return jax.vjp(product, *res)[1](_bf16(ct))

    f.defvjp(fwd, bwd)
    return f


def _conv_same(x, w):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv_up(x, w):
    """Transposed 4x4 conv, stride 2: a zero between input pixels, two of
    padding on each side, the kernel as stored."""
    n, h, wd, c = x.shape
    z = jnp.zeros((n, 2 * h - 1, 2 * wd - 1, c), x.dtype)
    z = z.at[:, ::2, ::2, :].set(x)
    return jax.lax.conv_general_dilated(
        z, w.astype(x.dtype), (1, 1), ((2, 2), (2, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _matmul(x, w):
    return x @ w.astype(x.dtype)


_ROUNDED = {f: _rounded(f) for f in (_conv_same, _conv_up, _matmul)}


def forward(params, cond, height, width, rounded: bool = False):
    def op(f):
        return _ROUNDED[f] if rounded else f
    base = params["ln_in"]["g"].shape[0]
    x = op(_matmul)(cond, params["proj"]["w"]) + params["proj"]["b"]
    x = x.reshape(cond.shape[0], height // 16, width // 16, base)
    x = _leaky(_layernorm(params["ln_in"], x))
    for i in range(4):
        x = _leaky(op(_conv_up)(x, params[f"up{i}_t"]["w"])
                   + params[f"up{i}_t"]["b"])
        x = op(_conv_same)(x, params[f"up{i}_c"]["w"]) + params[f"up{i}_c"]["b"]
        x = _leaky(_layernorm(params[f"up{i}_ln"], x))
    return op(_conv_same)(x, params["out"]["w"]) + params["out"]["b"]


@partial(jax.jit, static_argnames=("height", "width", "dtype", "rounded"))
def loss_and_grad_sum(params, cond, target, height, width, dtype, rounded):
    """Sum of |error| over a block of rows, and its gradient, with every
    operation in ``dtype`` (float32 at the highest matmul precision, with
    bfloat16 operands where ``rounded``; or a lower precision throughout,
    for the control)."""
    def loss(p):
        p = jax.tree.map(lambda a: a.astype(dtype), p)
        pred = forward(p, cond.astype(dtype), height, width, rounded)
        return jnp.sum(jnp.abs(pred - target.astype(dtype)).astype(jnp.float32))
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        return jax.value_and_grad(loss)(params)


def loss_and_grad(params, cond, target, height, width, rows: int = 8,
                  dtype=jnp.float32, rounded: bool = False):
    """Batch-mean L1 loss and its gradient, in blocks of ``rows`` samples."""
    n = cond.shape[0]
    total, grad = 0.0, None
    for s in range(0, n, rows):
        v, g = loss_and_grad_sum(params, cond[s:s + rows],
                                 target[s:s + rows], height, width, dtype,
                                 rounded)
        total = total + v
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    scale = 1.0 / target.size
    return total * scale, jax.tree.map(lambda a: a.astype(jnp.float32) * scale,
                                       grad)


@partial(jax.jit, static_argnames=("lr",))
def adam(params, m, v, grads, step, lr):
    """One Adam step; ``step`` counts from 1."""
    m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, m, grads)
    v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, v, grads)
    c1, c2 = 1 - B1 ** step, 1 - B2 ** step
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + EPS),
        params, m, v)
    return params, m, v
