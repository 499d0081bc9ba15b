"""Work the algorithms need, counted from configuration and plane counts.

The same count holds whatever implements the work:

* FLOPs cover convolutions and dense layers only, 2 per multiply-add.  A
  transposed convolution is counted on its undilated input (each input pixel
  meets the whole kernel once); the zeros a dilated implementation inserts
  are not work.
* Training costs three forward passes' FLOPs per sample (forward, and the
  two products of the backward pass).
* Logical compressed bytes are 2 per kept bit plane plus the 2-byte
  fixed-accuracy block header, counted from the plane counts, never from
  the program's array shapes, so a change of resident layout does not stale
  them.
"""
from __future__ import annotations

import numpy as np

HEADER_BYTES = 2          # per block, fixed-accuracy mode
PLANE_BYTES = 2           # one bit per value of a 4x4 block


def surrogate_forward_flops(model: dict) -> int:
    """FLOPs of one forward pass of one sample of the Fig. 1 surrogate."""
    h, w = model["height"] // 16, model["width"] // 16
    ch = model["base_channels"]
    flops = 2 * model["cond_dim"] * h * w * ch             # dense projection
    for _ in range(4):
        cout = max(ch // 2, 32)
        flops += 2 * h * w * 16 * ch * cout                # 4x4 transposed
        h, w = 2 * h, 2 * w
        flops += 2 * h * w * 9 * cout * cout               # 3x3 conv
        ch = cout
    flops += 2 * h * w * 9 * ch * model["fields"]          # output conv
    return flops


def surrogate_train_flops(model: dict) -> int:
    return 3 * surrogate_forward_flops(model)


def logical_bytes(nplanes) -> np.ndarray:
    """Per-sample logical compressed bytes from (..., nb) plane counts."""
    npl = np.asarray(nplanes, np.int64)
    return HEADER_BYTES * npl.shape[-1] + PLANE_BYTES * npl.sum(axis=-1)


def raw_bytes(sample_shape) -> int:
    """Bytes of one float32 sample."""
    return 4 * int(np.prod(sample_shape))
