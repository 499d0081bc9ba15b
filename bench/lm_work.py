"""Work of the LM serving cells, counted from the published config.

The same count holds whatever implements the work:

* FLOPs are 2 per multiply-add of the projections (attention q, k, v, o;
  Mamba-2 in and out; the MLP's three) and of the head, the causal
  attention products (q.k and p.v over the positions a token attends), and
  the SSM recurrence (state update and read-out: 4 per head, head channel
  and state channel).  The head counts for the tokens whose logits are
  taken: a prefill's last token and every decode token.
* A decode step's logical bytes are a lower bound on what it must move:
  every weight once at its stored width (the embedding only for the rows it
  reads), each active slot's keys and values up to its depth, and every
  slot's SSM and conv state read and written, at the configuration's state
  widths.
"""
from __future__ import annotations

import numpy as np

PARAM_BYTES = {"bfloat16": 2, "float32": 4}


def _dims(conf: dict) -> dict:
    d, heads, kv, hd = (conf["hidden_size"], conf["num_attention_heads"],
                        conf["num_key_value_heads"], conf["head_dim"])
    d_ssm, gn = conf["mamba_d_ssm"], conf["mamba_n_groups"] * conf["mamba_d_state"]
    nh = conf["mamba_n_heads"]
    proj = (2 * d * heads * hd + 2 * d * kv * hd
            + d * (2 * d_ssm + 2 * gn + nh) + d_ssm * d
            + 3 * d * conf["intermediate_size"])
    layer = (proj + conf["mamba_d_conv"] * (d_ssm + 2 * gn)
             + (d_ssm + 2 * gn) * conf["mamba_conv_bias"] + 3 * nh + d_ssm
             + 2 * d)
    return {"proj": proj, "layer": layer, "attn": 2 * heads * hd,
            "kv": 2 * kv * hd, "ssm": d_ssm * conf["mamba_d_state"],
            "conv": (conf["mamba_d_conv"] - 1) * (d_ssm + 2 * gn),
            "head": d * conf["vocab_size"]}


def token_flops(conf: dict, attended) -> np.ndarray:
    """FLOPs of the layers for tokens that attend ``attended`` positions
    each (no head)."""
    m = _dims(conf)
    per = 2 * m["proj"] + 2 * m["attn"] * np.asarray(attended, np.float64) \
        + 4 * m["ssm"]
    return per * conf["num_hidden_layers"]


def serve_flops(conf: dict, prompt_lens, output_lens) -> float:
    """FLOPs of serving requests: each prompt's prefill (its last token
    through the head) and the decode steps after its first token."""
    head = 2 * _dims(conf)["head"]
    total = 0.0
    for p, n in zip(np.asarray(prompt_lens), np.asarray(output_lens)):
        total += token_flops(conf, np.arange(1, p + 1)).sum() + head
        if n > 1:
            total += (token_flops(conf, p + np.arange(1, n)).sum()
                      + head * (n - 1))
    return float(total)


def decode_bytes(conf: dict, steps: int, slots: int, prompt_lens,
                 output_lens) -> float:
    """Logical bytes of ``steps`` decode steps over ``slots`` slots that
    served these requests (each decodes ``n - 1`` tokens after its first)."""
    m = _dims(conf)
    pb = PARAM_BYTES[conf["param_dtype"]]
    layers = conf["num_hidden_layers"]
    weights = pb * (layers * m["layer"] + m["head"] + conf["hidden_size"]
                    + slots * conf["hidden_size"])
    state = 2 * 4 * layers * slots * (m["ssm"] + m["conv"])
    attended = sum(float((p + np.arange(1, n)).sum())
                   for p, n in zip(np.asarray(prompt_lens),
                                   np.asarray(output_lens)) if n > 1)
    kv = 4 * layers * m["kv"] * attended
    return float(steps * (weights + state) + kv)
