"""Faults planted in the LM serving program, to show that the serving cell's
``correct`` fails each of them.

Each fault wraps one function of ``repro.models.lm`` (install it with
``plant``, then clear JAX's caches so the engine's programs are traced
anew):

* ``ssm_state_dropped``: the prefill hands decode a zero SSM state, as if
  the recurrence's state were not carried from the prompt into the answer;
* ``groups_swapped``: Mamba-2 head h reads the B and C of the other group
  (the group order of in_proj's and the conv's B and C channels reversed);
* ``kv_position_off_by_one``: a decode step writes its key and value one
  position past its own, so it attends the slot's stale entry in its place.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp


def _ssm_state_dropped(lm):
    real = lm.lm_prefill

    def prefill(*a, **k):
        logits, cache = real(*a, **k)
        return logits, dict(cache, ssm=jnp.zeros_like(cache["ssm"]))
    return "lm_prefill", prefill


def _groups_swapped(lm):
    real = lm.ssm_block

    def block(lp, x, cfg, *a, **k):
        di = cfg.ssm_heads * cfg.ssm_head_dim
        g, n = cfg.ssm_groups, cfg.ssm_state
        # B then C, each g groups of n channels, from column `start` on
        flip = lambda start: (start + np.arange(2 * g * n).reshape(
            2, g, n)[:, ::-1]).ravel()
        cols_in = np.concatenate([np.arange(2 * di), flip(2 * di),
                                  np.arange(2 * di + 2 * g * n,
                                            lp["ssm_in"].shape[-1])])
        cols_conv = np.concatenate([np.arange(di), flip(di)])
        lp = dict(lp, ssm_in=lp["ssm_in"][..., cols_in],
                  ssm_conv_w=lp["ssm_conv_w"][..., cols_conv])
        if "ssm_conv_b" in lp:
            lp["ssm_conv_b"] = lp["ssm_conv_b"][..., cols_conv]
        return real(lp, x, cfg, *a, **k)
    return "ssm_block", block


def _kv_position_off_by_one(lm):
    real = lm.attn_block

    def block(lp, x, cfg, positions, *, kv_cache=None, cache_pos=None, **k):
        if kv_cache is not None and x.shape[1] == 1:
            cache_pos = cache_pos + 1
        return real(lp, x, cfg, positions, kv_cache=kv_cache,
                    cache_pos=cache_pos, **k)
    return "attn_block", block


FAULTS = {"ssm_state_dropped": _ssm_state_dropped,
          "groups_swapped": _groups_swapped,
          "kv_position_off_by_one": _kv_position_off_by_one}


def plant(name: str, setattr_=setattr):
    """Replace the function of ``repro.models.lm`` that fault ``name``
    breaks (``setattr_`` may be pytest's ``monkeypatch.setattr``).  Returns
    a callable that puts the real function back."""
    from repro.models import lm
    attr, fn = FAULTS[name](lm)
    real = getattr(lm, attr)
    setattr_(lm, attr, fn)
    return lambda: setattr(lm, attr, real)
