"""Algorithm 1: model-centric compression error tolerance (paper §IV).

Given a model trained on lossless data, its own L1 prediction error ``e``
per sample upper-bounds the detail the model can learn (Threshold 2,
Fig. 4).  The search starts at ``t = 4^d * e / c(d)`` (ZFP expected-L1
calibration, c(2) ~= 1.089 from Fox & Lindstrom) and doubles the L-inf
tolerance while the realized L1 compression error stays at or below ``e``.
No retraining is ever performed.

Two entry points:
  find_tolerance        -- reference per-sample Python loop
  find_tolerance_batch  -- the whole doubling/halving search for a stack of
                           samples inside ONE jitted lax.while_loop with
                           per-sample active masks: building tolerances for
                           N samples is a single compiled dispatch, not
                           N x iters encode calls.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression import fa_precompute_batch, fa_stats_batch, get_codec
from repro.obs import trace as obs_trace

C_D = {1: 1.044, 2: 1.089, 3: 1.134, 4: 1.178}   # Fox & Lindstrom, Appendix A


@dataclasses.dataclass
class ToleranceResult:
    tolerance: float            # final L-inf tolerance
    model_l1: float             # e: model output L1 error (the bound)
    compression_l1: float       # realized L1 error at `tolerance`
    ratio: float                # realized compression ratio
    iterations: int


def find_tolerance(sample: np.ndarray, model_l1_error: float,
                   d: int = 2, max_iters: int = 8) -> ToleranceResult:
    """Algorithm 1 for one sample (any (..., H, W) float array).

    model_l1_error: mean-|.| prediction error of the lossless-trained model
    on this sample (same normalization as ``sample``).
    """
    e = float(model_l1_error)
    x = jnp.asarray(sample, jnp.float32)
    codec = get_codec("fixed_accuracy", backend="jnp")

    def roundtrip(t):
        cf = codec.encode_batch(x[None], jnp.asarray([t], jnp.float32))
        xd = codec.decode_batch(cf)[0]
        l1 = float(jnp.mean(jnp.abs(xd - x)))
        return l1, float(x.size * 4 / int(np.asarray(codec.nbytes(cf))[0]))

    t = (4.0 ** d) * e / C_D[d]
    best = None
    iters = 0
    while iters < max_iters:
        iters += 1
        l1, ratio = roundtrip(float(t))
        if l1 <= e:
            saturated = best is not None and ratio <= best.ratio * 1.01
            best = ToleranceResult(float(t), e, l1, ratio, iters)
            if saturated:       # all blocks at zero planes: ratio cannot grow
                break
            t *= 2.0
        else:
            break
    if best is None:        # initial guess already exceeded e: halve downward
        while iters < max_iters:
            iters += 1
            t /= 2.0
            l1, ratio = roundtrip(float(t))
            if l1 <= e:
                best = ToleranceResult(float(t), e, l1, ratio, iters)
                break
    if best is None:
        best = ToleranceResult(float(t), e, float("inf"), 1.0, iters)
    return best


def algorithm1_per_sample(samples: Sequence[np.ndarray],
                          model_l1_errors: Sequence[float],
                          d: int = 2) -> list[ToleranceResult]:
    """Per-sample adaptive tolerances for a dataset (paper Algorithm 1)."""
    return [find_tolerance(s, e, d=d)
            for s, e in zip(samples, model_l1_errors)]


# ---------------------------------------------------------------------------
# batched Algorithm 1: one jitted search for a whole stack of samples
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchToleranceResult:
    """Vectorized ToleranceResult: every field is an (N,) array."""
    tolerance: np.ndarray
    model_l1: np.ndarray
    compression_l1: np.ndarray
    ratio: np.ndarray
    iterations: np.ndarray

    def __len__(self) -> int:
        return len(self.tolerance)

    def as_results(self) -> list[ToleranceResult]:
        return [ToleranceResult(float(self.tolerance[i]),
                                float(self.model_l1[i]),
                                float(self.compression_l1[i]),
                                float(self.ratio[i]),
                                int(self.iterations[i]))
                for i in range(len(self))]


@partial(jax.jit, static_argnames=("d", "max_iters"))
def _search_batch(xs: jnp.ndarray, es: jnp.ndarray, d: int, max_iters: int):
    """Doubling/halving searches for all samples in one lax.while_loop.

    Per-sample masks replicate the reference control flow: double while the
    realized L1 stays under ``e`` (stopping when the ratio saturates), halve
    downward when the initial guess overshoots, freeze a sample the moment
    its search terminates.  Every iteration evaluates the whole stack with
    one batched encode/decode; finished samples are masked out of the state
    updates, so results match find_tolerance exactly.

    The loop body is stats-only: quantize / forward lift / negabinary are
    hoisted out of the while_loop once (``fa_precompute_batch``), and each
    iteration only re-derives per-block plane counts and the truncated
    decode (``fa_stats_batch``) — the loop needs nothing but per-sample L1
    and byte counts, and pack(MAX_WORDS)→unpack is an exact inverse, so the
    decisions are those of find_tolerance's full encode→decode roundtrip
    (tests assert so).
    """
    n = xs.shape[0]
    sample_size = int(np.prod(xs.shape[1:]))
    state = fa_precompute_batch(xs)

    def evaluate(t):
        l1, nbytes = fa_stats_batch(state, t)
        return l1, sample_size * 4.0 / nbytes

    init = {
        "t": (4.0 ** d) * es / C_D[d],
        "best_t": jnp.zeros((n,), jnp.float32),
        "best_l1": jnp.full((n,), jnp.inf, jnp.float32),
        "best_ratio": jnp.ones((n,), jnp.float32),
        "have_best": jnp.zeros((n,), bool),
        "going_down": jnp.zeros((n,), bool),
        "done": jnp.zeros((n,), bool),
        "iters": jnp.zeros((n,), jnp.int32),
    }

    def cond(s):
        return jnp.any(~s["done"])

    def body(s):
        active = ~s["done"]
        l1, ratio = evaluate(s["t"])
        iters = s["iters"] + active.astype(jnp.int32)
        ok = l1 <= es

        # success: record best; stop if ratio saturated (all blocks already
        # at zero planes) or if this was the halving phase's first success
        rec = active & ok
        saturated = s["have_best"] & (ratio <= s["best_ratio"] * 1.01)
        best_t = jnp.where(rec, s["t"], s["best_t"])
        best_l1 = jnp.where(rec, l1, s["best_l1"])
        best_ratio = jnp.where(rec, ratio, s["best_ratio"])
        have_best = s["have_best"] | rec
        stop_ok = rec & (saturated | s["going_down"])

        # failure: overshoot ends a doubling search; a fresh failure flips
        # the sample into the halving phase
        fail = active & ~ok
        stop_fail = fail & s["have_best"]
        go_down = fail & ~s["have_best"]

        done = s["done"] | stop_ok | stop_fail | (iters >= max_iters)
        t = jnp.where(rec & ~stop_ok, s["t"] * 2.0, s["t"])
        t = jnp.where(go_down, t * 0.5, t)
        # a sample that just terminated keeps its last *evaluated* tolerance
        # (the reference loop never advances t past its final encode; this
        # matters for the no-solution path, whose result reports final t)
        t = jnp.where(done, s["t"], t)
        return {"t": t, "best_t": best_t, "best_l1": best_l1,
                "best_ratio": best_ratio, "have_best": have_best,
                "going_down": s["going_down"] | go_down, "done": done,
                "iters": iters}

    s = jax.lax.while_loop(cond, body, init)
    tolerance = jnp.where(s["have_best"], s["best_t"], s["t"])
    l1 = jnp.where(s["have_best"], s["best_l1"], jnp.inf)
    ratio = jnp.where(s["have_best"], s["best_ratio"], 1.0)
    return tolerance, l1, ratio, s["iters"]


def find_tolerance_batch(samples: np.ndarray | Sequence[np.ndarray],
                         model_l1_errors: Sequence[float] | np.ndarray,
                         d: int = 2,
                         max_iters: int = 8) -> BatchToleranceResult:
    """Algorithm 1 for a stack of same-shape samples in one compiled call.

    Equivalent to ``[find_tolerance(s, e) for s, e in zip(...)]`` but the
    whole search runs device-side: one jitted lax.while_loop whose
    stats-only body evaluates every still-active sample (see
    ``_search_batch``).
    """
    xs = jnp.asarray(np.stack([np.asarray(s, np.float32) for s in samples])
                     if not isinstance(samples, (np.ndarray, jnp.ndarray))
                     else samples, jnp.float32)
    es = jnp.asarray(np.asarray(model_l1_errors, np.float32))
    assert xs.shape[0] == es.shape[0], "one model error per sample"
    with obs_trace.span("tolerance.search_batch", cat="certify",
                        samples=int(xs.shape[0])) as sp:
        found = _search_batch(xs, es, d, max_iters)
        # the host waits here for the whole search to finish on the device
        with obs_trace.span("tolerance.readback", cat="certify"):
            tol, l1, ratio, iters = (np.asarray(a) for a in found)
        sp.set(max_iterations=int(iters.max(initial=0)))
    return BatchToleranceResult(tol, np.asarray(es), l1, ratio, iters)
