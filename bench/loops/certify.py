"""Certification cells: Algorithm 1 and the fixed-accuracy encode.

Set-up uploads the configuration's raw members to the device (the pool) and
fixes the normalisation.  The window takes members in an order drawn from
the seed, cycling through the pool, and for each runs the program's
certification stage: normalise (``FieldNormalizer``), channels first,
``find_tolerance_batch`` at the configuration's model error, and the
fixed-accuracy codec's Pallas encode at the tolerances found.  Set-up runs
the same stage once on a member outside the window, which compiles it.

A seeded reservoir keeps ``COMPARED`` of the window's members.  ``check``
runs the plain reference (``bench/reference/zfp_ref.py``) on each: its own
normalisation with the same statistics, its own Algorithm 1 and encode.  It
compares, per snapshot, the tolerance, emax, plane counts and payload, and
decodes the program's output with its own decoder to check the error bound
(``max |x - x'| <= tol``) and Algorithm 1's L1 bound (``mean |x - x'| <= e``)
that the configuration states.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import dataset, loadgen, prep, work
from bench.reference import zfp_ref

COMPARED = 2
ROWS = 4                  # snapshots per reference call


def reference_encode(xs, model_l1: float):
    """Reference Algorithm 1 and encode, ``ROWS`` snapshots at a time."""
    tols, pay, emax, npl = [], [], [], []
    for s in range(0, xs.shape[0], ROWS):
        x = xs[s:s + ROWS]
        t = zfp_ref.search(x, model_l1)
        p, e, n = zfp_ref.encode(x, jnp.asarray(t))
        tols.append(t)
        pay.append(np.asarray(p))
        emax.append(np.asarray(e))
        npl.append(np.asarray(n))
    return (np.concatenate(tols), np.concatenate(pay), np.concatenate(emax),
            np.concatenate(npl))


def compare(xs, model_l1: float, prog, ref) -> dict:
    """Per-snapshot agreement of the program's output ``prog`` (tolerances,
    payload, emax, nplanes as numpy) with the reference's ``ref``, and the
    bounds of the program's output decoded by the reference."""
    tol, pay, emax, npl = prog
    rtol, rpay, remax, rnpl = ref
    w = max(pay.shape[-1], rpay.shape[-1])

    def widen(p):
        return np.pad(p, ((0, 0), (0, 0), (0, w - p.shape[-1])))
    differs = ((tol != rtol)
               | np.any(emax != remax, axis=1) | np.any(npl != rnpl, axis=1)
               | np.any(widen(pay) != widen(rpay), axis=(1, 2)))
    shape = tuple(xs.shape[1:])
    linf, l1 = [], []
    for s in range(0, xs.shape[0], ROWS):
        dec = zfp_ref.decode(jnp.asarray(pay[s:s + ROWS]),
                             jnp.asarray(emax[s:s + ROWS]),
                             jnp.asarray(npl[s:s + ROWS]), shape)
        err = jnp.abs(dec - xs[s:s + ROWS])
        linf.append(np.asarray(jnp.max(err, axis=(1, 2, 3))))
        l1.append(np.asarray(jnp.mean(err, axis=(1, 2, 3))))
    linf, l1 = np.concatenate(linf), np.concatenate(l1)
    return {"differs": differs,
            "bound_ratio": float(np.max(linf / tol)),
            "l1_ratio": float(np.max(l1 / model_l1))}


def reference_input(member, mean, std):
    return jnp.transpose((member - jnp.asarray(mean)) / jnp.asarray(std),
                         (0, 3, 1, 2))


class CertifyCell:
    def __init__(self, ctx):
        from repro.compression import get_codec
        cfg = ctx.config
        self.model_l1 = float(cfg["model_l1"])
        self.rng = np.random.default_rng(ctx.seed)
        _, fields = dataset.load_ensemble(cfg)
        self.pool = [jax.device_put(np.asarray(f)) for f in fields]
        self.mean, self.std = prep.norm_stats(self.pool)
        self.norm = prep.normalizer(self.mean, self.std)
        self.codec = get_codec("fixed_accuracy")
        self.order = loadgen.member_order(len(self.pool), self.rng)
        self.sample_bytes = work.raw_bytes(
            (fields.shape[-1],) + tuple(fields.shape[2:4]))
        self.snapshots = int(fields.shape[1])
        jax.block_until_ready(self._certify(len(self.pool) - 1)[1])

    def _certify(self, m):
        return prep.certify_member(self.norm, self.pool[m], self.model_l1,
                                   self.codec)

    def window(self, seconds: float, mark) -> dict:
        kept, seen, last = [], 0, None
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            m = next(self.order)
            with mark("bench.certify_member"):
                tols, cf = self._certify(m)
            seen += 1
            if len(kept) < COMPARED:             # seeded reservoir sample
                kept.append((m, tols, cf))
            else:
                j = int(self.rng.integers(seen))
                if j < COMPARED:
                    kept[j] = (m, tols, cf)
            last = cf
        jax.block_until_ready(last)
        elapsed = time.perf_counter() - t0
        self.kept = kept
        samples = seen * self.snapshots
        out_bytes = np.mean([work.logical_bytes(np.asarray(cf.nplanes)).sum()
                             for _, _, cf in kept])
        return {"certify_samples_per_s": samples / elapsed,
                "counts": {"window_s": elapsed, "members": seen,
                           "samples": samples, "attempted": samples,
                           "failed": 0,
                           "encode_bytes_per_member":
                               float(self.snapshots * self.sample_bytes
                                     + out_bytes)}}

    def free(self):
        keep = {m for m, _, _ in self.kept}
        self.pool = [p if i in keep else None for i, p in enumerate(self.pool)]
        self.norm = self.codec = None

    def check(self):
        differs, bound, l1 = [], 0.0, 0.0
        for m, tols, cf in self.kept:
            xs = reference_input(self.pool[m], self.mean, self.std)
            prog = (tols, np.asarray(cf.payload), np.asarray(cf.emax),
                    np.asarray(cf.nplanes))
            res = compare(xs, self.model_l1, prog,
                          reference_encode(xs, self.model_l1))
            differs.append(res["differs"])
            bound = max(bound, res["bound_ratio"])
            l1 = max(l1, res["l1_ratio"])
        share = float(np.mean(np.concatenate(differs)))
        return [("mismatch_share", share, LIMITS["mismatch_share"]),
                ("bound_ratio", bound, LIMITS["bound_ratio"]),
                ("l1_ratio", l1, LIMITS["l1_ratio"])]


# ``bound_ratio`` and ``l1_ratio`` are the guarantees the configuration
# states (error bound, Algorithm 1's L1 bound); ``mismatch_share`` is an
# exact comparison (PERF.md).
LIMITS = {"mismatch_share": 0.0, "bound_ratio": 1.0, "l1_ratio": 1.0}


NEEDS_DATASET = True


def setup(ctx):
    return CertifyCell(ctx)
