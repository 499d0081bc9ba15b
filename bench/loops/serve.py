"""Serving cells: the surrogate fleet answering open-loop rollout queries.

Set-up makes ``members`` sets of weights from the seed in one jitted call,
stacks them behind ``SurrogateServeEngine`` with ``slots`` batch slots, and
serves one one-step query, which compiles the fleet step.  The window hands
``engine.run`` the ``rate * --seconds`` queries of an open-loop Poisson
stream at the traffic's fixed rate, and ends when the last of them has been
answered.  A query asks for the first ``T`` snapshots of a
simulation whose parameters are drawn in the RT ranges of
``sim/ensemble.sample_params``, ``T`` from the traffic's weighted mix.  Its
latency runs from its scheduled arrival to its last mean and band on the
host (the engine's own stamp), so queueing counts.

The engine keeps every answer it returns; at this grid one 51-step answer
is 0.5 GB, so the queries drop their fields on arrival, all but ``CHECKED``
drawn from the seed (the longest among them) that ``check`` compares with
the plain reference: each member's forward pass at the precision the
configuration states, their mean, and the band width ``2 sigmas std`` over
members, compared snapshot by snapshot and field by field through their
spatial means, and averaged over every checked snapshot and field.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import loadgen, work
from bench.reference import surrogate_ref as sref

CHECKED = 6
RT_RANGES = {"atwood": (0.25, 0.65), "amplitude": (0.01, 0.05),
             "mode": (1.0, 4.0), "log_diffusivity": (-3.9, -3.2)}


def conditions(n: int, rng: np.random.Generator) -> np.ndarray:
    """(n, 6) parameter vectors in the RT ranges, laid out as the solver's
    ``SimParams.as_vector`` (atwood, amplitude, mode, log10 diffusivity,
    PCHIP seed share 0, impulse 0)."""
    cols = [rng.uniform(*RT_RANGES[k], size=n) for k in
            ("atwood", "amplitude", "mode", "log_diffusivity")]
    return np.stack(cols + [np.zeros(n), np.zeros(n)], axis=1).astype(
        np.float32)


def make_queries(tr: dict, seconds: float, rng: np.random.Generator):
    """Arrival times, rollout lengths and parameter vectors of the
    ``rate * seconds`` queries of one window: the same gaps and lengths for
    every seed, in the seed's order (``bench/loadgen.py``)."""
    rate = float(tr["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    arrivals = np.cumsum(loadgen.poisson_gaps(n, rate, rng))
    lengths = loadgen.exact_mix(n, tr["lengths"], tr["weights"], rng)
    return arrivals, lengths, conditions(n, rng)


def reference_band(members, conds, model: dict, sigmas: float,
                   rows: int = 8, dtype=jnp.float32, rounded=None):
    """Member mean and band width of the plain reference over ``conds``:
    at the precision the configuration states (``rounded`` None: as the
    platform's default precision rounds, see
    ``bench/reference/surrogate_ref.py``), or in ``dtype`` throughout."""
    if rounded is None:
        rounded = dtype == jnp.float32 and sref.default_rounds()
    preds = []
    for m in range(jax.tree.leaves(members)[0].shape[0]):
        p = jax.tree.map(lambda a: a[m], members)
        out = [_forward(p, conds[s:s + rows], model["height"],
                        model["width"], dtype, rounded)
               for s in range(0, conds.shape[0], rows)]
        preds.append(jnp.concatenate(out))
    preds = jnp.stack(preds)
    return (np.asarray(jnp.mean(preds, 0)),
            np.asarray(2.0 * sigmas * jnp.std(preds, 0)))


def _forward(p, cond, height, width, dtype, rounded):
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        p = jax.tree.map(lambda a: a.astype(dtype), p)
        return sref.forward(p, cond.astype(dtype), height, width,
                            rounded).astype(jnp.float32)


_forward = jax.jit(_forward, static_argnames=("height", "width", "dtype",
                                              "rounded"))


def field_mean_gaps(a, ref) -> np.ndarray:
    """Gap between the spatial means of ``a`` and ``ref`` for each snapshot
    and field, against the root mean square of the reference field there.
    Arrays are (T, H, W, F); returns (T, F)."""
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    scale = np.sqrt(np.mean(np.square(ref), axis=(1, 2)))
    return np.abs(a.mean(axis=(1, 2)) - ref.mean(axis=(1, 2))) / scale


def entry_gaps(mean, width, ref_mean, ref_width) -> dict:
    """The served mean's and band width's gaps from the reference's, one
    per snapshot and field (``field_mean_gaps``)."""
    return {"mean_gap": field_mean_gaps(mean, ref_mean),
            "band_gap": field_mean_gaps(width, ref_width)}


def reduce_gaps(entries: dict) -> dict:
    """The numbers compared: the average gap of the served mean and of the
    band width over every checked snapshot and field."""
    return {k: float(np.mean(np.concatenate([np.ravel(e) for e in v])))
            for k, v in entries.items()}


class ServeCell:
    def __init__(self, ctx):
        from repro.models.surrogate import SurrogateConfig
        from repro.serving import SurrogateServeEngine
        from repro.serving.surrogate_engine import SurrogateQuery

        cfg, tr = ctx.config, ctx.traffic
        self.model = dict(cfg["model"])
        self.tr = tr
        self.sigmas = float(tr["sigmas"])
        self.nsnaps = int(cfg["dataset"]["nsnaps"])
        self.rng = np.random.default_rng(ctx.seed)
        keys = jax.random.split(
            jax.random.PRNGKey(int(self.rng.integers(2 ** 31))),
            int(tr["members"]))
        shape = tuple(self.model[k] for k in (
            "height", "width", "fields", "base_channels", "cond_dim"))
        self.members = jax.jit(jax.vmap(
            lambda k: sref.init_params(k, shape)))(keys)
        self.engine = SurrogateServeEngine(
            self.members, SurrogateConfig(**self.model),
            batch_slots=int(tr["slots"]), sigmas=self.sigmas)

        class Query(SurrogateQuery):
            """Keeps its served fields only when drawn for the check."""
            keep = False

            def __setattr__(self, name, value):
                if name in ("mean", "width") and not self.keep:
                    value = None
                object.__setattr__(self, name, value)

        self.Query = Query
        times = np.linspace(0.0, 1.0, self.nsnaps, dtype=np.float32)
        self.times = times
        warm = Query(params_vec=conditions(1, self.rng)[0], times=times[:1])
        self.engine.run([warm])

    def window(self, seconds: float, mark) -> dict:
        arrivals, lengths, params = make_queries(self.tr, seconds, self.rng)
        queries = [self.Query(params_vec=p, times=self.times[:t],
                              arrival=float(a))
                   for a, t, p in zip(arrivals, lengths, params)]
        longest = int(np.argmax(lengths))
        others = [i for i in range(len(queries)) if i != longest]
        picked = [longest] + list(self.rng.choice(
            others, size=min(CHECKED - 1, len(others)), replace=False))
        for i in picked:
            queries[i].keep = True
        t0 = time.perf_counter()
        with mark("bench.engine_run"):
            done = self.engine.run(queries)
        elapsed = time.perf_counter() - t0
        self.checked = [queries[i] for i in picked]
        lat = np.array([q.latency for q in done], np.float64)
        wait = np.array([q._seated - q.arrival for q in done], np.float64)
        evals = int(np.sum(lengths)) * int(self.tr["members"])
        return {"serve_latency_p95_ms": 1000.0 * float(np.percentile(lat, 95)),
                "counts": {"window_s": elapsed, "queries": len(done),
                           "attempted": len(queries),
                           "failed": len(queries) - len(done),
                           "queue_wait_p95_ms":
                               1000.0 * float(np.percentile(wait, 95)),
                           "member_evals": evals,
                           "forward_flops": work.surrogate_forward_flops(
                               self.model)}}

    def free(self):
        self.engine = None

    def check(self):
        pooled = {"mean_gap": [], "band_gap": []}
        for q in self.checked:
            conds = np.concatenate(
                [np.repeat(q.params_vec[None], q.steps, 0),
                 np.asarray(q.times)[:, None]], axis=1).astype(np.float32)
            ref_mean, ref_width = reference_band(
                self.members, jnp.asarray(conds), self.model, self.sigmas)
            for k, v in entry_gaps(q.mean, q.width, ref_mean,
                                   ref_width).items():
                pooled[k].append(v)
        return [(k, v, LIMITS[k]) for k, v in reduce_gaps(pooled).items()]


# Only whole-field means separate the control from sound runs: pointwise,
# and over squares down to 64 x 64, the program's bfloat16 operand rounding
# differs from the reference's by as much as the control does.  Over the
# ~450 checked snapshots and fields, the largest gap is the tail of that
# noise and separates the control by 2.5-3.8x only; the average separates
# it by 6.5x (band) and 9.6x (mean).  Limits set from the program's
# readings over 13 seeds and the control's over 4 (PERF.md, "How correct
# is decided").
LIMITS = {"mean_gap": 4.5e-5, "band_gap": 1.8e-5}


def setup(ctx):
    return ServeCell(ctx)
