"""Training cells: the fused gather -> decode -> loss/grad -> Adam step.

Set-up builds the configuration's compressed store through the code under
test (``bench/prep.py``), makes the weights from the seed, and builds one
object, the compiled step with its state.  It drives that object through its
first ``REF_STEPS`` steps with the window's own call and feed (the loader
``train_surrogate`` builds, the index upload, the jitted step), which also
compiles the step, and records what the reference is compared with: each
step's loss, the first gradient as Adam holds it after one step, and each
leaf's change after the last of them.  The window then goes on with the same
object: every step uploads a batch of indices and dispatches the step, and
the loss is read every ``loss_every`` steps, as the train loop does.

``check`` runs the plain reference (``bench/reference``) on the same weights
and batches: its own decode of the stored payload, the surrogate's forward
and backward pass at the precision the configuration states, and Adam.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import dataset, prep, work
from bench.reference import surrogate_ref as sref
from bench.reference import zfp_ref

REF_STEPS = 3
LEAF_FLOOR = 1e-3       # leaves with a smaller reference gradient (against
                        # the median leaf's) move by round-off alone


def leaf_norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree_util.tree_leaves(tree)])


def worst_gap(program: np.ndarray, reference: np.ndarray,
              keep: np.ndarray | None = None) -> float:
    """Largest gap between two per-leaf norms, against the reference leaf's
    norm or the median leaf's, whichever is larger."""
    if keep is None:
        keep = np.ones(reference.shape, bool)
    scale = np.maximum(reference, np.median(reference[keep]))
    return float(np.max(np.abs(program - reference)[keep] / scale[keep]))


def compare(prog: dict, ref: dict) -> list:
    """The numbers ``correct`` rests on, from the program's and the
    reference's readings of the first steps (see module docstring)."""
    loss_gap = float(np.max(np.abs(prog["losses"] - ref["losses"])
                            / np.abs(ref["losses"])))
    keep = ref["grad"] >= LEAF_FLOOR * np.median(ref["grad"])
    return [("loss_gap", loss_gap),
            ("grad_gap", worst_gap(prog["grad"], ref["grad"])),
            ("update_gap", worst_gap(prog["update"], ref["update"], keep))]


def reference_readings(params0, batches, model: dict, lr: float,
                       dtype=jnp.float32, rounded=None) -> dict:
    """Losses, first gradient and change after ``len(batches)`` steps of the
    plain reference, from ``params0`` over ``(cond, target)`` batches: at
    the precision the configuration states (``rounded`` None: as the
    platform's default precision rounds, see
    ``bench/reference/surrogate_ref.py``), or in ``dtype`` throughout."""
    if rounded is None:
        rounded = dtype == jnp.float32 and sref.default_rounds()
    p = params0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad = [], None
    for s, (cond, target) in enumerate(batches, start=1):
        loss, g = sref.loss_and_grad(p, cond, target, model["height"],
                                     model["width"], dtype=dtype,
                                     rounded=rounded)
        losses.append(float(loss))
        if grad is None:
            grad = leaf_norms(g)
        p, m, v = sref.adam(p, m, v, g, jnp.float32(s), lr)
    delta = jax.tree.map(jnp.subtract, p, params0)
    return {"losses": np.array(losses), "grad": grad,
            "update": leaf_norms(delta)}


def reference_targets(payload, emax, nplanes, shape, rows: int = 4):
    """Channels-last f32 targets from stored rows, by the reference decoder."""
    out = [zfp_ref.decode(payload[s:s + rows], emax[s:s + rows],
                          nplanes[s:s + rows], shape)
           for s in range(0, payload.shape[0], rows)]
    return jnp.transpose(jnp.concatenate(out), (0, 2, 3, 1))


class TrainCell:
    def __init__(self, ctx):
        from repro.data import channels_last
        from repro.models.surrogate import SurrogateConfig, make_conditions
        from repro.train import source
        from repro.train.optimizer import AdamConfig

        cfg, tr = ctx.config, ctx.traffic
        self.model = dict(cfg["model"])
        self.batch = int(tr["batch"])
        self.loss_every = int(tr["loss_every"])
        self.lr = float(tr["lr"])

        pvec, fields = dataset.load_ensemble(cfg)
        members = [jax.device_put(np.asarray(f)) for f in fields]
        mean, std = prep.norm_stats(members)
        self.store = prep.compressed_store(members, prep.normalizer(mean, std),
                                           float(cfg["model_l1"]))
        del members
        self.cond = make_conditions(pvec, fields.shape[1])
        self.sample_shape = self.store.shape
        self.decode_bytes = float(
            np.mean(work.logical_bytes(self.store.nplanes))
            + work.raw_bytes(self.sample_shape))

        self.mcfg = SurrogateConfig(**self.model)
        self.opt_cfg = AdamConfig(lr=self.lr)
        self.src = source.make_batch_source(self.store, self.cond,
                                            channels_last)
        self.step = source.make_fused_step(self.src, self.mcfg, self.opt_cfg)
        self.start(ctx.seed)

    def start(self, seed: int):
        """Weights, Adam state and batch order from ``seed``, then the first
        ``REF_STEPS`` steps (the set-up calls this once; the calibration
        tool once per seed)."""
        from repro.train import source
        from repro.train.optimizer import adam_init
        rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(int(rng.integers(2 ** 31)))
        self.params0 = sref.init_params(key, tuple(self.model[k] for k in (
            "height", "width", "fields", "base_channels", "cond_dim")))
        self.params = self.params0
        self.opt = adam_init(self.params0, self.opt_cfg)
        loader = source.make_loader(self.store, None, self.batch,
                                    int(rng.integers(2 ** 31)))
        self.batches = loader.iter_epochs(None)
        self.steps = 0
        self.bad_losses = 0
        self._first_steps()

    def _advance(self, mark=None):
        with mark("bench.index_upload"):
            idx = next(self.batches)
            item = self.src.fetch(idx)
        with mark("bench.step"):
            self.params, self.opt, loss = self.step(self.params, self.opt,
                                                    item)
        self.steps += 1
        if self.steps % self.loss_every == 0:
            with mark("bench.loss_read"):
                if not np.isfinite(float(loss)):
                    self.bad_losses += 1
        return idx, loss

    def _first_steps(self):
        from bench.trace_reduce import mark
        idxs, losses = [], []
        for s in range(REF_STEPS):
            idx, loss = self._advance(mark)
            idxs.append(np.asarray(idx))
            losses.append(loss)
            if s == 0:
                grad = jax.tree.map(lambda m: m / 0.1, self.opt.m)
                self.prog = {"grad": leaf_norms(grad)}
        delta = jax.tree.map(jnp.subtract, self.params, self.params0)
        self.prog.update(losses=np.array([float(x) for x in losses]),
                         update=leaf_norms(delta))
        rows = np.concatenate(idxs)
        self.ref_rows = (self.store.payload[rows], self.store.emax[rows],
                         self.store.nplanes[rows],
                         jnp.asarray(self.cond[rows]))
        jax.block_until_ready((self.params, self.ref_rows))

    def window(self, seconds: float, mark) -> dict:
        n0 = self.steps
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._advance(mark)
        jax.block_until_ready(self.params)
        elapsed = time.perf_counter() - t0
        steps = self.steps - n0
        samples = steps * self.batch
        return {"train_samples_per_s": samples / elapsed,
                "counts": {"window_s": elapsed, "steps": steps,
                           "samples": samples, "attempted": steps,
                           "failed": self.bad_losses,
                           "train_flops_per_sample":
                               work.surrogate_train_flops(self.model),
                           "decode_bytes_per_sample": self.decode_bytes}}

    def free(self):
        for name in ("store", "src", "step", "params", "opt", "batches"):
            setattr(self, name, None)

    def check(self):
        payload, emax, nplanes, cond = self.ref_rows
        targets = reference_targets(payload, emax, nplanes, self.sample_shape)
        b = self.batch
        batches = [(cond[s * b:(s + 1) * b], targets[s * b:(s + 1) * b])
                   for s in range(REF_STEPS)]
        ref = reference_readings(self.params0, batches, self.model, self.lr)
        limits = dict(LIMITS)
        return [(name, value, limits[name])
                for name, value in compare(self.prog, ref)]


# Limits, set from the program's readings over a dozen seeds, the control's
# and the faults' (PERF.md, "How correct is decided").
LIMITS = {"loss_gap": 4e-5, "grad_gap": 0.06, "update_gap": 0.08}


NEEDS_DATASET = True


def setup(ctx):
    return TrainCell(ctx)
