"""``correct`` fails the control: the plain reference put in the program's
place and computed a precision lower (bfloat16 for the float32 that the
configurations state), at a size a test run holds, on the CPU."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.tests import tiny


def _ctx(cell, seed=2 ** 31 + 99):
    return harness.Context(cell, seed, 0.0, False,
                           peaks=harness.peaks_for("TPU v5 lite"))


def test_train_control_fails_a_limit(tmp_path):
    from bench.loops import train as drv
    cell = tiny.tiny_cell("train", tmp_path)
    state = cell.loop.setup(_ctx(cell))
    payload, emax, nplanes, cond = state.ref_rows
    targets = drv.reference_targets(payload, emax, nplanes,
                                    state.sample_shape)
    b = state.batch
    batches = [(cond[s * b:(s + 1) * b], targets[s * b:(s + 1) * b])
               for s in range(drv.REF_STEPS)]
    ref = drv.reference_readings(state.params0, batches, state.model,
                                 state.lr)
    sound = dict(drv.compare(state.prog, ref))
    assert all(v <= drv.LIMITS[k] for k, v in sound.items()), sound
    low = drv.reference_readings(state.params0, batches, state.model,
                                 state.lr, dtype=jnp.bfloat16)
    control = dict(drv.compare(low, ref))
    assert any(v > drv.LIMITS[k] for k, v in control.items()), control

def test_certify_control_fails_a_limit(tmp_path):
    from bench.loops import certify as drv
    cell = tiny.tiny_cell("certify", tmp_path)
    state = cell.loop.setup(_ctx(cell))
    xs = drv.reference_input(state.pool[0], state.mean, state.std)
    ref = drv.reference_encode(xs, state.model_l1)
    low = xs.astype(jnp.bfloat16).astype(jnp.float32)
    res = drv.compare(xs, state.model_l1,
                      drv.reference_encode(low, state.model_l1), ref)
    assert np.mean(res["differs"]) > drv.LIMITS["mismatch_share"]

def test_serve_control_fails_a_limit(tmp_path):
    from bench import trace_reduce
    from bench.loops import serve as drv
    cell = tiny.tiny_cell("serve", tmp_path)
    state = cell.loop.setup(_ctx(cell))
    state.window(0.5, trace_reduce.mark)
    assert all(v <= lim for _, v, lim in state.check())
    q = max(state.checked, key=lambda q: q.steps)
    conds = jnp.asarray(np.concatenate(
        [np.repeat(q.params_vec[None], q.steps, 0),
         np.asarray(q.times)[:, None]], axis=1).astype(np.float32))
    ref = drv.reference_band(state.members, conds, state.model, state.sigmas)
    low = drv.reference_band(state.members, conds, state.model, state.sigmas,
                             dtype=jnp.bfloat16)
    control = drv.reduce_gaps(
        {k: [v] for k, v in drv.entry_gaps(*low, *ref).items()})
    assert any(v > drv.LIMITS[k] for k, v in control.items()), control
