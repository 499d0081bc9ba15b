"""Codec-founded lossy checkpoints: manifest codec field, decode_tree
restore, jnp<->pallas backend parity, certified tolerances, and the
`.tmp`-directory GC/resume fix."""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.compression import get_codec
from repro.train import checkpoint as ckpt


@pytest.fixture
def state():
    rng = np.random.default_rng(0)
    params = {"dense": {"w": jnp.asarray(rng.normal(size=(64, 96)), jnp.float32),
                        "b": jnp.asarray(rng.normal(size=(96,)), jnp.float32)}}
    opt = {"m": jax.tree.map(lambda x: x * 0.01, params),
           "v": jax.tree.map(lambda x: x * 1e-4, params),
           "step": jnp.asarray(3, jnp.int32)}
    return {"params": params, "opt": opt}


def _max_err(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def test_lossless_still_bit_exact(state, tmp_path):
    p = ckpt.save_checkpoint(str(tmp_path), 1, state)
    out, meta = ckpt.restore_checkpoint(p, state)
    assert _max_err(out, state) == 0.0
    assert "codec" not in meta
    assert meta["stored_bytes"] == meta["raw_bytes"]


def test_lossy_bits_shorthand_records_codec_spec(state, tmp_path):
    p = ckpt.save_checkpoint(str(tmp_path), 1, state, lossy_bits=14)
    with open(os.path.join(p, "manifest.json")) as f:
        meta = json.load(f)
    assert meta["codec"]["spec"]["name"] == "fixed_rate"
    assert meta["codec"]["spec"]["params"]["bits_per_value"] == 14
    assert meta["stored_bytes"] < meta["raw_bytes"]
    out, _ = ckpt.restore_checkpoint(p, state)
    assert _max_err(out, state) < 1e-2
    # small/int leaves stayed raw and bit-exact
    assert bool(jnp.all(out["params"]["dense"]["b"]
                        == state["params"]["dense"]["b"]))
    assert int(out["opt"]["step"]) == 3


def test_codec_and_lossy_bits_mutually_exclusive(state, tmp_path):
    with pytest.raises(ValueError):
        ckpt.save_checkpoint(str(tmp_path), 1, state, lossy_bits=12,
                             codec=get_codec("fixed_rate", bits_per_value=12,
                                             backend="jnp"))


@pytest.mark.parametrize("save_backend", ["jnp", "pallas"])
def test_save_restore_parity_across_backends(state, tmp_path, save_backend):
    """A checkpoint restores through the codec its manifest records, and
    the result matches a jnp-encoded checkpoint's bit-for-bit (the pallas
    path runs the compiled oracle on CPU, held bit-identical to jnp)."""
    restored = {}
    for backend in sorted({"jnp", save_backend}):
        codec = get_codec("fixed_rate", bits_per_value=13, backend=backend)
        p = ckpt.save_checkpoint(str(tmp_path / backend), 1, state,
                                 codec=codec)
        restored[backend], meta = ckpt.restore_checkpoint(p, state)
        assert meta["codec"]["spec"]["backend"] == backend
    assert _max_err(restored["jnp"], restored[save_backend]) == 0.0
    assert _max_err(restored[save_backend], state) < 0.02


def test_certified_tolerance_restore_within_bound(state, tmp_path):
    rng = np.random.default_rng(1)
    params2 = jax.tree.map(
        lambda x: x + jnp.asarray(
            2e-3 * rng.standard_normal(x.shape), x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, state["params"])
    tols = ckpt.certify_param_tolerances(state["params"], params2,
                                         min_size=1024)
    assert "dense/w" in tols and tols["dense/w"] > 0
    codec = get_codec("fixed_accuracy", backend="jnp")
    st = {"params": params2, "opt": state["opt"]}
    p = ckpt.save_checkpoint(str(tmp_path), 2, st, codec=codec,
                             tolerances={"params": tols})
    out, meta = ckpt.restore_checkpoint(p, st)
    err = float(jnp.max(jnp.abs(out["params"]["dense"]["w"]
                                - params2["dense"]["w"])))
    assert err <= tols["dense/w"]
    # tolerance provenance is in the manifest
    assert meta["codec"]["tolerances"]["params"]["dense/w"] == pytest.approx(
        tols["dense/w"])
    # leaves without a certified tolerance stayed raw
    tmeta = meta["codec"]["trees"]["params"]
    flags = {l["key"]: l["compressed"] for l in tmeta["leaves"]}
    assert flags["dense/w"] and not flags["dense/b"]


def test_certify_skips_zero_displacement(state):
    tols = ckpt.certify_param_tolerances(state["params"], state["params"],
                                         min_size=1024)
    assert tols == {}                                  # no displacement: raw


def test_residual_codec_checkpoint(state, tmp_path):
    codec = get_codec("fixed_accuracy+residual", tolerance=1e-3,
                      backend="jnp")
    p = ckpt.save_checkpoint(str(tmp_path), 1, state, codec=codec)
    out, meta = ckpt.restore_checkpoint(p, state)
    assert meta["codec"]["spec"]["name"] == "fixed_accuracy+residual"
    err = float(jnp.max(jnp.abs(out["params"]["dense"]["w"]
                                - state["params"]["dense"]["w"])))
    assert err <= 2e-3 + 1e-6                          # corrector clip bound


# ---------------------------------------------------------------------------
# crashed-save leftovers (.tmp dirs)
# ---------------------------------------------------------------------------

def test_crashed_tmp_dir_not_resumed_and_not_counted(state, tmp_path):
    """Crash injection: a kill between manifest write and the atomic rename
    leaves step_*.tmp behind.  It must neither be offered for resume nor
    evict a real checkpoint from the keep window."""
    d = str(tmp_path)
    for step in (1, 2):
        ckpt.save_checkpoint(d, step, state, keep=2)
    # simulate a crashed save of step 3: complete tmp dir, no rename
    crash = os.path.join(d, "step_0000000003.tmp")
    os.makedirs(crash)
    with open(os.path.join(crash, "manifest.json"), "w") as f:
        json.dump({"step": 3}, f)
    np.savez(os.path.join(crash, "arrays.npz"))
    os.remove(os.path.join(d, "LATEST"))               # force the dir scan

    latest = ckpt.latest_checkpoint(d)
    assert latest is not None and latest.endswith("step_0000000002")

    # the next save's GC must keep BOTH real checkpoints (keep=2): the tmp
    # leftover used to count as the newest entry and evict step 2
    ckpt.save_checkpoint(d, 4, state, keep=2)
    kept = sorted(x for x in os.listdir(d)
                  if x.startswith("step_") and not x.endswith(".tmp"))
    assert kept == ["step_0000000002", "step_0000000004"]


def test_interrupted_save_is_replaced_on_retry(state, tmp_path):
    d = str(tmp_path)
    os.makedirs(os.path.join(d, "step_0000000001.tmp"))  # torn leftover
    p = ckpt.save_checkpoint(d, 1, state)
    assert os.path.basename(p) == "step_0000000001"
    out, _ = ckpt.restore_checkpoint(p, state)
    assert _max_err(out, state) == 0.0


# ---------------------------------------------------------------------------
# train-loop integration: certified lossy checkpointing end to end
# ---------------------------------------------------------------------------

def test_train_loop_certified_checkpoint_roundtrip(tmp_path):
    from repro.models.surrogate import SurrogateConfig
    from repro.train.loop import TrainConfig, train_surrogate

    rng = np.random.default_rng(0)
    n, h, w, f = 16, 8, 8, 4
    cond = rng.normal(size=(n, 3)).astype(np.float32)
    fields = rng.normal(size=(n, h, w, f)).astype(np.float32)
    mcfg = SurrogateConfig(height=h, width=w, fields=f, base_channels=4,
                           cond_dim=3)
    codec = get_codec("fixed_accuracy", backend="jnp")  # no default tol:
    tcfg = TrainConfig(epochs=2, batch_size=8, ckpt_dir=str(tmp_path),
                       ckpt_every_steps=2, log_every=1, prefetch=0,
                       ckpt_codec=codec)                # -> certified mode
    params, losses = train_surrogate(
        mcfg, tcfg, cond, lambda idx: jnp.asarray(fields[idx]),
        num_samples=n)
    latest = ckpt.latest_checkpoint(str(tmp_path))
    assert latest is not None
    with open(os.path.join(latest, "manifest.json")) as f_:
        meta = json.load(f_)
    assert meta["codec"]["spec"]["name"] == "fixed_accuracy"
    certified = meta["codec"].get("tolerances", {}).get("params", {})
    out, _ = ckpt.restore_checkpoint(latest, {"params": params})
    # every certified leaf restored within its recorded tolerance
    flat = ckpt._flatten(params)
    restored = ckpt._flatten(out["params"])
    assert certified                                    # something compressed
    for key, tol in certified.items():
        err = float(np.max(np.abs(restored[key] - flat[key])))
        assert err <= tol
