"""Fault-tolerant checkpointing: atomic manifests, auto-resume, lossy mode.

Layout per step:  <dir>/step_<n>/arrays.npz + manifest.json, committed by an
atomic rename of the temp directory; a top-level LATEST file is rewritten
last.  Restart scans LATEST (falling back to the newest complete manifest),
so a crash mid-write can never be resumed from a torn checkpoint.

Checkpoints are *logically indexed* (flattened path -> full unsharded array),
so a restart may use a different mesh shape (elastic scaling): the runtime
re-shards on load.

The manifest's ``extra`` dict carries the data-pipeline state alongside the
model: the train loop stores ``extra["loader"] = {epoch, step_in_epoch,
seed}`` (see repro.data.loader.ShardedLoader.state) so a resumed run
restores the loader to the exact batch position, not just the parameters --
the exact-resume guarantee documented in train/loop.py.  Params and
optimizer float32 tensors round-trip bit-exactly through the npz payload
unless a codec is set.

Lossy mode routes large float tensors through any registered Codec via the
tree-codec seam (compression/api.py): the manifest records the full codec
spec plus per-tree ``TreeCodecMeta`` (leaf shapes, dtypes, which leaves
compressed), and ``restore_checkpoint`` reconstructs through ``decode_tree``
-- no reshape math lives here.  ``lossy_bits`` remains as shorthand for the
fixed-rate codec.  The safety criterion mirrors Algorithm 1: the induced
parameter perturbation must stay below the optimizer's own per-step
displacement -- :func:`certify_param_tolerances` runs that search on the
parameter tensors themselves, yielding per-leaf certified tolerances for a
fixed-accuracy codec ("resume within certified tolerance").
"""
from __future__ import annotations

import json
import os
import shutil
import time
from typing import Any, Dict, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression import (
    Codec,
    TreeCodecMeta,
    codec_from_spec,
    codec_spec,
    decode_tree,
    encode_tree,
    get_codec,
    tree_nbytes,
)

# leaves smaller than this stay raw: header overhead beats the ratio there
MIN_LOSSY_SIZE = 4096


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _resolve_codec(codec, lossy_bits) -> Optional[Codec]:
    if codec is not None and lossy_bits is not None:
        raise ValueError("pass codec= or lossy_bits=, not both")
    if lossy_bits is not None:
        return get_codec("fixed_rate", bits_per_value=int(lossy_bits))
    return codec


def certify_param_tolerances(params_prev, params, *, multiple: float = 1.0,
                             min_size: int = MIN_LOSSY_SIZE,
                             d: int = 2) -> Dict[str, float]:
    """Per-leaf certified checkpoint tolerances via Algorithm 1 on parameters.

    The paper's argument, one level down: a restored parameter may deviate by
    up to the optimizer's own per-step displacement without leaving the
    trajectory's noise floor.  For each large float leaf we take ``e =
    multiple * mean|params - params_prev|`` (the realized displacement of
    the step that produced this checkpoint) and run the same doubling/halving
    search used for training data to find the largest L-inf tolerance whose
    realized L1 error stays under ``e``.

    Returns ``{leaf_key: tolerance}`` keyed as in
    :func:`repro.compression.tree_leaf_keys`, ready to pass as
    ``save_checkpoint(..., tolerances={"params": ...})``.  Leaves smaller
    than ``min_size`` are skipped (they are stored raw anyway).
    """
    from repro.core.tolerance import find_tolerance

    flat_prev = _flatten(params_prev)
    tols: Dict[str, float] = {}
    for key, arr in _flatten(params).items():
        if not (np.issubdtype(arr.dtype, np.floating) and arr.size >= min_size):
            continue
        e = float(multiple) * float(np.mean(np.abs(
            arr.astype(np.float64) - flat_prev[key].astype(np.float64))))
        if e <= 0.0:
            continue
        res = find_tolerance(arr.astype(np.float32), e, d=d)
        if np.isfinite(res.compression_l1):
            tols[key] = res.tolerance
    return tols


def save_checkpoint(ckpt_dir: str, step: int, state: Dict[str, Any],
                    extra: Optional[dict] = None,
                    lossy_bits: Optional[int] = None,
                    codec: Optional[Codec] = None,
                    tolerances: Union[None, float, Mapping[str, Any]] = None,
                    keep: int = 3) -> str:
    """state: dict of pytrees (e.g. {"params": ..., "opt": ..., "data": ...}).

    codec: any registered Codec; large float leaves route through it via
    ``encode_tree`` and the manifest records the spec + per-tree meta.
    lossy_bits: shorthand for the fixed-rate codec (mutually exclusive).
    tolerances: forwarded per state entry to ``encode_tree`` -- a scalar for
    every leaf, or ``{name: scalar-or-{leaf_key: tol}}`` (e.g. the output of
    :func:`certify_param_tolerances` under ``"params"``).  Recorded in the
    manifest as tolerance provenance.
    """
    codec = _resolve_codec(codec, lossy_bits)
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:010d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)

    arrays: Dict[str, np.ndarray] = {}
    meta: Dict[str, Any] = {"step": step, "time": time.time(),
                            "lossy_bits": lossy_bits, "extra": extra or {}}
    raw_bytes = stored_bytes = 0
    if codec is None:
        for name, tree in state.items():
            for key, arr in _flatten(tree).items():
                arrays[f"{name}/{key}"] = arr
                raw_bytes += arr.nbytes
        stored_bytes = raw_bytes
    else:
        meta["codec"] = {"spec": codec_spec(codec), "trees": {}}
        if tolerances is not None and not isinstance(tolerances, Mapping):
            meta["codec"]["tolerance"] = float(tolerances)
        for name, tree in state.items():
            tols = (tolerances.get(name)
                    if isinstance(tolerances, Mapping) else tolerances)
            enc, tmeta = encode_tree(codec, tree, min_size=MIN_LOSSY_SIZE,
                                     tolerances=tols)
            meta["codec"]["trees"][name] = tmeta.to_json()
            if isinstance(tols, Mapping):
                meta["codec"].setdefault("tolerances", {})[name] = {
                    k: float(v) for k, v in tols.items()}
            for e, spec in zip(enc, tmeta.leaves):
                full = f"{name}/{spec.key}"
                if spec.compressed:
                    for aname, a in codec.field_to_arrays(e).items():
                        arrays[f"{full}.zfp/{aname}"] = a
                else:
                    arrays[full] = np.asarray(e)
            r, s = tree_nbytes(codec, enc, tmeta)
            raw_bytes += r
            stored_bytes += s
    meta["raw_bytes"] = raw_bytes
    meta["stored_bytes"] = stored_bytes
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(meta, f)
    if os.path.exists(final):                    # re-save after restart
        shutil.rmtree(final)
    os.replace(tmp, final)                       # atomic commit
    with open(os.path.join(ckpt_dir, "LATEST.tmp"), "w") as f:
        f.write(os.path.basename(final))
    os.replace(os.path.join(ckpt_dir, "LATEST.tmp"),
               os.path.join(ckpt_dir, "LATEST"))
    _gc(ckpt_dir, keep)
    return final


def _is_checkpoint_dir(ckpt_dir: str, d: str) -> bool:
    # a leftover step_*.tmp from a crashed save is NOT a checkpoint: it must
    # neither count toward `keep` nor be offered for resume
    return (d.startswith("step_") and not d.endswith(".tmp")
            and os.path.isdir(os.path.join(ckpt_dir, d)))


def _gc(ckpt_dir: str, keep: int):
    steps = sorted(d for d in os.listdir(ckpt_dir)
                   if _is_checkpoint_dir(ckpt_dir, d))
    for d in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)


def latest_checkpoint(ckpt_dir: str) -> Optional[str]:
    if not os.path.isdir(ckpt_dir):
        return None
    latest = os.path.join(ckpt_dir, "LATEST")
    if os.path.exists(latest):
        cand = os.path.join(ckpt_dir, open(latest).read().strip())
        if os.path.exists(os.path.join(cand, "manifest.json")):
            return cand
    steps = sorted(d for d in os.listdir(ckpt_dir)
                   if _is_checkpoint_dir(ckpt_dir, d))
    for d in reversed(steps):                    # newest complete manifest
        cand = os.path.join(ckpt_dir, d)
        if os.path.exists(os.path.join(cand, "manifest.json")):
            return cand
    return None


def restore_checkpoint(path: str,
                       template: Dict[str, Any]) -> Tuple[Dict[str, Any], dict]:
    """Restore into the structure of ``template`` (same pytree defs).

    Lossy checkpoints decode through the codec recorded in the manifest.
    """
    with open(os.path.join(path, "manifest.json")) as f:
        meta = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))
    codec_meta = meta.get("codec")
    codec = None
    tree_metas: Dict[str, TreeCodecMeta] = {}
    if codec_meta is not None:
        codec = codec_from_spec(codec_meta["spec"])
        tree_metas = {name: TreeCodecMeta.from_json(tm)
                      for name, tm in codec_meta["trees"].items()}
    out = {}
    for name, tree in template.items():
        restored: Dict[str, np.ndarray] = {}
        if name in tree_metas:
            tmeta = tree_metas[name]
            enc = []
            for spec in tmeta.leaves:
                full = f"{name}/{spec.key}"
                if spec.compressed:
                    prefix = full + ".zfp/"
                    enc.append(codec.field_from_arrays(
                        {k[len(prefix):]: data[k] for k in data.files
                         if k.startswith(prefix)}, spec.shape2d))
                else:
                    enc.append(data[full])
            decoded = decode_tree(enc, tmeta, codec=codec)
            restored = {spec.key: np.asarray(x)
                        for spec, x in zip(tmeta.leaves, decoded)}
        else:
            for key in _flatten(tree):
                restored[key] = data[f"{name}/{key}"]
        leaves_paths = jax.tree_util.tree_flatten_with_path(tree)
        keys_in_order = ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                                  for p in path) for path, _ in leaves_paths[0]]
        new_leaves = [jnp.asarray(restored[k]) for k in keys_in_order]
        out[name] = jax.tree_util.tree_unflatten(leaves_paths[1], new_leaves)
    return out, meta
