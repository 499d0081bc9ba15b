import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax-importing module: jax locks the platform and the
# device count on first init.  512 placeholder host devices back the
# production meshes, and the dry run only compiles, so it never takes a chip.

import argparse
import json
import re
import time
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ALL_ARCHS, SHAPE_CELLS, ArchConfig, ShapeCell,
                           cell_applicable, get_config)
from repro.distributed.sharding import (batch_specs, cache_specs,
                                        make_shardings, opt_specs,
                                        param_specs, resolve_specs)
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models import lm
from repro.train.optimizer import AdamConfig, AdamState, adam_init, adam_update

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, cell: ShapeCell) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = cell.global_batch, cell.seq_len
    f32, i32 = jnp.float32, jnp.int32
    if cell.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((b,), i32),
                "pos": jax.ShapeDtypeStruct((), i32)}
    if cfg.encoder_layers:                       # enc-dec: split the budget
        half = s // 2
        return {"tokens": jax.ShapeDtypeStruct((b, half), i32),
                "labels": jax.ShapeDtypeStruct((b, half), i32),
                "encoder_embeds": jax.ShapeDtypeStruct((b, half, cfg.frontend_dim), f32)}
    out = {"tokens": jax.ShapeDtypeStruct((b, s - cfg.frontend_seq), i32),
           "labels": jax.ShapeDtypeStruct((b, s - cfg.frontend_seq), i32)}
    if cfg.frontend == "vision":
        out["frontend_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.frontend_seq, cfg.frontend_dim), f32)
    return out


def _abstract_state(cfg: ArchConfig):
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda p: adam_init(p, AdamConfig()), params)
    return params, opt


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, microbatches: int = 1):
    """Training step; ``microbatches > 1`` = gradient accumulation (scan over
    micro-slices of the global batch) -- divides live activation memory by k
    at identical collective volume (§Perf iteration)."""
    opt_cfg = AdamConfig(lr=1e-4, grad_clip=1.0)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(lm.lm_loss)(params, cfg, batch)
        else:
            k = microbatches

            def slice_batch(i):
                return jax.tree.map(
                    lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:])[i],
                    batch)

            def micro(acc, i):
                tot, g_acc = acc
                l, g = jax.value_and_grad(lm.lm_loss)(params, cfg,
                                                      slice_batch(i))
                g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                     g_acc, g)
                return (tot + l, g_acc), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.zeros(()), zeros), jnp.arange(k))
            loss = loss / k
            grads = jax.tree.map(lambda g: g / k, grads)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        params, opt_state = adam_update(grads, opt_state, params, opt_cfg)
        return params, opt_state, loss

    return train_step


def make_train_step_podcompressed(cfg: ArchConfig, mesh, pspecs,
                                  codec=12):
    """THE PAPER'S TECHNIQUE ON THE WIRE: error-bounded ZFP compression of
    the cross-pod gradient exchange (DESIGN.md §4.3).

    Per-pod gradients are computed under plain GSPMD by vmapping the loss
    over a pod-split batch with ``spmd_axis_name='pod'``: the model runs in
    ordinary auto-sharded code (no manual region around it -- XLA's SPMD
    partitioner cannot partition the layer/loss scans inside a partially
    manual subgroup), and because the grad outputs keep their leading pod
    dim, GSPMD only reduces within pods.  The cross-pod combine then runs in
    a small fully-manual shard_map over just the gradient trees: each device
    compresses its OWN grad shard through the tree-codec seam (blocks align
    with the shard, no resharding), exchanges only the encoded fields around
    the pod ring (collective-permute of int32 payload/emax/nplanes words
    ~ bits/32 of raw volume for fixed-rate), and every pod decodes every
    payload so parameters stay bit-identical across pods.  ``codec`` is any
    registered Codec or an int (fixed-rate bits); a fixed-accuracy codec
    makes the exchange error-bounded instead of rate-bounded.
    Error-feedback residual carry is available in repro.core.grad_compress
    for real training runs."""
    from jax.sharding import PartitionSpec as P
    from repro.compression import decode_tree, encode_tree
    from repro.core.grad_compress import as_codec
    codec = as_codec(codec)
    opt_cfg = AdamConfig(lr=1e-4, grad_clip=1.0)
    n_pod = int(mesh.shape["pod"])
    perm = [(i, (i + 1) % n_pod) for i in range(n_pod)]
    pod_specs = jax.tree.map(lambda s: P("pod", *s), pspecs,
                             is_leaf=lambda x: isinstance(x, P))

    def exchange(grads_pods):
        # fully-manual over the whole mesh: leaves are this device's own
        # pod's grad shard with the vmap dim reduced to size 1
        gf = jax.tree.map(lambda g: jnp.squeeze(g, 0).astype(jnp.float32),
                          grads_pods)
        treedef = jax.tree_util.tree_structure(gf)
        enc, meta = encode_tree(codec, gf)
        acc = decode_tree(enc, meta, codec=codec)
        for _ in range(n_pod - 1):
            # everything the decode needs crosses the wire: CompressedField
            # is a pytree, so one tree.map ppermutes payload/emax/nplanes
            # (and any raw leaves the codec skipped) -- shape metadata is
            # static, zero bytes
            enc = jax.tree.map(lambda x: jax.lax.ppermute(x, "pod", perm),
                               enc)
            dec = decode_tree(enc, meta, codec=codec)
            acc = [a + d for a, d in zip(acc, dec)]
        mean = jax.tree_util.tree_unflatten(treedef,
                                            [a / n_pod for a in acc])
        # out_specs omit 'pod': every pod decoded the same payloads, so the
        # mean is pod-replicated by construction (check_vma off)
        return jax.tree.map(lambda m, g: m.astype(g.dtype),
                            mean, jax.tree.map(lambda g: g[0], grads_pods))

    def train_step(params, opt_state, batch):
        lm.set_constraint_exclude(("pod",))   # vmap's spmd_axis_name owns it
        try:
            batch_pods = jax.tree.map(
                lambda x: x.reshape(n_pod, x.shape[0] // n_pod,
                                    *x.shape[1:]), batch)
            losses, grads = jax.vmap(
                lambda b: jax.value_and_grad(lm.lm_loss)(params, cfg, b),
                spmd_axis_name="pod")(batch_pods)
            grads = jax.shard_map(exchange, mesh=mesh,
                                  in_specs=(pod_specs,), out_specs=pspecs,
                                  check_vma=False)(grads)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            params, opt_state = adam_update(grads, opt_state, params, opt_cfg)
            return params, opt_state, jnp.mean(losses)
        finally:
            lm.set_constraint_exclude(())

    return train_step


def make_prefill_step(cfg: ArchConfig, max_seq: int):
    def prefill(params, batch):
        return lm.lm_prefill(params, cfg, batch, max_seq)
    return prefill


def make_serve_step(cfg: ArchConfig):
    def serve(params, cache, tokens, pos):
        return lm.serve_step(params, cfg, cache, tokens, pos)
    return serve


# ---------------------------------------------------------------------------
# analytic per-device HBM-traffic model (documented in EXPERIMENTS.md §Roofline)
# ---------------------------------------------------------------------------

def analytic_memory_traffic(cfg: ArchConfig, cell: ShapeCell,
                            n_chips: int, n_model: int = 16) -> float:
    """Napkin HBM bytes/device/step.  XLA cost_analysis undercounts loop
    bodies and fusion effects both ways; this model counts the physically
    unavoidable traffic: TP-sharded weight reads per pass, optimizer state
    r/w, residual-stream + FFN activations, per-chunk KV rereads, cache
    reads for decode, and vocab logits."""
    n_dp = n_chips // n_model
    p_total = lm.param_count(cfg)
    p_active = lm.active_param_count(cfg)
    d, f, l = cfg.d_model, max(cfg.d_ff, 1), cfg.num_layers
    hkv, hd = max(cfg.num_kv_heads, 1), max(cfg.hdim, 1)
    s = cell.seq_len
    b_loc = max(cell.global_batch // n_dp, 1)
    v = cfg.vocab_size

    if cfg.num_experts:
        f_act = 3 * cfg.experts_per_token * cfg.d_ff + cfg.moe_dense_ff
    else:
        f_act = 2 * f
    act_layer_bytes = 6 * d + f_act                       # per token, bf16=2B
    nc = max(s // cfg.attn_chunk, 1)
    kv_reread = 0.0
    if cfg.family != "ssm":
        kv_reread = l * b_loc * nc * s * hkv * hd * 2 * 2  # k+v per q-chunk

    cache_bytes = 0.0
    if cell.kind != "train" and cfg.family != "ssm":
        cache_bytes = l * cell.global_batch * s * hkv * hd * 2 * 2 / n_chips
    if cfg.family == "ssm" or cfg.hybrid:
        cache_bytes += (l * cell.global_batch * cfg.ssm_heads * cfg.ssm_head_dim
                        * cfg.ssm_state * 4) / n_chips

    if cell.kind == "train":
        weights = 4 * p_total * 2 / n_model                # fwd/dgrad/wgrad/remat
        opt = p_total * 20 / n_chips                       # f32 m,v r/w + p
        acts = l * b_loc * s * act_layer_bytes * 2 * 3     # fwd+bwd+remat
        vocab = 2 * b_loc * s * (v / n_model) * 4          # logits chunks f32
        return weights + opt + acts + kv_reread + vocab
    if cell.kind == "prefill":
        weights = p_total * 2 / n_model
        acts = l * b_loc * s * act_layer_bytes * 2
        return weights + acts + kv_reread + cache_bytes    # cache write
    # decode: every weight (active) + the whole cache, once per token
    weights = p_active * 2 / n_model
    return weights + cache_bytes


# ---------------------------------------------------------------------------
# per-cell dry run
# ---------------------------------------------------------------------------

def run_cell(arch: str, cell: ShapeCell, multi_pod: bool,
             save: bool = True, cfg_override=None,
             microbatches: int = 1,
             pod_grad_compress_bits: int = 0) -> Dict[str, Any]:
    cfg = cfg_override or get_config(arch)
    ok, reason = cell_applicable(cfg, cell)
    label = f"{arch} x {cell.name} x {'2x16x16' if multi_pod else '16x16'}"
    if not ok:
        print(f"[dryrun] SKIP {label}: {reason}")
        return {"arch": arch, "cell": cell.name, "multi_pod": multi_pod,
                "skipped": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    params_s, opt_s = _abstract_state(cfg)
    pspecs = resolve_specs(param_specs(params_s), params_s, mesh)
    psh = make_shardings(mesh, pspecs)
    lm.set_constraint_mesh(mesh)
    t0 = time.time()

    with mesh:
        if cell.kind == "train":
            if pod_grad_compress_bits and multi_pod:
                step = make_train_step_podcompressed(
                    cfg, mesh, pspecs, pod_grad_compress_bits)
            else:
                step = make_train_step(cfg, microbatches)
            ispec = input_specs(cfg, cell)
            bspecs = {k: v for k, v in
                      batch_specs(cfg, cell.kind, multi_pod).items()
                      if k in ispec}
            bsh = make_shardings(mesh, bspecs, ispec)
            osh = make_shardings(mesh, opt_specs(pspecs))
            fn = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None),
                         donate_argnums=(0, 1))
            lowered = fn.lower(params_s, opt_s, ispec)
        elif cell.kind == "prefill":
            ispec = input_specs(cfg, cell)
            step = make_prefill_step(cfg, cell.seq_len if not cfg.encoder_layers
                                     else cell.seq_len // 2)
            bspecs = {k: v for k, v in
                      batch_specs(cfg, cell.kind, multi_pod).items()
                      if k in ispec}
            bsh = make_shardings(mesh, bspecs, ispec)
            cache_s = jax.eval_shape(
                lambda: lm.init_cache(cfg, cell.global_batch,
                                      cell.seq_len if not cfg.encoder_layers
                                      else cell.seq_len // 2,
                                      enc_seq=cell.seq_len // 2
                                      if cfg.encoder_layers else 0))
            csh = make_shardings(mesh,
                                 cache_specs(cfg, cell.global_batch, multi_pod),
                                 cache_s)
            fn = jax.jit(step, in_shardings=(psh, bsh),
                         out_shardings=(None, csh))
            lowered = fn.lower(params_s, ispec)
        else:                                          # decode
            step = make_serve_step(cfg)
            ispec = input_specs(cfg, cell)
            cache_s = jax.eval_shape(
                lambda: lm.init_cache(cfg, cell.global_batch, cell.seq_len,
                                      enc_seq=cell.seq_len // 2
                                      if cfg.encoder_layers else 0))
            csh = make_shardings(mesh,
                                 cache_specs(cfg, cell.global_batch, multi_pod),
                                 cache_s)
            dp = (("pod", "data") if multi_pod else ("data",))
            from jax.sharding import NamedSharding, PartitionSpec as P
            n_dp = 32 if multi_pod else 16
            tok_sh = NamedSharding(mesh, P(dp) if cell.global_batch % n_dp == 0
                                   else P())
            fn = jax.jit(step, in_shardings=(psh, csh, tok_sh, None),
                         out_shardings=(None, csh), donate_argnums=(1,))
            lowered = fn.lower(params_s, cache_s, ispec["tokens"], ispec["pos"])

        compiled = lowered.compile()

    lm.set_constraint_mesh(None)
    compile_s = time.time() - t0
    cost = compiled.cost_analysis() or {}
    try:
        memory = compiled.memory_analysis()
        mem = {k: int(getattr(memory, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")
               if hasattr(memory, k)}
    except Exception as e:                             # CPU backend gaps
        mem = {"error": str(e)}

    from repro.launch.hlo_analysis import analyze
    parsed = analyze(compiled.as_text())
    flops_dev = float(parsed["flops"])
    # TPU-dtype-corrected collective bytes (XLA:CPU promotes bf16 reductions
    # to f32; TPU reduces in bf16 -- §Perf methodology, EXPERIMENTS.md)
    coll_dev = float(parsed["collective_bytes_tpu"])
    bytes_dev = float(analytic_memory_traffic(cfg, cell, n_chips))
    result = {
        "arch": arch, "cell": cell.name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "multi_pod": multi_pod, "n_chips": n_chips,
        "pod_grad_compress_bits": (pod_grad_compress_bits
                                   if cell.kind == "train" else 0),
        "compile_seconds": round(compile_s, 1),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collective_bytes_uncorrected": float(parsed["collective_bytes"]),
        "collectives": {k: float(v) for k, v in parsed["collectives"].items()},
        "xla_cost_analysis": {"flops_unscaled": float(cost.get("flops", 0.0)),
                              "bytes_unscaled": float(cost.get("bytes accessed", 0.0))},
        "memory_analysis": mem,
        "terms": {
            "compute_s": flops_dev / PEAK_FLOPS_BF16,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_dev / ICI_BW,
        },
    }
    result["bottleneck"] = max(result["terms"], key=result["terms"].get)

    n_params = lm.param_count(cfg)
    n_active = lm.active_param_count(cfg)
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        model_flops = 6 * n_active * tokens
    elif cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        model_flops = 2 * n_active * tokens
    else:
        tokens = cell.global_batch
        model_flops = 2 * n_active * tokens
    hlo_global = flops_dev * n_chips
    result.update(model_flops=model_flops, params=n_params,
                  active_params=n_active,
                  useful_flops_ratio=model_flops / hlo_global if hlo_global else 0.0)

    print(f"[dryrun] OK {label}: compile={compile_s:.0f}s "
          f"compute={result['terms']['compute_s']:.4f}s "
          f"memory={result['terms']['memory_s']:.4f}s "
          f"collective={result['terms']['collective_s']:.4f}s "
          f"bottleneck={result['bottleneck']} "
          f"useful={result['useful_flops_ratio']:.2f}")
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        gc_tag = (f"_gc{pod_grad_compress_bits}"
                  if result["pod_grad_compress_bits"] else "")
        tag = f"{arch}_{cell.name}_{result['mesh']}{gc_tag}.json"
        with open(os.path.join(RESULTS_DIR, tag), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--cell", default="all",
                    help=f"one of {[c.name for c in SHAPE_CELLS]} or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--grad-compress-bits", type=int, default=0,
                    help="compress the cross-pod gradient exchange at this "
                         "fixed rate (train cells on the multi-pod mesh; "
                         "results save with a _gc<bits> suffix)")
    args = ap.parse_args()

    archs = list(ALL_ARCHS) if args.arch == "all" else [args.arch]
    cells = [c for c in SHAPE_CELLS if args.cell in ("all", c.name)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for cell in cells:
            for mp in meshes:
                try:
                    run_cell(arch, cell, mp,
                             pod_grad_compress_bits=args.grad_compress_bits)
                except Exception as e:
                    failures.append((arch, cell.name, mp, str(e)[:200]))
                    print(f"[dryrun] FAIL {arch} x {cell.name} x mp={mp}: {e}")
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {failures}")
    print("[dryrun] all requested cells passed")


if __name__ == "__main__":
    main()
