"""HLO analysis parser + sharding-rule unit tests (no 512-device meshes here:
the dry-run itself owns that; these tests validate the machinery on the
single real device)."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze, parse_module
from repro.distributed.sharding import resolve_specs, param_specs
from jax.sharding import Mesh, PartitionSpec as P


def test_parser_flops_exact_no_loop():
    m, k, n = 256, 512, 128
    comp = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32)).compile()
    res = analyze(comp.as_text())
    assert res["flops"] == pytest.approx(2 * m * k * n, rel=0.01)


def test_parser_scales_scan_loops():
    L, m, k = 12, 64, 64

    def f(ws, x):
        return jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), None), x, ws)[0]

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((L, k, k), jnp.float32),
        jax.ShapeDtypeStruct((m, k), jnp.float32)).compile()
    res = analyze(comp.as_text())
    assert res["flops"] == pytest.approx(L * 2 * m * k * k, rel=0.05)


def test_parser_nested_scan():
    L, inner, m, k = 6, 4, 32, 32

    def f(ws, x):
        def outer(h, w):
            h2 = jax.lax.scan(lambda hh, _: (jnp.tanh(hh @ w), None), h,
                              None, length=inner)[0]
            return h2, None
        return jax.lax.scan(outer, x, ws)[0]

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((L, k, k), jnp.float32),
        jax.ShapeDtypeStruct((m, k), jnp.float32)).compile()
    res = analyze(comp.as_text())
    assert res["flops"] == pytest.approx(L * inner * 2 * m * k * k, rel=0.05)


def test_parse_module_structure():
    comp = jax.jit(lambda x: jnp.sin(x) @ x.T).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    comps = parse_module(comp.as_text())
    assert any("main" in n for n in comps)
    ops = [i.opcode for c in comps.values() for i in c.instructions]
    assert "dot" in ops


# ---------------------------------------------------------------------------
# sharding divisibility resolution
# ---------------------------------------------------------------------------

def test_resolve_drops_nondividing_axes():
    # resolve_specs only reads axis names/sizes, so a fake suffices
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16))
    spec = {"w": P(None, "data", "model", None)}
    shapes = {"w": jax.ShapeDtypeStruct((24, 2048, 8, 128), jnp.float32)}
    out = resolve_specs(spec, shapes, FakeMesh())
    assert out["w"] == P(None, "data", None, None)   # 8 % 16 != 0 -> dropped


def test_param_specs_cover_all_leaves():
    from repro.configs import reduced_config
    from repro.models import lm
    cfg = reduced_config("qwen3-moe-30b-a3b")
    shapes = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    specs = param_specs(shapes)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat_p = jax.tree_util.tree_leaves(shapes)
    assert len(flat_s) == len(flat_p)


def test_input_specs_all_cells():
    from repro.configs import ALL_ARCHS, SHAPE_CELLS, get_config, cell_applicable
    from repro.launch.dryrun import input_specs
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        for cell in SHAPE_CELLS:
            if not cell_applicable(cfg, cell)[0]:
                continue
            spec = input_specs(cfg, cell)
            assert "tokens" in spec
            for v in spec.values():
                assert isinstance(v, jax.ShapeDtypeStruct)


def test_analytic_traffic_positive_all_cells():
    from repro.configs import ALL_ARCHS, SHAPE_CELLS, get_config, cell_applicable
    from repro.launch.dryrun import analytic_memory_traffic
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        for cell in SHAPE_CELLS:
            if not cell_applicable(cfg, cell)[0]:
                continue
            assert analytic_memory_traffic(cfg, cell, 256) > 0


# ---------------------------------------------------------------------------
# pod-compressed gradient exchange (subprocess: needs 8 host devices, and the
# device count must be locked before repro.launch.dryrun pins it to 512)
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_never_takes_the_chip():
    """The dry run pins itself to host devices even when the environment
    asks for the TPU, so a launcher's dry-run child cannot hold the chip."""
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH=os.path.join(_REPO, "src"),
               JAX_PLATFORMS="tpu")
    proc = subprocess.run(
        [sys.executable, "-c", "import repro.launch.dryrun, jax; "
         "print(jax.default_backend(), jax.device_count())"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.split() == ["cpu", "512"]

_POD_COMPRESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
assert jax.device_count() == 8            # lock before the dryrun import
from jax.sharding import Mesh, PartitionSpec as P
from repro.configs import ShapeCell, reduced_config
from repro.distributed.sharding import (batch_specs, make_shardings,
                                        opt_specs, param_specs, resolve_specs)
from repro.launch.dryrun import (_abstract_state, input_specs,
                                 make_train_step, make_train_step_podcompressed)
from repro.launch.hlo_analysis import analyze
from repro.models import lm
from repro.train.optimizer import AdamConfig, adam_init

cfg = reduced_config("internlm2-1.8b")
cell = ShapeCell("tiny_train", 16, 4, "train")
mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
            ("pod", "data", "model"))

params_s, opt_s = _abstract_state(cfg)
pspecs = resolve_specs(param_specs(params_s), params_s, mesh)
psh = make_shardings(mesh, pspecs)
ispec = input_specs(cfg, cell)
bspecs = {k: v for k, v in batch_specs(cfg, "train", True).items()
          if k in ispec}
bsh = make_shardings(mesh, bspecs, ispec)
osh = make_shardings(mesh, opt_specs(pspecs))
lm.set_constraint_mesh(mesh)


def compile_step(step):
    with mesh:
        fn = jax.jit(step, in_shardings=(psh, osh, bsh),
                     out_shardings=(psh, osh, None))
        return fn, fn.lower(params_s, opt_s, ispec).compile()


rng = np.random.default_rng(0)
params = lm.init_lm(jax.random.PRNGKey(0), cfg)
opt = adam_init(params, AdamConfig())
batch = {k: jnp.asarray(rng.integers(0, cfg.vocab_size, s.shape), jnp.int32)
         for k, s in ispec.items()}

results = {}
fn_raw, comp_raw = compile_step(make_train_step(cfg))
results["raw"] = analyze(comp_raw.as_text())["collectives"]
_, _, loss_raw = fn_raw(params, opt, batch)
results["loss_raw"] = float(loss_raw)

for bits in (8, 24):
    step = make_train_step_podcompressed(cfg, mesh, pspecs, bits)
    fn, comp = compile_step(step)
    results[f"gc{bits}"] = analyze(comp.as_text())["collectives"]
    if bits == 8:
        p2, _, loss_c = fn(params, opt, batch)
        results["loss_compressed"] = float(loss_c)
        results["params_finite"] = bool(all(
            bool(jnp.all(jnp.isfinite(l.astype(jnp.float32))))
            for l in jax.tree_util.tree_leaves(p2)))
lm.set_constraint_mesh(None)
print("RESULT" + json.dumps(results))
"""


@pytest.mark.slow
def test_pod_compressed_gradient_exchange_hlo_and_numerics(tmp_path):
    """The dryrun gradient-compression path end to end on 8 fake devices:
    the cross-pod exchange becomes a collective-permute whose volume scales
    with the codec rate, and the compressed step runs to a finite loss that
    matches the uncompressed step (loss is computed pre-update)."""
    import json
    import subprocess
    import sys

    script = tmp_path / "pod_compress_dryrun.py"
    script.write_text(_POD_COMPRESS_SCRIPT)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run([sys.executable, str(script)], cwd=_REPO, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    res = json.loads(line[len("RESULT"):])

    # the compressed step exchanges encoded payloads via collective-permute;
    # the raw step all-reduces and has no cross-pod permute traffic
    raw_perm = res["raw"].get("collective-permute", 0)
    gc8 = res["gc8"]["collective-permute"]
    gc24 = res["gc24"]["collective-permute"]
    assert gc8 > raw_perm
    # wire volume tracks the rate: 24-bit payloads carry ~(14/6)x the words
    # of 8-bit ones (payload bits/2 + emax + nplanes, per 16-value block)
    assert gc24 > 1.5 * gc8
    # numerics: finite updated params, and the pre-update loss matches raw
    assert res["params_finite"]
    assert res["loss_compressed"] == pytest.approx(res["loss_raw"], rel=1e-3)
