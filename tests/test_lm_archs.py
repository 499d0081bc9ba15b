"""Per-arch smoke tests: reduced configs, one forward/train step on CPU,
shape + finiteness asserts; decode-vs-forward consistency per cache family."""
import dataclasses
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_ARCHS, SHAPE_CELLS, cell_applicable, get_config, reduced_config
from repro.models import lm

KEY = jax.random.PRNGKey(0)

# One cheap attention arch + the SSM arch stay in the fast lane; every other
# end-to-end train/decode parametrization compiles a full model and is
# marked slow (deselect with -m "not slow"; the tier-1 run keeps them all).
FAST_ARCHS = ("internlm2-1.8b", "mamba2-130m")


def _arch_params(archs):
    return [a if a in FAST_ARCHS else pytest.param(a, marks=pytest.mark.slow)
            for a in archs]


def _batch(cfg, b=2, s=64):
    batch = {"tokens": jnp.full((b, s), 3, jnp.int32),
             "labels": jnp.ones((b, s), jnp.int32)}
    if cfg.frontend == "vision":
        batch["frontend_embeds"] = jnp.full(
            (b, cfg.frontend_seq, cfg.frontend_dim), 0.1, jnp.float32)
    if cfg.encoder_layers:
        batch["encoder_embeds"] = jnp.full((b, s, cfg.frontend_dim), 0.1,
                                           jnp.float32)
    return batch


@pytest.mark.parametrize("arch", _arch_params(ALL_ARCHS))
def test_arch_train_step_smoke(arch):
    cfg = reduced_config(arch)
    params = lm.init_lm(KEY, cfg)
    batch = _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm.lm_loss(p, cfg, batch)))(params)
    assert np.isfinite(float(loss))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert bool(jnp.isfinite(leaf).all())


@pytest.mark.parametrize("arch", _arch_params(ALL_ARCHS))
def test_arch_forward_output_shape(arch):
    cfg = reduced_config(arch)
    params = lm.init_lm(KEY, cfg)
    batch = _batch(cfg, b=2, s=64)
    hidden, aux = jax.jit(lambda p: lm.lm_forward(p, cfg, batch))(params)
    expect_s = 64 + (cfg.frontend_seq if cfg.frontend == "vision" else 0)
    assert hidden.shape == (2, expect_s, cfg.d_model)
    assert bool(jnp.isfinite(hidden).all())


@pytest.mark.parametrize("arch", _arch_params(["internlm2-1.8b", "mamba2-130m",
                                               "hymba-1.5b",
                                               "qwen3-moe-30b-a3b",
                                               "falcon-h1-34b"]))
def test_decode_matches_forward(arch):
    """KV/SSM/hybrid caches: step-by-step decode == full causal forward."""
    cfg = dataclasses.replace(reduced_config(arch), attn_chunk=16,
                              capacity_factor=8.0)  # lossless dispatch
    params = lm.init_lm(jax.random.PRNGKey(42), cfg)
    b, s = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(7), (b, s), 0, cfg.vocab_size)
    hidden, _ = jax.jit(lambda p: lm.lm_forward(p, cfg, {"tokens": toks}))(params)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    full_logits = np.asarray(jnp.einsum("bsd,dv->bsv", hidden, w)
                             * cfg.lm_head_multiplier)
    cache = lm.init_cache(cfg, b, s, dtype=jnp.float32)
    step = jax.jit(lambda p, c, t, pos: lm.serve_step(p, cfg, c, t, pos))
    errs = []
    for t in range(s):
        logits, cache = step(params, cache, toks[:, t], jnp.int32(t))
        errs.append(np.abs(np.asarray(logits) - full_logits[:, t]).max())
    tol = 2e-4 if arch == "qwen3-moe-30b-a3b" else 2e-5   # bf16 MoE dispatch
    assert max(errs) < tol, f"decode diverges from forward: {max(errs)}"


@pytest.mark.parametrize("per_slot", [True, False],
                         ids=["vector_pos", "scalar_pos"])
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mamba2-130m",
                                  "hymba-1.5b", "qwen3-moe-30b-a3b",
                                  "falcon-h1-34b"])
def test_decode_step_writes_only_new_rows(arch, per_slot):
    """A decode step changes only the K/V rows [l, b, pos[b]] of every layer
    and each layer's conv/SSM state; every other cache entry is returned
    bit for bit, whichever cache leaves the arch has."""
    cfg = reduced_config(arch)
    params = lm.init_lm(KEY, cfg)
    b, max_seq = 3, 16
    pos = (jnp.asarray([3, 9, max_seq - 1], jnp.int32) if per_slot
           else jnp.int32(max_seq - 1))
    shapes = lm.init_cache(cfg, b, max_seq, dtype=jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(5), len(shapes))
    cache = {n: jax.random.normal(k, c.shape, c.dtype)
             for k, (n, c) in zip(keys, sorted(shapes.items()))}
    toks = jax.random.randint(jax.random.PRNGKey(6), (b,), 0, cfg.vocab_size)
    logits, new = jax.jit(lambda p, c, t, q: lm.serve_step(p, cfg, c, t, q))(
        params, cache, toks, pos)
    assert bool(jnp.isfinite(logits).all())
    assert set(new) == set(cache)
    rows = np.zeros((cfg.num_layers, b, max_seq), bool)
    rows[:, np.arange(b), np.broadcast_to(np.asarray(pos), (b,))] = True
    for name in cache:
        old, got = np.asarray(cache[name]), np.asarray(new[name])
        assert got.shape == old.shape and got.dtype == old.dtype, name
        if name in ("k", "v"):
            np.testing.assert_array_equal(got[~rows], old[~rows], name)
            assert (got[rows] != old[rows]).any(axis=(1, 2)).all(), name
        else:               # recurrent state: every layer and slot rewritten
            assert (got != old).reshape(cfg.num_layers, b, -1).any(-1).all(), \
                name


def test_prefill_matches_forward():
    cfg = dataclasses.replace(reduced_config("internlm2-1.8b"), attn_chunk=16)
    params = lm.init_lm(KEY, cfg)
    b, s = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab_size)
    hidden, _ = jax.jit(lambda p: lm.lm_forward(p, cfg, {"tokens": toks}))(params)
    w = params["lm_head"]
    want = np.asarray(jnp.einsum("bd,dv->bv", hidden[:, -1], w))
    logits, cache = jax.jit(lambda p: lm.lm_prefill(
        p, cfg, {"tokens": toks}, s, cache_dtype=jnp.float32))(params)
    np.testing.assert_allclose(np.asarray(logits), want, atol=2e-5)
    # prefilled cache continues correctly
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, _ = jax.jit(lambda p, c, t: lm.serve_step(p, cfg, c, t,
                                                       jnp.int32(s)))(
        params, cache, nxt)
    assert bool(jnp.isfinite(logits2).all())


def test_moe_router_load_balance_aux_positive():
    cfg = reduced_config("qwen3-moe-30b-a3b")
    params = lm.init_lm(KEY, cfg)
    batch = _batch(cfg)
    _, aux = jax.jit(lambda p: lm.lm_forward(p, cfg, batch))(params)
    assert float(aux) > 0.0


def test_param_counts_match_init():
    for arch in ("internlm2-1.8b", "qwen3-moe-30b-a3b", "mamba2-130m",
                 "falcon-h1-34b"):
        cfg = reduced_config(arch)
        params = lm.init_lm(KEY, cfg)
        n_init = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        assert n_init == lm.param_count(cfg)


def test_active_params_less_than_total_for_moe():
    cfg = get_config("qwen3-moe-30b-a3b")
    assert lm.active_param_count(cfg) < lm.param_count(cfg) / 4


def test_full_config_param_counts_sane():
    """The registry configs reproduce published parameter scales."""
    expected = {"internlm2-1.8b": (1.5e9, 2.5e9),
                "qwen2.5-14b": (12e9, 16e9),
                "codeqwen1.5-7b": (6e9, 8.5e9),
                "command-r-35b": (28e9, 40e9),  # GQA variant: 30.3B
                "arctic-480b": (400e9, 520e9),
                "qwen3-moe-30b-a3b": (25e9, 34e9),
                "mamba2-130m": (1e8, 1.8e8),
                "falcon-h1-34b": (33.5e9, 33.7e9)}
    for arch, (lo, hi) in expected.items():
        n = lm.param_count(get_config(arch))
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9}, {hi/1e9}]"


def test_cell_applicability_rules():
    long = [c for c in SHAPE_CELLS if c.name == "long_500k"][0]
    assert cell_applicable(get_config("mamba2-130m"), long)[0]
    assert cell_applicable(get_config("hymba-1.5b"), long)[0]
    assert not cell_applicable(get_config("command-r-35b"), long)[0]
    train = SHAPE_CELLS[0]
    for a in ALL_ARCHS:
        assert cell_applicable(get_config(a), train)[0]
