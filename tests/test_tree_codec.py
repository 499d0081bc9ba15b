"""Tree-codec layer (encode_tree/decode_tree/TreeCodecMeta), the
ResidualCorrectedCodec wrapper, and the re-founded grad_compress API."""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.compression import (
    TreeCodecMeta,
    codec_from_spec,
    codec_spec,
    decode_tree,
    encode_tree,
    get_codec,
    leaf_2d_shape,
    tree_leaf_keys,
    tree_nbytes,
)
from repro.core.grad_compress import (
    as_codec,
    compress_decompress,
    compressed_psum_tree,
    tree_collective_bytes,
)


@pytest.fixture
def tree():
    rng = np.random.default_rng(0)
    return {"w": jnp.asarray(rng.normal(size=(32, 48)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(128,)), jnp.float32),
            "scale": jnp.asarray(1.5, jnp.float32),
            "step": jnp.asarray(7, jnp.int32)}


# ---------------------------------------------------------------------------
# encode_tree / decode_tree
# ---------------------------------------------------------------------------

def test_leaf_2d_shape_conventions():
    assert leaf_2d_shape((6, 8, 16)) == (48, 16)     # lead dims fold into rows
    assert leaf_2d_shape((128,)) == (64, 2)          # 1D divisible by 64
    assert leaf_2d_shape((100,)) == (1, 100)         # 1D indivisible: one row
    assert leaf_2d_shape(()) == (1, 1)               # scalar


def test_tree_leaf_keys_match_flatten_order(tree):
    keys = tree_leaf_keys(tree)
    assert keys == ["b", "scale", "step", "w"]       # dict: sorted keys
    nested = {"a": {"x": jnp.zeros(3), "y": [jnp.zeros(2), jnp.zeros(2)]}}
    assert tree_leaf_keys(nested) == ["a/x", "a/y/0", "a/y/1"]


def test_roundtrip_fixed_rate_preserves_structure_and_dtypes(tree):
    codec = get_codec("fixed_rate", bits_per_value=16, backend="jnp")
    treedef = jax.tree_util.tree_structure(tree)
    enc, meta = encode_tree(codec, tree)
    out = decode_tree(enc, meta, codec=codec, treedef=treedef)
    assert jax.tree_util.tree_structure(out) == treedef
    for k in tree:
        assert out[k].dtype == tree[k].dtype
        assert out[k].shape == tree[k].shape
        err = float(jnp.max(jnp.abs(out[k].astype(jnp.float32)
                                    - tree[k].astype(jnp.float32))))
        assert err < 0.01
    assert int(out["step"]) == 7                      # int leaf: untouched


def test_noncompressible_leaves_pass_through_bit_exact(tree):
    codec = get_codec("fixed_rate", bits_per_value=8, backend="jnp")
    enc, meta = encode_tree(codec, tree, min_size=1000)
    by_key = dict(zip(tree_leaf_keys(tree), enc))
    flags = {l.key: l.compressed for l in meta.leaves}
    assert flags == {"w": True, "b": False, "scale": False, "step": False}
    out = decode_tree(enc, meta, codec=codec)
    assert bool(jnp.all(out[0] == tree["b"]))         # raw float: bit-exact
    assert "b" in by_key and bool(jnp.all(by_key["b"] == tree["b"]))


def test_fixed_accuracy_per_leaf_tolerances(tree):
    codec = get_codec("fixed_accuracy", backend="jnp")
    enc, meta = encode_tree(codec, tree, tolerances={"w": 1e-3, "b": 1e-2})
    out = dict(zip(tree_leaf_keys(tree), decode_tree(enc, meta)))
    assert float(jnp.max(jnp.abs(out["w"] - tree["w"]))) <= 1e-3
    assert float(jnp.max(jnp.abs(out["b"] - tree["b"]))) <= 1e-2
    # no tolerance resolvable for 'scale' and no codec default -> raw
    flags = {l.key: l.compressed for l in meta.leaves}
    assert not flags["scale"] and bool(out["scale"] == tree["scale"])


def test_scalar_tolerance_applies_everywhere(tree):
    codec = get_codec("fixed_accuracy", backend="jnp")
    enc, meta = encode_tree(codec, tree, tolerances=5e-3)
    out = dict(zip(tree_leaf_keys(tree), decode_tree(enc, meta)))
    for k in ("w", "b", "scale"):
        assert float(jnp.max(jnp.abs(out[k] - tree[k]))) <= 5e-3


def test_meta_json_roundtrip_and_hashable(tree):
    codec = get_codec("fixed_rate", bits_per_value=12, backend="jnp")
    _, meta = encode_tree(codec, tree)
    meta2 = TreeCodecMeta.from_json(json.loads(json.dumps(meta.to_json())))
    assert meta2 == meta and hash(meta2) == hash(meta)
    rebuilt = meta2.make_codec()
    assert codec_spec(rebuilt) == codec_spec(codec)


def test_codec_spec_roundtrip_all_registered():
    for c in (get_codec("fixed_rate", bits_per_value=9, backend="pallas"),
              get_codec("fixed_accuracy", tolerance=1e-4, backend="jnp"),
              get_codec("fixed_accuracy+residual", tolerance=1e-3,
                        backend="jnp")):
        assert codec_spec(codec_from_spec(codec_spec(c))) == codec_spec(c)


def test_encode_decode_trace_into_jit(tree):
    codec = get_codec("fixed_rate", bits_per_value=14, backend="jnp")
    treedef = jax.tree_util.tree_structure(tree)

    @jax.jit
    def rt(t):
        enc, meta = encode_tree(codec, t)
        return decode_tree(enc, meta, codec=codec, treedef=treedef)

    out = rt(tree)
    enc, meta = encode_tree(codec, tree)
    ref = decode_tree(enc, meta, codec=codec, treedef=treedef)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        assert bool(jnp.all(a == b))                  # jit == eager, bit-exact


def test_tree_nbytes_accounting(tree):
    codec = get_codec("fixed_rate", bits_per_value=8, backend="jnp")
    enc, meta = encode_tree(codec, tree)
    raw, stored = tree_nbytes(codec, enc, meta)
    exact_raw = sum(np.asarray(l).nbytes for l in jax.tree_util.tree_leaves(tree))
    assert raw == exact_raw
    assert stored < raw                               # 8/32 rate dominates


# ---------------------------------------------------------------------------
# residual-corrected codec (NeurLZ-style wrapper)
# ---------------------------------------------------------------------------

def test_residual_codec_bounded_and_not_worse():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 32, 48)), jnp.float32)
    tol = 1e-2
    plain = get_codec("fixed_accuracy", tolerance=tol, backend="jnp")
    corr = get_codec("fixed_accuracy+residual", tolerance=tol, backend="jnp")
    dec_p = plain.decode_batch(plain.encode_batch(x))
    rcf = corr.encode_batch(x)
    dec_c = corr.decode_batch(rcf)
    # correction is clipped to +/-tol: worst case 2*tol
    assert float(jnp.max(jnp.abs(dec_c - x))) <= 2 * tol + 1e-6
    # per-sample gating: never worse than the plain decode in L1
    l1_p = jnp.mean(jnp.abs(dec_p - x), axis=(1, 2))
    l1_c = jnp.mean(jnp.abs(dec_c - x), axis=(1, 2))
    assert bool(jnp.all(l1_c <= l1_p + 1e-7))


def test_residual_codec_improves_smooth_fields():
    # smooth field: the 4-neighborhood regression has real signal to exploit
    h = np.linspace(0, 4 * np.pi, 64)
    x = jnp.asarray(np.sin(h)[None, :, None] * np.cos(h)[None, None, :]
                    + 0.01 * np.random.default_rng(0).normal(size=(2, 64, 64)),
                    jnp.float32)
    tol = 5e-2
    plain = get_codec("fixed_accuracy", tolerance=tol, backend="jnp")
    corr = get_codec("fixed_accuracy+residual", tolerance=tol, backend="jnp")
    l1_p = float(jnp.mean(jnp.abs(plain.decode_batch(plain.encode_batch(x)) - x)))
    l1_c = float(jnp.mean(jnp.abs(corr.decode_batch(corr.encode_batch(x)) - x)))
    assert l1_c < l1_p


def test_residual_codec_field_arrays_roundtrip():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, 24, 32)), jnp.float32)
    corr = get_codec("fixed_accuracy+residual", tolerance=1e-3, backend="jnp")
    rcf = corr.encode_batch(x)
    arrays = corr.field_to_arrays(rcf)
    assert {"payload", "emax", "nplanes", "weights", "tols"} <= set(arrays)
    rcf2 = corr.field_from_arrays(arrays, (24, 32))
    assert bool(jnp.all(corr.decode_batch(rcf2) == corr.decode_batch(rcf)))


def test_residual_codec_nbytes_includes_weights():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(2, 24, 32)), jnp.float32)
    plain = get_codec("fixed_accuracy", tolerance=1e-3, backend="jnp")
    corr = get_codec("fixed_accuracy+residual", tolerance=1e-3, backend="jnp")
    n_p = np.asarray(plain.nbytes(plain.encode_batch(x)))
    n_c = np.asarray(corr.nbytes(corr.encode_batch(x)))
    assert bool(np.all(n_c > n_p))                    # corrector isn't free


def test_residual_through_tree_and_checkpoint_arrays(tree):
    corr = get_codec("fixed_accuracy+residual", tolerance=1e-3, backend="jnp")
    enc, meta = encode_tree(corr, tree)
    out = dict(zip(tree_leaf_keys(tree), decode_tree(enc, meta)))
    assert float(jnp.max(jnp.abs(out["w"] - tree["w"]))) <= 2e-3 + 1e-6


# ---------------------------------------------------------------------------
# grad_compress on the seam
# ---------------------------------------------------------------------------

def test_compress_decompress_accepts_int_bits_and_codec():
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
    errs = [float(jnp.max(jnp.abs(compress_decompress(g, b) - g)))
            for b in (8, 16, 24)]
    assert errs[0] > errs[1] > errs[2]                # more bits, less error
    ca = get_codec("fixed_accuracy", tolerance=1e-3, backend="jnp")
    assert float(jnp.max(jnp.abs(compress_decompress(g, ca) - g))) <= 1e-3


def test_as_codec():
    c = as_codec(12)
    assert c.name == "fixed_rate" and c.bits_per_value == 12
    assert as_codec(c) is c


def test_compressed_psum_tree_two_tree_return_and_error_feedback():
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    grads = {"w": jnp.stack([g, -g]),
             "step_like": jnp.stack([jnp.asarray(1, jnp.int32)] * 2)}

    def f(tree):
        return compressed_psum_tree(tree, "dev", 12)

    mean, res = jax.vmap(f, axis_name="dev")(grads)
    # two proper trees with the gradient structure
    assert set(mean) == set(res) == set(grads)
    assert mean["w"].shape == res["w"].shape == grads["w"].shape
    # both devices agree on the mean (they decoded the same payloads)
    assert bool(jnp.all(mean["w"][0] == mean["w"][1]))
    # error-feedback identity: residual = input - decoded, per device
    enc_dev0 = compress_decompress(g, 12)
    assert np.allclose(np.asarray(res["w"][0]), np.asarray(g - enc_dev0),
                       atol=1e-6)
    # int leaves pass through the pmean raw with zero residual
    assert int(mean["step_like"][0]) == 1
    assert int(res["step_like"][0]) == 0


def test_compressed_psum_tree_residual_carry_reduces_bias():
    # with error feedback, the *accumulated* applied update tracks the true
    # gradient sum better than compressing each step independently
    rng = np.random.default_rng(4)
    steps = [jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
             for _ in range(6)]
    bits = 6                                           # coarse: visible bias

    def run(carry_residual):
        res = {"g": jnp.zeros_like(steps[0])}
        applied = jnp.zeros_like(steps[0])
        for g in steps:
            def f(tree, r):
                return compressed_psum_tree(tree, "dev", bits, residuals=r)
            mean, res = jax.vmap(f, axis_name="dev")(
                {"g": g[None]}, {"g": res["g"][None]}
                if carry_residual else None)
            res = {"g": res["g"][0]}
            applied = applied + mean["g"][0]
        want = sum(np.asarray(s) for s in steps)
        return float(np.abs(np.asarray(applied) - want).max())

    assert run(True) < run(False)


def test_compressed_psum_tree_fixed_accuracy_bound():
    rng = np.random.default_rng(7)
    g = jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)
    ca = get_codec("fixed_accuracy", tolerance=1e-3, backend="jnp")

    def f(tree):
        return compressed_psum_tree(tree, "dev", ca)

    mean, res = jax.vmap(f, axis_name="dev")({"g": g[None]})
    assert float(jnp.max(jnp.abs(mean["g"][0] - g))) <= 1e-3
    assert float(jnp.max(jnp.abs(res["g"][0]))) <= 1e-3


def test_tree_collective_bytes_ratio():
    rng = np.random.default_rng(8)
    grads = {"a": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(256,)), jnp.float32)}
    raw, comp = tree_collective_bytes(grads, 8)
    assert raw == (64 * 64 + 256) * 4
    assert comp < raw / 2                             # 8/32 + headers
    raw2, comp2 = tree_collective_bytes(grads, None)
    assert raw2 == comp2 == raw                       # uncompressed baseline
