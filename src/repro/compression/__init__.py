"""TPU-adapted ZFP-style error-bounded lossy compression.

Public API:
  Codec / get_codec / register_codec      -- the unified codec seam (api.py);
                                             data, datagen and train consume this
  encode_fixed_rate / decode_fixed_rate   -- uniform bits-per-value (dense layout)
  encode_fixed_accuracy / decode          -- per-block plane counts, true error bound
  CompressedField                         -- pytree container + logical byte count
"""
from repro.compression.transform import Q_FIXED_POINT, TOTAL_PLANES
from repro.compression.zfp import (
    CompressedField,
    compressed_nbytes,
    compressed_nbytes_batch,
    compression_ratio,
    decode,
    decode_batch,
    decode_fixed_rate,
    encode_fixed_accuracy,
    encode_fixed_accuracy_batch,
    encode_fixed_rate,
    encode_fixed_rate_batch,
    trim_to_nplanes,
)
from repro.compression.transform import blockify, deblockify
from repro.compression.api import (
    BACKENDS,
    Codec,
    FAEncodeState,
    FixedAccuracyCodec,
    FixedRateCodec,
    LeafSpec,
    ResidualCorrectedCodec,
    ResidualCorrectedField,
    TreeCodecMeta,
    codec_from_plan,
    codec_from_spec,
    codec_names,
    codec_spec,
    decode_stacked_payloads,
    decode_tree,
    encode_tree,
    fa_precompute_batch,
    fa_stats_batch,
    get_codec,
    leaf_2d_shape,
    register_codec,
    tree_leaf_keys,
    tree_nbytes,
)

__all__ = [
    "BACKENDS",
    "Codec",
    "CompressedField",
    "FAEncodeState",
    "FixedAccuracyCodec",
    "FixedRateCodec",
    "LeafSpec",
    "ResidualCorrectedCodec",
    "ResidualCorrectedField",
    "TreeCodecMeta",
    "Q_FIXED_POINT",
    "TOTAL_PLANES",
    "blockify",
    "deblockify",
    "codec_from_plan",
    "codec_from_spec",
    "codec_names",
    "codec_spec",
    "compressed_nbytes",
    "compressed_nbytes_batch",
    "compression_ratio",
    "decode",
    "decode_batch",
    "decode_fixed_rate",
    "decode_stacked_payloads",
    "decode_tree",
    "encode_fixed_accuracy",
    "encode_fixed_accuracy_batch",
    "encode_fixed_rate",
    "encode_fixed_rate_batch",
    "encode_tree",
    "fa_precompute_batch",
    "fa_stats_batch",
    "get_codec",
    "leaf_2d_shape",
    "register_codec",
    "tree_leaf_keys",
    "tree_nbytes",
    "trim_to_nplanes",
]
