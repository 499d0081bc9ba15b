"""The trace reduction: a hand-made trace with known answers, and a small
trace recorded on the chip (``bench/testdata``)."""
from __future__ import annotations

import gzip
import os

import pytest

from bench import trace_reduce

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")

# window 0.5..10.5 us; ops 1..3 us (under gather_decode) and 4..5 us; an
# index upload open 3.5..4.1 us, an op running before the window opens
SYNTHETIC = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 800000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"
                                  stats { metadata_id: 1 str_value: "jit(f)/gather_decode/mul" } } }
  event_metadata { key: 2 value { id: 2 name: "convolution.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit__fused_step(7)" } }
  event_metadata { key: 4 value { id: 4 name: "copy.9" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 600000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.index_upload" } }
}
'''


def _encoded(text: str) -> bytes:
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(text)


def test_op_names_come_from_the_event_metadata():
    names = trace_reduce.op_names(_encoded(SYNTHETIC))
    assert names == {"/device:TPU:0": {
        "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)":
            "jit(f)/gather_decode/mul"}}


def test_synthetic_trace_reduces_to_known_numbers():
    t = trace_reduce.reduce_bytes(_encoded(SYNTHETIC))
    assert t.window_s == pytest.approx(10e-6)
    # copy.9 is clipped to 0.5..0.8 us, so busy is 0.3 + 2 + 1 us
    assert t.busy_s == pytest.approx(3.3e-6)
    assert t.scope_s("gather_decode") == pytest.approx(2e-6)
    assert t.module_s("_fused_step") == (1, pytest.approx(4e-6))
    gaps = dict(t.gaps)
    assert gaps["bench.index_upload"] == pytest.approx(1e-6)
    assert gaps["outside_spans"] == pytest.approx(0.2e-6 + 5.5e-6)
    top = t.breakdown()
    assert top["device_ops"][0] == [
        "%fusion.1 f32[8]{0} jit(f)/gather_decode/mul", pytest.approx(2e-6)]
    assert top["idle_gaps"][0][0] == "outside_spans"


def test_a_trace_without_the_window_span_is_refused():
    text = SYNTHETIC.replace('"bench.window"', '"other"')
    with pytest.raises(ValueError):
        trace_reduce.reduce_bytes(_encoded(text))


def test_union_merges_overlaps():
    starts, ends = trace_reduce._union([3, 0, 1, 4], [4, 2, 2.5, 5])
    assert starts.tolist() == [0, 3] and ends.tolist() == [2.5, 5]


def test_recorded_train_trace():
    """4.3 s of the train-rt window, traced on a TPU v5 lite (its breakdown
    as the benchmark first read it)."""
    with gzip.open(os.path.join(TESTDATA, "train-rt.xplane.pb.gz")) as f:
        t = trace_reduce.reduce_bytes(f.read())
    assert t.window_s == pytest.approx(4.313639226)
    assert t.busy_s == pytest.approx(4.30424918, rel=1e-6)
    calls, step_s = t.module_s("_fused_step")
    assert calls == 22
    assert 100 * t.scope_s("gather_decode") / step_s == pytest.approx(
        17.045059558835, rel=1e-6)
    top = t.breakdown()["device_ops"][0]
    assert top[0].startswith("%zfp_decode_blocks_fa.1 ")
    assert top[1] == pytest.approx(0.558686432, rel=1e-6)
    assert t.kernel_s("zfp_decode_blocks_fa") == pytest.approx(top[1])
