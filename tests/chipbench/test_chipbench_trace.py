"""The trace reduction on a small trace recorded on a TPU v5e, committed
xz-compressed under data/: one traced run of the short cell, 4 slots, two
requests of 64-80 prompt tokens and 4 output tokens each, 1-s window
(chipbench/readings.py --trace --keep-trace, measured on one TPU v5e chip)."""
import lzma
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "short_tiny.xplane.pb.xz"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(lzma.decompress(
        DATA.read_bytes()))
    return trace.reduce(pd)


def test_busy_inside_window(reduced):
    # as the reduction read this trace on the chip, where it was recorded
    assert reduced["window_s"] == pytest.approx(0.642500038)
    assert reduced["busy_s"] == pytest.approx(0.604023247)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert len(reduced["busy_s_per_device"]) == 1


def test_named_kernels_have_device_time(reduced):
    for kernel in ("paged_attention", "prefill_attention_paged",
                   "decode_sample", "decode"):
        assert trace.kernel_seconds(reduced, kernel) > 0, kernel
    # a name never matches another kernel's prefix
    assert trace.kernel_seconds(reduced, "decode") < sum(
        v for k, v in reduced["op_seconds"].items() if k.startswith("decode"))


def test_idle_gaps_named_by_harness_spans(reduced):
    gaps = reduced["idle_gaps"]
    assert gaps and all(name.startswith(trace.SPAN_PREFIX)
                        for name, _ in gaps)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    assert len(reduced["device_ops"]) <= 10 and len(gaps) <= 10


def test_union_and_names():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.op_name("%paged_attention.11 = f32[2] custom-call()") \
        == "paged_attention.11"
