"""The FLOP and byte counts against hand counts (chipbench/flops.py)."""
import pytest

from chipbench import flops, peaks
from chipbench import run as harness

V5E = peaks.peaks("TPU v5 lite")


def test_head_gemm_by_hand():
    # the decode head: one [rows, 2048] x [2048, 92544] GEMM over P(16,2)
    # codes each step, the codes read once a step
    conf = harness.load_config("internlm2-1.8b")
    D, V = 2048, 92544
    ops, nbytes = flops.head_work(conf, steps=3, rows=5)
    assert ops == 2 * 5 * D * V
    assert nbytes == 3 * D * V * 2 + 5 * (4 * D + 4)
    # bound by HBM bytes on a v5e: 0.46 ms a step for the codes alone
    assert nbytes / V5E["hbm_bytes"] > ops / V5E["bf16_flops"]


def test_decode_attention_by_hand():
    conf = harness.load_config("internlm2-1.8b")
    L, H, KV, Dh = 24, 16, 8, 128
    ops, nbytes = flops.decode_attention_work(conf, [100, 300])
    assert ops == 4 * H * Dh * 400 * L
    # P(8,2) keys and values of 400 positions, f32 queries and outputs
    assert nbytes == (2 * KV * Dh * 1 * 400 + 2 * 4 * H * Dh * 2) * L


def test_internlm2_decode_step_by_hand():
    conf = harness.load_config("internlm2-1.8b")
    L, D, H, KV, Dh, F, V = 24, 2048, 16, 8, 128, 8192, 92544
    per_layer = 2 * (D * H * Dh + 2 * D * KV * Dh + H * Dh * D + 3 * D * F)
    ctx = 1000
    attn = 2 * 2 * H * Dh * ctx      # q.k and p.v over ctx keys
    assert flops.token_flops(conf, ctx) == L * (per_layer + attn)
    assert flops.head_flops(conf) == 2 * D * V
    # 1.89 billion parameters: the weights a token passes through
    assert L * per_layer / 2 + 2 * V * D == pytest.approx(1.889e9, rel=2e-3)


def test_request_flops_sums_positions():
    conf = harness.load_config("internlm2-1.8b")
    p, n, s = 37, 5, 16
    want = sum(flops.token_flops(conf, q + 1) for q in range(s, p))
    want += flops.head_flops(conf)
    want += sum(flops.token_flops(conf, p + i) + flops.head_flops(conf)
                for i in range(1, n))
    assert flops.request_flops(conf, p, n, shared=s) == want


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_prefill_attention_by_hand():
    conf = harness.load_config("internlm2-1.8b")
    L, H, KV, Dh = 24, 16, 8, 128
    pk = {"bf16_flops": 1e12, "hbm_bytes": 1e12}   # both bounds 1 s/TB
    # 80 tokens as the engine chunks them: 64 then 16
    want = 0.0
    for start, c in ((0, 64), (64, 16)):
        ops = 4 * H * Dh * (c * start + c * (c + 1) / 2)
        nbytes = 2 * KV * Dh * (start + c) + 2 * 2 * H * Dh * c
        want += L * max(ops, nbytes) / 1e12
    got = flops.prefill_attention_least_seconds(conf, 80, [64, 16, 4, 1],
                                                0.0, pk)
    assert got == pytest.approx(want)
    # the first 64 positions served from shared pages: only the 16 remain
    shared = flops.prefill_attention_least_seconds(conf, 80, [64, 16, 4, 1],
                                                   64.0, pk)
    ops = 4 * H * Dh * (16 * 64 + 16 * 17 / 2)
    nbytes = 2 * KV * Dh * 80 + 2 * 2 * H * Dh * 16
    assert shared == pytest.approx(L * max(ops, nbytes) / 1e12)
