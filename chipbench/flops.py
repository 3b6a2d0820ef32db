"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count the work of the model, whatever implements it: each matrix
product once, attention over the keys a query actually attends (causal),
no recomputation and no padding rows.  They take the sizes the
configuration file runs (its `model` entry).  A multiply-add is two
operations.
"""
from __future__ import annotations

from typing import List, Tuple


def _dense(conf):
    m = conf["model"]
    return dict(L=m["n_layers"], D=m["d_model"], Hq=m["n_heads"],
                Hkv=m["n_kv_heads"], Dh=m["head_dim"], F=m["d_ff"],
                V=m["vocab_size"])


def gemm_shapes(conf) -> List[Tuple[int, int]]:
    """(K, N) of every posit-coded weight GEMM a token passes through in
    one layer-stack forward, the logits head excluded."""
    s = _dense(conf)
    D, Hq, Hkv, Dh, F = s["D"], s["Hq"], s["Hkv"], s["Dh"], s["F"]
    layer = [(D, Hq * Dh), (D, Hkv * Dh), (D, Hkv * Dh), (Hq * Dh, D),
             (D, F), (D, F), (F, D)]
    return layer * s["L"]


def head_shape(conf) -> Tuple[int, int]:
    s = _dense(conf)
    return s["D"], s["V"]


def token_flops(conf, ctx: int) -> int:
    """Operations of one token's pass through the layer stack (no head),
    the token attending `ctx` keys (itself included)."""
    mm = sum(2 * k * n for k, n in gemm_shapes(conf))
    s = _dense(conf)
    return mm + 4 * s["Hq"] * s["Dh"] * ctx * s["L"]


def head_flops(conf) -> int:
    k, n = head_shape(conf)
    return 2 * k * n


def request_flops(conf, prompt_len: int, n_out: int, shared: int = 0) -> int:
    """A whole request: its prompt from position `shared` on (earlier
    positions were served from shared pages), one head at the last prompt
    position, then n_out - 1 decoded tokens each with its head."""
    # token_flops is affine in ctx: a + b * ctx
    a = token_flops(conf, 0)
    b = token_flops(conf, 1) - a
    n_prompt = prompt_len - shared
    ctx_prompt = _range_sum(shared + 1, prompt_len)
    ctx_decode = _range_sum(prompt_len + 1, prompt_len + n_out - 1)
    return (a * (n_prompt + n_out - 1) + b * (ctx_prompt + ctx_decode)
            + head_flops(conf) * n_out)


def _range_sum(lo: int, hi: int) -> int:
    """lo + (lo + 1) + ... + hi (0 when hi < lo)."""
    return 0 if hi < lo else (lo + hi) * (hi - lo + 1) // 2


# bytes of one stored weight and one stored KV element, by serving policy
STORAGE_BYTES = {"serve_fused_p16": {"weight": 2, "kv": 1}}


def decode_attention_work(conf, contexts) -> Tuple[float, float]:
    """(operations, bytes) of paged decode attention over the whole layer
    stack for decoded tokens attending `contexts` keys each: every
    stored key and value read once, the query read and the output written
    in float32."""
    s = _dense(conf)
    kv = STORAGE_BYTES[conf["policy"]]["kv"]
    ctx = float(sum(contexts))
    n = len(contexts)
    ops = 4.0 * s["Hq"] * s["Dh"] * ctx * s["L"]
    nbytes = (2.0 * s["Hkv"] * s["Dh"] * kv * ctx
              + 2 * 4.0 * s["Hq"] * s["Dh"] * n) * s["L"]
    return ops, nbytes


def prefill_attention_least_seconds(conf, prompt_len: int, buckets,
                                    shared: float, peaks: dict) -> float:
    """Least time of the fused prefill attention over one prompt, chunked
    as the engine chunks it (greedy over `buckets`, largest first), the
    first `shared` positions served from shared pages.  Per chunk and
    layer: the stored history read once, the chunk's keys and values
    written, bfloat16 queries in and outputs out, causal operations; the
    larger of the two bounds."""
    s = _dense(conf)
    kv = STORAGE_BYTES[conf["policy"]]["kv"]
    total, start, left = 0.0, 0, prompt_len
    for b in sorted(buckets, reverse=True):
        while left >= b:
            if start + b > shared:
                # the part of a chunk below `shared` (a fraction of a
                # chunk, for a mean shared length) was not computed
                done = max(shared - start, 0.0) / b
                keys = b * start + b * (b + 1) / 2
                ops = 4.0 * s["Hq"] * s["Dh"] * keys
                nbytes = (2.0 * s["Hkv"] * s["Dh"] * kv * (start + b)
                          + 2 * 2.0 * s["Hq"] * s["Dh"] * b)
                total += (1 - done) * s["L"] * max(
                    ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes"])
            start += b
            left -= b
    return total


def head_work(conf, steps: int, rows: int) -> Tuple[float, float]:
    """(operations, bytes) of the fused decode head over `steps` decode
    steps that sampled `rows` tokens in all: the head's weight codes read
    once a step, each row's hidden state in and token out."""
    d, v = head_shape(conf)
    w = STORAGE_BYTES[conf["policy"]]["weight"]
    return 2.0 * d * v * rows, float(steps) * d * v * w + rows * (4.0 * d
                                                                 + 4)
