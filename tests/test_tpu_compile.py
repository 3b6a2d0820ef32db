"""Compile every main-path Pallas kernel for a TPU v5e at gemma3-4b widths.

Interpret mode (every other test) accepts unaligned blocks, in-kernel
reshapes and VMEM budgets that Mosaic refuses; these cases hand the
kernels to the TPU compiler for a described (not attached) v5e chip.
Widths: 4 slots, 8 query / 4 KV heads of 256, d_model 2560, d_ff 10240,
vocabulary 262144, P(8,2) KV pages of 16 tokens over max_seq 2048.  The
serving kernels keep their explicit names in the compiled HLO, where the
device trace reads them (`paged_attention`, `prefill_attention_paged`,
`decode_sample`, the codec's `decode` and `encode`).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so describing it while
pytest collects would break the other workers.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import P8_2, P16_2
from repro.kernels import autotune, paged_attention as pa, posit_codec
from repro.kernels import posit_matmul, prefill_attention as pf

B, HQ, HKV, DH, PS, D, F, V = 4, 8, 4, 256, 16, 2560, 10240, 262144
M = 2048 // PS          # pages per slot
NP = B * M + 1          # pool pages incl. the trash page
KVD = HKV * DH


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, **kw_shapes):
    def sds(s):
        return jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
    args = [sds(s) for s in shapes]
    kwargs = {k: sds(s) for k, s in kw_shapes.items()}
    return jax.jit(fn).lower(*args, **kwargs).compile()


KERNEL = re.compile(r"%([A-Za-z_][\w\-]*?)(?:\.\d+)? = [^\n]*"
                    r'custom_call_target="tpu_custom_call"')


def _kernels(compiled) -> set:
    """Names of the Pallas kernels in a compiled program's HLO text."""
    return set(KERNEL.findall(compiled.as_text()))


def _pages():
    return ((NP, PS, KVD), jnp.int8)


CODEC = {
    "encode_p16": (lambda x: posit_codec.encode(x, P16_2), jnp.float32),
    "encode_p8": (lambda x: posit_codec.encode(x, P8_2), jnp.float32),
    "decode_p16": (lambda c: posit_codec.decode(c, P16_2), jnp.int16),
    "decode_p8": (lambda c: posit_codec.decode(c, P8_2), jnp.int8),
}


@pytest.mark.parametrize("case", sorted(CODEC))
def test_codec_compiles(case, one_chip):
    fn, dt = CODEC[case]
    compiled = _compile(fn, one_chip, ((D, F), dt))
    assert _kernels(compiled) == {case.split("_")[0]}


def test_posit_matmul_compiles(one_chip):
    _compile(lambda a, b: posit_matmul.posit_matmul(a, b, P16_2, P16_2),
             one_chip, ((B, D), jnp.int16), ((D, F), jnp.int16))


# (query shape, partials): one decode token per slot, the T=4 speculative
# verify grid, and the per-shard partials of a kv_pages-sharded pool
PAGED = {
    "one_token": ((B, HQ, DH), False),
    "multi_query_t4": ((B, 4, HQ, DH), False),
    "one_token_partials": ((B, HQ, DH), True),
    "multi_query_t4_partials": ((B, 4, HQ, DH), True),
}


@pytest.mark.parametrize("case", sorted(PAGED))
def test_paged_attention_compiles(case, one_chip):
    q_shape, partials = PAGED[case]

    def fn(q, kp, vp, bt, ln, win, ok):
        return pa.paged_attention(q, kp, vp, bt, ln, win, fmt_kv=P8_2,
                                  page_ok=ok, partials=partials)
    compiled = _compile(fn, one_chip, (q_shape, jnp.float32), _pages(),
                        _pages(), ((B, M), jnp.int32), ((B,), jnp.int32),
                        ((1,), jnp.int32), ((B, M), jnp.int32))
    assert _kernels(compiled) == {"paged_attention"}


# (chunk bucket, history staged from an all-gathered global pool)
PREFILL = {"chunk64": (64, False), "chunk1": (1, False),
           "chunk64_sharded_pool": (64, True)}


@pytest.mark.parametrize("case", sorted(PREFILL))
def test_prefill_attention_compiles(case, one_chip):
    C, global_hist = PREFILL[case]

    def fn(q, k, v, kp, vp, bt, st, win, *hist):
        kw = {}
        if global_hist:
            kw = dict(hist_pool_k=hist[0], hist_pool_v=hist[1],
                      hist_bt=hist[2], page_ok=hist[3])
        return pf.prefill_attention_paged(
            q, k, v, kp, vp, bt, st, win, fmt_kv=P8_2,
            compute_dtype=jnp.bfloat16, flash_chunk=1024, **kw)
    shapes = [((B, C, HQ, DH), jnp.bfloat16), ((B, C, HKV, DH), jnp.bfloat16),
              ((B, C, HKV, DH), jnp.bfloat16), _pages(), _pages(),
              ((B, M), jnp.int32), ((B,), jnp.int32), ((1,), jnp.int32)]
    if global_hist:
        shapes += [_pages(), _pages(), ((B, M), jnp.int32),
                   ((B, M), jnp.int32)]
    compiled = _compile(fn, one_chip, *shapes)
    assert _kernels(compiled) == {"prefill_attention_paged"}


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "top_k"])
def test_decode_sample_compiles_with_tpu_v_block(greedy, one_chip):
    """The tied head at the v_block ops resolves on a TPU: the committed
    autotune cache holds no TPU entry, so the kernel default applies."""
    committed = autotune.AutotuneCache.load(autotune.DEFAULT_CACHE_PATH)
    tpu_cache = autotune.AutotuneCache("tpu", committed.entries)
    assert tpu_cache.lookup("decode_sample", (B, D, V), (P16_2,)) is None
    assert pa.default_v_block(V, D) < V

    def fn(x, w, noise):
        return pa.decode_sample(x, w, None if greedy else noise,
                                jnp.float32(0.7), plan="fused", fmt_w=P16_2,
                                transpose=True, greedy=greedy,
                                top_k=0 if greedy else 8)
    compiled = _compile(fn, one_chip, ((B, D), jnp.bfloat16),
                        ((V, D), jnp.int16), ((B, V), jnp.float32))
    assert _kernels(compiled) == {"decode_sample"}


# published heads whose vocabulary no power-of-two tile divides: the
# default tile leaves a masked partial last tile (qwen3-moe's [D, V] head;
# paligemma's tied [V, D] embedding)
ODD_HEADS = {"qwen3_moe_V151936_D4096": (151936, 4096, False),
             "paligemma_V257216_D2048": (257216, 2048, True)}


@pytest.mark.parametrize("case", sorted(ODD_HEADS))
def test_decode_sample_compiles_masked_last_tile(case, one_chip):
    V_, D_, transpose = ODD_HEADS[case]
    assert V_ % pa.default_v_block(V_, D_)

    def fn(x, w, noise):
        return pa.decode_sample(x, w, noise, jnp.float32(0.7), plan="fused",
                                fmt_w=P16_2, transpose=transpose, top_k=8)
    w_shape = (V_, D_) if transpose else (D_, V_)
    compiled = _compile(fn, one_chip, ((B, D_), jnp.bfloat16),
                        (w_shape, jnp.int16), ((B, V_), jnp.float32))
    assert _kernels(compiled) == {"decode_sample"}
