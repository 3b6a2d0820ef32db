"""Shared transformer building blocks (pure JAX, posit-quant aware).

Every matmul routes through `qdot`, which hands off to the posit GEMM
dispatch layer (`kernels/dispatch.py`): the QuantPolicy's execution plan
decides whether the dot fake-quantizes on float (training), runs the fused
Pallas kernel over posit codes (serving — weights packed, and activations
too when the policy sets an activation format), or runs the bit-exact
chunked-PDPU kernel (validation).  Both fake_quant and fused are trainable:
the fused plan carries a custom_vjp STE backward, so QAT can run the packed
kernel forward end to end.  All plans keep the PDPU contract —
low-precision posit operands, wide f32 accumulation.

Attention is a flash-style streaming softmax over KV chunks (lax.scan), so
prefill_32k never materializes an S x S score matrix; sliding-window layers
restrict work to the diagonal band.  KV caches may be stored as posit codes
(int8/int16) per the QuantPolicy — decoded exactly on read.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core.quant import QuantPolicy
from repro.kernels import dispatch, ops
from repro.parallel import sharding
from .config import ModelConfig

_NEG = -2.0e38


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def qdot(x, w, policy: QuantPolicy, prec_dtype=jnp.float32):
    """Posit-quantized matmul with wide accumulation (PDPU semantics).

    x: [..., K] activations; w: [K, N] weights — float masters or packed
    posit codes.  The execution plan (policy.execution) picks the datapath;
    see kernels/dispatch.py.  Every plan accumulates wide (f32) — the fused
    wide-accumulator property.

    prec_dtype is the *HLO output dtype* of the fake_quant dot: on TPU the
    MXU always accumulates f32 internally, but when the contraction dim is
    TP-sharded the dot output dtype is what the partial-sum all-reduce
    ships.  Models pass the compute dtype here when cfg.tp_bf16_reduce is on.
    """
    return dispatch.qdot(x, w, policy, prec_dtype=prec_dtype)


def qdot_grouped(x, w, policy: QuantPolicy, prec_dtype=jnp.float32,
                 out_dtype=None):
    """Grouped qdot over stacked expert weights (MoE expert einsums).

    x: [E, C, K] or [B, E, Cg, K]; w: [E, K, N] — float masters or packed
    posit codes.  Same plan semantics as `qdot`, per expert; the fused plan
    runs the batched Pallas kernel so EP serving reads expert stacks as
    int8/int16 codes.  See kernels/dispatch.qdot_grouped.
    """
    return dispatch.qdot_grouped(x, w, policy, prec_dtype=prec_dtype,
                                 out_dtype=out_dtype)


def tp_prec(cfg) -> jnp.dtype:
    """Output dtype for TP-contracted projections (see qdot)."""
    return cfg.compute_dtype if cfg.tp_bf16_reduce else jnp.float32


def wgather(cfg, w, tp_axes):
    """Weight-gather FSDP: re-constrain a weight to TP-only sharding right
    before its matmul, so the FSDP shard is all-gathered (in the compute
    dtype) rather than resolved by partial-summing activation-sized f32
    tensors across the data axis (cfg.fsdp_gather_weights)."""
    if not cfg.fsdp_gather_weights:
        return w
    return sharding.constrain(w, tp_axes)


def rms_norm(x, scale, eps=1e-6, upcast=True):
    """RMSNorm. The variance reduction is always f32; with upcast=False the
    full-tensor normalize runs in x.dtype — no f32 activation tensor is
    materialized, so SPMD all-reduces of the producing dot stay bf16
    (used when ModelConfig.tp_bf16_reduce is on)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    if upcast:
        out = x.astype(jnp.float32) * inv
        return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
    out = x * inv.astype(x.dtype)
    return out * (1.0 + scale).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embeddings. x: [..., S, H, D]; positions: [..., S]."""
    d = x.shape[-1]
    half = d // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # [..., S, half]
    ang = ang[..., :, None, :]  # broadcast over heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x, cap: float):
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, q_pos, kv_pos, *, causal: bool,
                    window: Optional[int], chunk_k: int = 1024,
                    softcap_val: float = 0.0):
    """Streaming-softmax attention over KV chunks (never S x S resident).

    q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D]; GQA via Hq = G * Hkv.
    q_pos: [B, Sq], kv_pos: [B, Skv] absolute positions for masking.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    # heads-leading layout with query rows ordered (query, group): the
    # layout the fused prefill kernel computes in, so both agree bitwise
    qg = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Hkv, Sq * G, D).astype(jnp.float32) * scale
    qp = jnp.repeat(q_pos, G, axis=1)  # [B, Sq*G] position of each row

    ck = min(chunk_k, Skv)
    n_chunks = -(-Skv // ck)
    pad = n_chunks * ck - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    # [n_chunks, B, Hkv, ck, D] / [n_chunks, B, ck]
    kc = k.reshape(B, n_chunks, ck, Hkv, D).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(B, n_chunks, ck, Hkv, D).transpose(1, 0, 3, 2, 4)
    pc = jnp.moveaxis(kv_pos.reshape(B, n_chunks, ck), 1, 0)

    def step(carry, blk):
        m, l, o = carry
        kb, vb, pb = blk  # [B, Hkv, ck, D], [B, ck]
        s = jnp.einsum("bhqd,bhkd->bhqk", qg, kb.astype(jnp.float32))
        s = softcap(s, softcap_val)
        mask = pb[:, None, None, :] >= 0
        if causal:
            mask &= qp[:, None, :, None] >= pb[:, None, None, :]
        if window is not None:
            mask &= (qp[:, None, :, None] - pb[:, None, None, :]) < window
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        o_new = o * corr[..., None] + pv
        return (m_new, l_new, o_new), None

    m0 = jnp.full((B, Hkv, Sq * G), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, Sq * G), jnp.float32)
    o0 = jnp.zeros((B, Hkv, Sq * G, D), jnp.float32)
    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0), (kc, vc, pc))
    o = o / jnp.maximum(l, 1e-30)[..., None]
    out = o.reshape(B, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4) \
           .reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, kv_pos, *,
                     window: Optional[int], softcap_val: float = 0.0):
    """Single-token attention over a (possibly posit-coded) KV cache.

    q: [B, 1, Hq, D]; k_cache/v_cache: [B, S, Hkv, D] floats (already
    decoded by the caller if stored as posit); cache_len: [B] valid length.

    The cache's sequence dim is sharded over the 'model' axis (kv_seq); the
    score/softmax path is constrained to keep that sharding so each shard
    attends over its local cache slice (flash-decode style: XLA emits the
    tiny max/sum partial reductions instead of all-gathering the cache).
    """
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    k_cache = sharding.constrain(k_cache, ("batch", "kv_seq", None, None))
    v_cache = sharding.constrain(v_cache, ("batch", "kv_seq", None, None))
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32))
    s = softcap(s, softcap_val)
    s = sharding.constrain(s, ("batch", None, None, "kv_seq"))
    q_pos = cache_len[:, None]  # this token's position == #valid entries
    mask = kv_pos < q_pos  # [B, S]
    if window is not None:
        mask &= (q_pos - kv_pos) <= window
    s = jnp.where(mask[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = sharding.constrain(p, ("batch", None, None, "kv_seq"))
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache with optional posit storage (QuantPolicy.kv_cache)
# ---------------------------------------------------------------------------

def kv_store_dtype(cfg: ModelConfig):
    fmt = cfg.quant.kv_cache
    if fmt is None:
        return cfg.compute_dtype
    return {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[fmt.storage_bits]


def kv_encode(cfg: ModelConfig, x):
    fmt = cfg.quant.kv_cache
    if fmt is None:
        return x.astype(cfg.compute_dtype)
    return posit.pack(x, fmt)


def kv_decode(cfg: ModelConfig, x):
    fmt = cfg.quant.kv_cache
    if fmt is None:
        return x
    return posit.unpack(x, fmt, dtype=cfg.compute_dtype)


# ---------------------------------------------------------------------------
# embeddings / head / losses
# ---------------------------------------------------------------------------

def embed_tokens(emb, tokens, cfg: ModelConfig):
    x = jnp.take(emb, tokens, axis=0).astype(cfg.compute_dtype)
    return sharding.constrain(x, ("batch", None, "embed_act"))


def logits_head(x, emb_or_head, cfg: ModelConfig, transpose: bool):
    # the head historically quantizes only the weights — final hidden states
    # reach the vocab projection unquantized regardless of the policy (under
    # activation-coded fused serving the head therefore takes the
    # float-activation fast path while the trunk runs both operands coded)
    policy = cfg.quant
    if policy.activations is not None:
        policy = dataclasses.replace(policy, activations=None)
    w = emb_or_head
    if transpose:  # tied embedding [V, D] -> project with its transpose
        w = w.T    # lossless for packed posit codes too (pure reindexing)
    out = dispatch.qdot(x, w, policy, prec_dtype=jnp.float32,
                        out_dtype=jnp.float32)
    out = softcap(out, cfg.logit_softcap)
    return sharding.constrain(out, ("batch", None, "vocab"))


@dataclasses.dataclass
class SampleSpec:
    """Sampling epilogue parameters for the fused one-program decode step.

    Constructed inside the engine's jit'd decode function (never crosses a
    jit boundary, so no pytree registration): `noise` is per-slot standard
    gumbel [B, V] (None when greedy — categorical(key, l) == argmax of
    gumbel + l), `temperature` a traced f32 scalar, `greedy`/`top_k` static.
    """
    noise: Optional[jax.Array]
    temperature: jax.Array
    greedy: bool
    top_k: int


def sample_head(x, emb_or_head, cfg: ModelConfig, sample: SampleSpec,
                transpose: bool):
    """Fused replacement for `logits_head` + the serving sampler.

    Replays logits_head's head qdot plan (weights-only quantization, f32
    accumulate, logit softcap) and the temperature/top-k/gumbel sampler in
    one Pallas program (ops.decode_sample), streaming the vocab axis so the
    [B, V] logits never round-trip through HBM.  Bit-identical tokens to
    the two-program logits_head -> sampler path.

    x: [B, D] hidden rows (one decode token per slot).  The head weights
    stay untransposed — the kernel transposes per vocab tile, which commutes
    with the elementwise decode.  bit_exact plans have no fused head
    (the engine keeps the decomposed path there).
    """
    policy = cfg.quant
    w = emb_or_head
    fmt_w = policy.weights
    if policy.execution == "fake_quant":
        plan = "fake_quant"
        if not dispatch.is_packed(w):
            # float masters: qdot fake-quantizes the weights on float before
            # the dot (elementwise, so it commutes with the in-kernel
            # transpose) and the kernel sees plain float weights
            w = policy.maybe_quant_weight(w.astype(x.dtype))
            fmt_w = None
    elif policy.execution == "fused":
        plan = "fused"
        if not dispatch.is_packed(w) and fmt_w is not None:
            # the STE forward: encode float masters once, decode in-kernel
            # (ops._ste_primal's matmul_posit_weights path)
            w = ops.encode(w.astype(jnp.float32), fmt_w)
    else:
        raise ValueError(f"no fused decode head for execution plan "
                         f"{policy.execution!r}")
    return ops.decode_sample(
        x, w, sample.noise, sample.temperature, plan=plan, fmt_w=fmt_w,
        transpose=transpose, greedy=sample.greedy, top_k=sample.top_k,
        softcap_val=cfg.logit_softcap)


def cross_entropy(logits, labels, mask=None):
    """Stable CE over a (possibly vocab-sharded) logits tensor. f32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
