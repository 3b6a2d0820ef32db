"""Unified model API — dispatch by config family.

Every architecture exposes the same five entry points:
    param_specs(cfg)                      -> pytree[ParamSpec]
    init(rng, cfg)                        -> params
    apply(params, batch, cfg)             -> logits          (train/encode)
    prefill(params, batch, cfg, max_seq)  -> (logits, cache) (serving)
    decode_step(params, tokens, cache, cfg) -> (logits, cache')
plus `input_specs(cfg, shape)` producing allocation-free ShapeDtypeStructs
for the dry-run, and `cache_specs` for decode-state dry-runs.

Posit-packed checkpoints (re-exported from `packing`): `pack_params` /
`unpack_params` convert qdot weights to/from posit code arrays,
`init_packed` builds the packed tree straight from a seed,
`packed_param_specs` types the restore tree, `pack_manifest` tags the
checkpoint.  apply/prefill/decode_step accept packed params transparently
(the GEMM dispatch layer detects code containers).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import transformer, moe, mamba, hybrid
from .config import ModelConfig, ShapeConfig
from .module import ParamSpec, abstract_params, init_params
from .packing import (pack_params, unpack_params, packed_param_specs,  # noqa: F401
                      pack_manifest, weight_bytes, init_packed)
from .paged import PagedLayout  # noqa: F401  (re-exported serving layout)


def _mod(cfg: ModelConfig):
    return {
        "dense": transformer,
        "encoder": transformer,
        "vlm": transformer,
        "moe": moe,
        "ssm": mamba,
        "hybrid": hybrid,
    }[cfg.family]


def param_specs(cfg: ModelConfig):
    return _mod(cfg).param_specs(cfg)


def init(rng, cfg: ModelConfig):
    return init_params(rng, param_specs(cfg))


def apply(params, batch, cfg: ModelConfig, **kw):
    return _mod(cfg).apply(params, batch, cfg, **kw)


def prefill(params, batch, cfg: ModelConfig, max_seq: Optional[int] = None):
    if cfg.is_encoder:
        raise ValueError(f"{cfg.name} is encoder-only: no autoregressive serving")
    return _mod(cfg).prefill(params, batch, cfg, max_seq=max_seq)


def decode_step(params, tokens, cache, cfg: ModelConfig, shard=None):
    """shard: optional paged.PageShard when the paged KV pool is sharded
    along kv_pages and this call runs inside a shard_map over that axis
    (block tables hold global page ids; see models/paged.py)."""
    if cfg.is_encoder:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    return _mod(cfg).decode_step(params, tokens, cache, cfg, shard=shard)


def sample_noise(keys, vocab_size: int):
    """Per-slot standard-gumbel noise [B, V] for the fused decode step —
    exactly what `jax.random.categorical(key, logits)` draws internally,
    so `argmax(noise + logits/T)` replays the decomposed sampler bitwise."""
    return jax.vmap(
        lambda kk: jax.random.gumbel(kk, (vocab_size,), jnp.float32))(keys)


def decode_and_sample(params, tokens, cache, cfg: ModelConfig, noise,
                      temperature, *, greedy: bool, top_k: int, shard=None):
    """One-program decode step: attention + logits head + sampling epilogue
    in a single device dispatch.  Returns ([B] int32 tokens, cache') —
    bit-identical to `decode_step` followed by the engine sampler (the
    model's sample_head replays the head qdot plan and the temperature /
    top-k / gumbel-argmax sampler inside one Pallas program).

    noise: [B, V] f32 gumbel rows from `sample_noise` (None when greedy);
    temperature: f32 scalar (ignored when greedy)."""
    if cfg.is_encoder:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    from . import common
    spec = common.SampleSpec(noise=noise, temperature=temperature,
                             greedy=greedy, top_k=top_k)
    return _mod(cfg).decode_step(params, tokens, cache, cfg, shard=shard,
                                 sample=spec)


def decode_verify(params, tokens, cache, cfg: ModelConfig, noise,
                  temperature, *, greedy: bool, top_k: int, shard=None):
    """k-token speculative verify: tokens [B, T] (committed next token +
    T-1 draft proposals per slot) run as ONE batched multi-query paged-
    attention dispatch under the serve policy.  Returns ([B, T] int32
    target tokens, cache' with length + T) — row (b, j) is bitwise the
    token T sequential `decode_and_sample` calls would have emitted at
    that position given the same inputs, so callers accept the longest
    draft prefix that matches and roll back the rest on the host.

    noise: [B*T, V] f32 gumbel rows from `sample_noise` over per-(slot,
    draw-index) keys, b-major (None when greedy); temperature: f32 scalar
    (ignored when greedy)."""
    if cfg.is_encoder:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    mod = _mod(cfg)
    if not hasattr(mod, "decode_verify"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no k-token verify step")
    from . import common
    spec = common.SampleSpec(noise=noise, temperature=temperature,
                             greedy=greedy, top_k=top_k)
    return mod.decode_verify(params, tokens, cache, cfg, shard=shard,
                             sample=spec)


def prefill_chunk(params, tokens, cache, slot, cfg: ModelConfig, shard=None):
    """Process one prompt chunk [1, C] for one slot of a serving cache
    (dense or paged) at positions length[slot] + [0, C).  The serving
    engine's chunked-prefill path: fixed bucketed chunk shapes instead of
    a retrace per prompt length, writes straight into the slot's cache/
    pages instead of a whole-cache splice.  shard: optional kv_pages
    PageShard (inside a shard_map; see decode_step)."""
    if cfg.is_encoder:
        raise ValueError(f"{cfg.name} is encoder-only: no autoregressive serving")
    return _mod(cfg).prefill_chunk(params, tokens, cache, slot, cfg,
                                   shard=shard)


def prefill_chunk_batched(params, tokens, cache, active, cfg: ModelConfig,
                          shard=None):
    """Cross-slot batched chunked prefill: advance every active slot by one
    same-size chunk in a single [B, C] program.  tokens: [B, C] int32
    (inactive rows are padding); active: [B] bool.  The caller zeroes
    inactive rows' length/block-table metadata (paged writes land on the
    trash page); inactive rows of batch-dim state (dense KV, SSM/conv) are
    reverted internally.  One compile per chunk bucket — the serving
    engine's batched-prefill path.  shard: optional kv_pages PageShard
    (inside a shard_map; see decode_step).  Returns (last-position logits
    [B, V], cache')."""
    if cfg.is_encoder:
        raise ValueError(f"{cfg.name} is encoder-only: no autoregressive serving")
    return _mod(cfg).prefill_chunk_batched(params, tokens, cache, active, cfg,
                                           shard=shard)


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int,
                layout: Optional[PagedLayout] = None):
    return _mod(cfg).cache_specs(cfg, batch, max_seq, layout)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               layout: Optional[PagedLayout] = None):
    return _mod(cfg).init_cache(cfg, batch, max_seq, layout)


# ---------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStruct stand-ins, never allocated)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig, *, with_labels=True):
    """Model inputs for one assigned shape cell.

    train/prefill: full [B, S] token batch (plus stub-frontend embeddings
    for audio/vlm, which replace/augment part of the sequence).
    decode: one token per row; the KV/SSM cache comes from cache_specs.
    """
    B, S = shape.global_batch, shape.seq_len
    batch = {}
    if shape.kind == "decode":
        batch["tokens"] = jax.ShapeDtypeStruct((B,), jnp.int32)
        return batch
    if cfg.frontend == "audio_stub":
        batch["frames"] = jax.ShapeDtypeStruct((B, S, cfg.frontend_dim), jnp.float32)
    elif cfg.frontend == "vision_stub":
        batch["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_tokens, cfg.frontend_dim), jnp.float32)
        batch["tokens"] = jax.ShapeDtypeStruct((B, S - cfg.frontend_tokens), jnp.int32)
    else:
        batch["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if with_labels and shape.kind == "train":
        batch["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return batch


def abstract_state(cfg: ModelConfig):
    return abstract_params(param_specs(cfg))
