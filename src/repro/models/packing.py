"""One-shot param packing: float checkpoints -> posit-code weight arrays.

The paper's storage win — P(n<=16) weights in int8/int16 containers — only
materializes if the *checkpoint* holds codes and the serving matmul decodes
them in-kernel (`kernels/dispatch.py`, execution='fused').  This module is
the conversion pass:

    params_packed = pack_params(params, cfg)        # float -> codes
    mgr.save(step, params_packed, extra=pack_manifest(cfg))
    ...
    engine = ServingEngine.from_checkpoint(cfg, dir, ...)   # serves codes

Only weights consumed through the GEMM dispatch layer are packed (per
family, below) — dense projections via `qdot`, routed MoE expert stacks
(we_*) via `qdot_grouped`, SSM in/out projections via `qdot`.  Other leaves
— norms, embeddings read by jnp.take, routers, conv taps, SSM scan params —
stay float.  Packing is one rounding per weight (posit encode), identical
to what fake_quant applies on the fly, so a packed model served fused
computes the same quantized function.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core.formats import PositFormat
from .config import ModelConfig
from .module import ParamSpec

# weight-leaf names consumed via the GEMM dispatch layer, by sub-family.
_ATTN_NAMES = ("wq", "wk", "wv", "wo")
_MLP_NAMES = ("wi_gate", "wi_up", "wo_mlp")
_EXPERT_NAMES = ("we_gate", "we_up", "we_down")   # stacked: qdot_grouped
_SHARED_EXPERT_NAMES = ("ws_gate", "ws_up", "ws_down")
_SSM_NAMES = ("in_proj", "out_proj")

_SUPPORTED_FAMILIES = ("dense", "encoder", "vlm", "moe", "ssm", "hybrid")


def packable_paths(cfg: ModelConfig) -> Tuple[Tuple[str, ...], ...]:
    """Paths (key tuples) of the weight leaves that pack to posit codes."""
    fam = cfg.family
    if fam in ("dense", "encoder", "vlm"):
        paths = [("layers", n) for n in _ATTN_NAMES + _MLP_NAMES]
    elif fam == "moe":
        names = _ATTN_NAMES + _EXPERT_NAMES
        if cfg.n_shared_experts:
            names += _SHARED_EXPERT_NAMES
        paths = [("layers", n) for n in names]
    elif fam == "ssm":
        paths = [("layers", n) for n in _SSM_NAMES]
    elif fam == "hybrid":
        # jamba-style blocks: attention + mamba + dense-FFN + MoE sub-trees
        paths = [("blocks", "attn", n) for n in _ATTN_NAMES]
        paths += [("blocks", "mamba", n) for n in _SSM_NAMES]
        paths += [("blocks", "ffn", n) for n in _MLP_NAMES]
        paths += [("blocks", "moe", n) for n in _EXPERT_NAMES]
    else:
        raise NotImplementedError(
            f"param packing not supported for family '{fam}' "
            f"(have {sorted(_SUPPORTED_FAMILIES)})")
    if not cfg.tie_embeddings:
        paths.append(("head",))
    return tuple(paths)


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree[k]
    tree[path[-1]] = value


def _copy_tree(tree):
    if isinstance(tree, dict):
        return {k: _copy_tree(v) for k, v in tree.items()}
    return tree


def pack_params(params, cfg: ModelConfig, fmt: PositFormat = None):
    """Replace every packable float weight with posit codes (int8/int16).

    One rounding per weight — the same rounding fake_quant applies on every
    forward pass, applied once at conversion time instead.
    """
    fmt = fmt or cfg.quant.weights
    if fmt is None:
        raise ValueError("pack_params needs a weights format "
                         "(cfg.quant.weights or explicit fmt)")
    packed = _copy_tree(params)
    for path in packable_paths(cfg):
        leaf = _get(params, path)
        _set(packed, path, posit.pack(jnp.asarray(leaf, jnp.float32), fmt))
    return packed


def init_packed(rng, cfg: ModelConfig, fmt: PositFormat = None,
                sharding=None):
    """`pack_params(api.init(rng, cfg), cfg, fmt)`, built one leaf at a time.

    Each leaf is initialised from its own RNG stream exactly as `api.init`
    does, and a packable one is packed (in one jitted program) before the
    next leaf exists, so only that leaf's float transient is ever live —
    never the float tree, nor float and codes side by side.  This is what
    lets a model whose float masters exceed device memory (gemma3-4b:
    14.45 GiB of f32 masters, 8.48 GiB packed) be served from a seed on
    one 16 GiB chip.  Bitwise equal to packing the float init.

    `sharding` (e.g. replicated over a serving mesh) places each finished
    leaf as it is built, so the tree never sits whole on one device before
    it is spread out."""
    from . import api
    from .module import init_leaf, leaf_keys

    fmt = fmt or cfg.quant.weights
    if fmt is None:
        raise ValueError("init_packed needs a weights format "
                         "(cfg.quant.weights or explicit fmt)")
    specs = api.param_specs(cfg)
    packable = set(packable_paths(cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    out = []
    for key, (path, spec) in zip(leaf_keys(rng, specs), flat):
        leaf = init_leaf(key, spec)
        if tuple(k.key for k in path) in packable:
            leaf = _pack_jit(leaf, fmt)
        if sharding is not None:
            leaf = jax.device_put(leaf, sharding)
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


_pack_jit = jax.jit(posit.pack, static_argnames="fmt")


def unpack_params(params, cfg: ModelConfig, fmt: PositFormat = None,
                  dtype=jnp.float32):
    """Inverse of pack_params: decode code leaves back to float arrays."""
    fmt = fmt or cfg.quant.weights
    if fmt is None:
        raise ValueError("unpack_params needs a weights format")
    out = _copy_tree(params)
    for path in packable_paths(cfg):
        leaf = _get(params, path)
        _set(out, path, posit.unpack(leaf, fmt, dtype=dtype))
    return out


def packed_param_specs(cfg: ModelConfig, fmt: PositFormat = None):
    """param_specs with packable leaves re-typed to the code storage dtype —
    the `like` tree for restoring a packed checkpoint (checkpoint.restore)."""
    from . import api

    fmt = fmt or cfg.quant.weights
    if fmt is None:
        raise ValueError("packed_param_specs needs a weights format")
    storage = {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[fmt.storage_bits]
    out = _copy_tree(api.param_specs(cfg))
    for path in packable_paths(cfg):
        spec = _get(out, path)
        _set(out, path, spec._replace(dtype=storage))
    return out


def pack_manifest(cfg: ModelConfig, fmt: PositFormat = None) -> dict:
    """Checkpoint `extra` metadata marking a packed-weights checkpoint."""
    fmt = fmt or cfg.quant.weights
    if fmt is None:
        raise ValueError("pack_manifest needs a weights format "
                         "(cfg.quant.weights or explicit fmt)")
    return {"packed_weights": True, "weights_format": str(fmt),
            "weights_n": fmt.n, "weights_es": fmt.es}


def weight_bytes(params) -> int:
    """Total weight storage footprint (the HBM-resident bytes for weights)."""
    return int(sum(x.nbytes for x in jax.tree.leaves(params)))
