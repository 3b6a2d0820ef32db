"""Minimal from-scratch parameter system (no flax/haiku available).

A model is three pure things:
  * ``param_specs(cfg) -> pytree[ParamSpec]``  (shapes/dtypes/logical axes/init)
  * ``init(rng, cfg)   -> pytree[jnp.ndarray]`` (materialize the specs)
  * ``apply(params, inputs, cfg) -> outputs``

ParamSpecs make the multi-pod dry-run allocation-free: shardings and
ShapeDtypeStructs come straight from the specs, no tracing or host memory.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamSpec(NamedTuple):
    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | fan_in | embed
    dtype: Any = jnp.float32

    def abstract(self):
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def _is_spec(x):
    return isinstance(x, ParamSpec)


def abstract_params(specs):
    """pytree[ParamSpec] -> pytree[ShapeDtypeStruct] (no allocation)."""
    return jax.tree.map(lambda s: s.abstract(), specs, is_leaf=_is_spec)


def init_leaf(key, s: ParamSpec):
    """Materialize one spec from its RNG stream."""
    if s.init == "zeros":
        return jnp.zeros(s.shape, s.dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, s.dtype)
    if s.init == "normal":
        return (0.02 * jax.random.normal(key, s.shape)).astype(s.dtype)
    if s.init == "embed":
        return (1.0 * jax.random.normal(key, s.shape)).astype(s.dtype)
    if s.init == "fan_in":
        fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
        std = 1.0 / math.sqrt(fan_in)
        return (std * jax.random.normal(key, s.shape)).astype(s.dtype)
    if s.init.startswith("const:"):
        return jnp.full(s.shape, float(s.init.split(":")[1]), s.dtype)
    if s.init == "arange1":  # 1..n (Mamba A_log style)
        n = int(np.prod(s.shape))
        return jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)
                       ).reshape(s.shape).astype(s.dtype)
    raise ValueError(f"unknown init '{s.init}'")


def leaf_keys(rng, specs):
    """The per-leaf RNG streams `init_params` draws from, in flatten
    order of the spec tree."""
    return jax.random.split(rng, len(jax.tree.leaves(specs, is_leaf=_is_spec)))


def init_params(rng, specs):
    """Materialize a spec tree with deterministic per-leaf RNG streams."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = leaf_keys(rng, specs)
    return jax.tree.unflatten(treedef,
                              [init_leaf(k, s) for k, s in zip(keys, leaves)])


def param_count(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=_is_spec)
    return int(sum(np.prod(s.shape) for s in leaves))


def param_bytes(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=_is_spec)
    return int(sum(np.prod(s.shape) * jnp.dtype(s.dtype).itemsize for s in leaves))
