"""Random weights from the seed, made on the device in one jitted call.

The tree has the shapes and dtypes the program serves (its packed
parameter specs): integer leaves are P(16,2) weight codes, the rest are
float32.  Values follow the configuration file's `init` table, keyed by a
leaf's name; integer leaves use its "codes" entry.  The reference reads
the same tree and decodes the codes with the benchmark's own codec, so
nothing it uses was made by the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import posit_ref


def _leaf(key, shape, dtype, rule, fmt):
    kind, *args = rule
    if jnp.issubdtype(dtype, jnp.integer):
        if kind != "fan_in":
            raise ValueError(f"code leaves take a fan_in rule, got {rule}")
        std = args[0] / jnp.sqrt(float(shape[-2]))
        x = std * jax.random.normal(key, shape, jnp.float32)
        return posit_ref.codes_from_values(x, *fmt, dtype=dtype)
    if kind == "normal":
        return args[0] * jax.random.normal(key, shape, dtype)
    raise ValueError(f"unknown init rule {rule}")


def build(spec_tree, init: dict, seed: int, fmt=(16, 2)):
    """spec_tree: pytree of objects with .shape/.dtype (the program's
    packed parameter specs).  Returns the tree on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=lambda s: hasattr(s, "shape")
        and hasattr(s, "dtype"))
    plan = []
    for path, spec in leaves:
        name = str(getattr(path[-1], "key", path[-1]))
        dtype = jnp.dtype(spec.dtype)
        rule = init["codes"] if jnp.issubdtype(dtype, jnp.integer) \
            else init.get(name)
        if rule is None:
            raise KeyError(f"no init rule for weight leaf {name!r}")
        plan.append((tuple(spec.shape), dtype, tuple(rule)))

    def make(key):
        return [_leaf(jax.random.fold_in(key, i), shape, dtype, rule, fmt)
                for i, (shape, dtype, rule) in enumerate(plan)]

    fn = jax.jit(make)
    out = fn(jax.random.key(seed))
    jax.block_until_ready(out)
    return jax.tree.unflatten(treedef, out)
