"""Logical-axis sharding: one rule table drives DP / FSDP / TP / EP / SP.

Every parameter and activation in `repro.models` is annotated with *logical*
axis names; this module maps them onto physical mesh axes, dropping any
assignment whose dimension is not divisible by the mesh axis (so the same
model code runs on 1 device, a 16x16 pod, or a 2x16x16 multi-pod mesh).

Physical axes:
  pod   : slowest interconnect (inter-pod DCN/ICI) — data parallel only
  data  : in-pod data parallel + FSDP parameter sharding
  model : tensor/expert parallel

Rule highlights (1000+-chip posture):
  batch        -> (pod, data)   activations data-parallel across everything
  heads/mlp/
  vocab/expert -> model         tensor & expert parallelism
  embed/ffout  -> data          ZeRO-3/FSDP: parameters sharded over the DP
                                axis, all-gathered by XLA at use site
  kv_seq       -> model         sequence-parallel KV cache for long decode
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes). None = replicate.
DEFAULT_RULES: dict = {
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": "model",        # sequence-sharded KV cache (long-context decode)
    "kv_pages": "model",      # paged KV: the page pool shards over the same
                              # axis as kv_seq (a page is a sequence block)
    "embed": "data",          # FSDP shard of params' d_model dim
    "embed_act": None,        # activations keep embed replicated (TP gathers)
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_cap": "data",
    "expert_mlp": None,
    "vocab": "model",
    "layers": None,
    "ssm_state": None,
    "ssm_heads": "model",
    "conv": None,
    "stack": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: tuple = tuple(sorted(DEFAULT_RULES.items()))

    def as_dict(self):
        return dict(self.rules)

    def replace(self, **kw):
        d = self.as_dict()
        d.update(kw)
        return ShardingRules(tuple(sorted(d.items())))


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def spec_for(shape: Sequence[int], logical_axes: Sequence[Optional[str]],
             mesh: Mesh, rules: ShardingRules = ShardingRules()) -> P:
    """Build a PartitionSpec, dropping assignments that don't divide evenly
    or that reference axes missing from this mesh (e.g. 'pod' on 1 pod)."""
    table = rules.as_dict()
    used = set()
    entries = []
    for dim, name in zip(shape, logical_axes):
        phys = table.get(name) if name is not None else None
        if phys is None:
            entries.append(None)
            continue
        phys_t = tuple(a for a in (phys if isinstance(phys, (tuple, list)) else (phys,))
                       if a in mesh.shape and a not in used)
        size = 1
        for a in phys_t:
            size *= mesh.shape[a]
        if size <= 1 or dim % size != 0:
            # retry with a shrinking prefix (e.g. (pod,data) -> (pod,))
            while phys_t and (size <= 1 or dim % size != 0):
                phys_t = phys_t[:-1]
                size = 1
                for a in phys_t:
                    size *= mesh.shape[a]
        if not phys_t or size <= 1 or dim % size != 0:
            entries.append(None)
            continue
        used.update(phys_t)
        entries.append(phys_t if len(phys_t) > 1 else phys_t[0])
    return P(*entries)


def sharding_for(shape, logical_axes, mesh, rules=ShardingRules()) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, logical_axes, mesh, rules))


def mesh_axes_for(logical: str, mesh: Mesh,
                  rules: ShardingRules = ShardingRules()) -> tuple:
    """Physical mesh axes the rule table maps `logical` onto, restricted to
    axes actually present in this mesh (absent axes — e.g. 'model' on a
    data-only mesh — are dropped, the same fallback `spec_for` applies)."""
    phys = rules.as_dict().get(logical)
    if phys is None:
        return ()
    phys_t = phys if isinstance(phys, (tuple, list)) else (phys,)
    return tuple(a for a in phys_t if a in mesh.shape)


def mesh_axis_size(logical: str, mesh: Mesh,
                   rules: ShardingRules = ShardingRules()) -> int:
    """Total device count a `logical` axis shards over on this mesh (1 when
    its physical axes are absent — the replicate fallback)."""
    out = 1
    for a in mesh_axes_for(logical, mesh, rules):
        out *= mesh.shape[a]
    return out


def shard_map(f, mesh, in_specs, out_specs):
    """Fully-manual shard_map over every mesh axis.  Used by the
    compressed train step (train/step.py) and the sharded serving engine
    (serve/engine.py)."""
    return jax.shard_map(f, mesh=mesh, axis_names=set(mesh.axis_names),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Run jitted programs on `mesh`, with its axes visible to `constrain`.

    (`jax.set_mesh` does both too, but in JAX 0.9 it also maps every jit
    output's sharding back onto the mesh, which fails for some shardings
    the partitioner picks.)"""
    with mesh, jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        yield


def constrain(x, logical_axes, mesh: Optional[Mesh] = None,
              rules: ShardingRules = ShardingRules()):
    """with_sharding_constraint by logical axes; no-op without a mesh.

    The mesh defaults to the abstract mesh `use_mesh` installed.  Axes that are
    Manual in the current context (inside a shard_map) are dropped from
    the spec — the surrounding shard_map already owns them."""
    mesh = mesh or jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return x
    manual = {name for name, t in zip(mesh.axis_names, mesh.axis_types)
              if t == AxisType.Manual}
    spec = spec_for(x.shape, logical_axes, mesh, rules)
    if manual:
        cleaned = []
        for entry in spec:
            if entry is None:
                cleaned.append(None)
            elif isinstance(entry, tuple):
                kept = tuple(a for a in entry if a not in manual)
                cleaned.append(kept if kept else None)
            else:
                cleaned.append(None if entry in manual else entry)
        spec = P(*cleaned)
        if all(e is None for e in spec):
            return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_specs(spec_tree, mesh, rules=ShardingRules()):
    """Map a pytree of (shape, logical_axes) ParamSpecs (see models.module)
    to a pytree of NamedShardings."""
    from repro.models.module import ParamSpec  # local import to avoid cycle

    def one(ps):
        return sharding_for(ps.shape, ps.logical_axes, mesh, rules)

    return jax.tree.map(one, spec_tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))
