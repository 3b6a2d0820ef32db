"""Every assigned config has full packable-path coverage: no weight family
consumed through the GEMM dispatch layer is silently left float.

The invariant: any param-spec leaf whose name is a qdot/qdot_grouped-consumed
weight (attention/MLP projections, routed and shared expert stacks, SSM
in/out projections, the untied head) MUST appear in packable_paths(cfg).
Leaves consumed outside the dispatch layer (norms, routers, embeddings,
conv taps, SSM scan params, stub frontend projections) are exempt.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.formats import P16_2
from repro.core.quant import QuantPolicy
from repro.models import api, packing
from repro.models.module import ParamSpec

# every leaf name consumed via dispatch.qdot / dispatch.qdot_grouped
QDOT_WEIGHT_NAMES = frozenset({
    "wq", "wk", "wv", "wo",                    # attention projections
    "wi_gate", "wi_up", "wo_mlp",              # dense FFN
    "we_gate", "we_up", "we_down",             # routed expert stacks
    "ws_gate", "ws_up", "ws_down",             # shared experts
    "in_proj", "out_proj",                     # SSM projections
    "head",                                    # untied vocab head
})


def _spec_paths(tree, prefix=()):
    if isinstance(tree, ParamSpec):
        yield prefix
        return
    for k, v in tree.items():
        yield from _spec_paths(v, prefix + (k,))


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_packable_paths_cover_every_qdot_weight(name):
    cfg = configs.get_smoke(name).replace(quant=QuantPolicy(weights=P16_2))
    specs = api.param_specs(cfg)
    declared = set(packing.packable_paths(cfg))
    present = {p for p in _spec_paths(specs)}
    # 1) every declared packable path exists in the spec tree
    missing = declared - present
    assert not missing, f"{name}: packable paths absent from specs: {missing}"
    # 2) every qdot-weight leaf in the spec tree is declared packable
    qdot_leaves = {p for p in present if p[-1] in QDOT_WEIGHT_NAMES}
    unpacked = qdot_leaves - declared
    assert not unpacked, (
        f"{name}: weight families silently left float: {sorted(unpacked)}")
    # 3) something actually packs for every family
    assert declared, f"{name}: no packable paths at all"


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_pack_params_round_trip_types(name):
    """pack_params output agrees with packed_param_specs leaf for leaf
    (dtype + shape) — the contract from_checkpoint restores against."""
    cfg = configs.get_smoke(name).replace(quant=QuantPolicy(weights=P16_2))
    params = api.init(jax.random.key(0), cfg)
    packed = api.pack_params(params, cfg)
    abstract = jax.tree.map(
        lambda s: s.abstract(), api.packed_param_specs(cfg),
        is_leaf=lambda s: isinstance(s, ParamSpec))
    flat_p = jax.tree.leaves(packed)
    flat_a = jax.tree.leaves(abstract)
    assert len(flat_p) == len(flat_a)
    for arr, st in zip(flat_p, flat_a):
        assert arr.shape == st.shape
        assert arr.dtype == st.dtype
    # packed leaves really shrink the storage footprint
    assert api.weight_bytes(packed) < api.weight_bytes(params)
    # and decode back to exactly the quantized masters
    restored = api.unpack_params(packed, cfg)
    path = packing.packable_paths(cfg)[0]
    leaf, master = restored, params
    for k in path:
        leaf, master = leaf[k], master[k]
    from repro.core import posit
    want = posit.quantize(jnp.asarray(master, jnp.float32), P16_2)
    assert (np.asarray(leaf) == np.asarray(want)).all()


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_init_packed_equals_packed_init(name):
    """init_packed (leaf-at-a-time, never the whole float tree) builds
    bitwise the tree pack_params makes from the float init."""
    cfg = configs.get_smoke(name).replace(quant=QuantPolicy(weights=P16_2))
    want = api.pack_params(api.init(jax.random.key(3), cfg), cfg)
    got = api.init_packed(jax.random.key(3), cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_init_packed_places_each_leaf():
    """With a sharding, every leaf comes out placed on it (replicated over
    a serving mesh), with the same values as the unplaced build."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_serving_mesh
    cfg = configs.get_smoke("gemma3_4b").replace(
        quant=QuantPolicy(weights=P16_2))
    rep = NamedSharding(make_serving_mesh(1), PartitionSpec())
    want = api.init_packed(jax.random.key(3), cfg)
    got = api.init_packed(jax.random.key(3), cfg, sharding=rep)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.sharding == rep
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
