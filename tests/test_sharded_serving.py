"""Sharded paged posit-KV serving: cross-topology parity and invariants.

The page pool shards along kv_pages (each device owns a contiguous global
page-id range with its own budget; block tables keep global ids) and every
serving entry point runs under a fully-manual shard_map — see
models/paged.py for the id contract and serve/engine.py for the scheduler.

Three tiers here:

  * pure-host unit tests (any device count): PagedLayout global<->local
    id mapping with out-of-range/trash-page invariants, the sharded
    PageAllocator's per-device budgets + affinity/spill policy, and the
    sharding-rule helpers (spec_for / constrain / tree_specs /
    mesh_axes_for with the kv_pages rule and its axis-absent fallback).
  * 1-device numerics: the log-sum-exp partial merge vs the unsharded
    kernel finalize, with pages split across simulated owners.
  * multi-device integration via subprocesses (the test_distributed.py
    idiom — XLA_FLAGS device-count forcing must precede jax init): token
    parity of a 2-device mesh engine against the 1-device engine across
    {transformer, moe, hybrid} x {f32, coded} KV, per-device page-budget
    admission guards, full pool reclamation after drain, and mesh
    validation errors.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.paged import PagedLayout, PageShard, localize_ids
from repro.parallel import sharding
from repro.serve import PageAllocator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(n_devices: int):
    return {**os.environ,
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
            "PYTHONPATH": os.path.join(REPO, "src")}


def _run(code: str, n_devices: int = 2, timeout: int = 600):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=_env(n_devices),
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# PagedLayout: global <-> (shard, local) page-id mapping
# ---------------------------------------------------------------------------


def test_layout_global_local_mapping():
    lay = PagedLayout(page_size=4, n_pages=12, n_shards=3)
    assert lay.pages_per_shard == 4
    assert lay.capacity == 12 - 3
    for g in range(12):
        s, loc = lay.shard_of(g), lay.local_id(g)
        assert 0 <= s < 3 and 0 <= loc < 4
        assert lay.global_id(s, loc) == g
        # every shard's local page 0 is its trash page — and nothing else is
        assert lay.is_trash(g) == (g % 4 == 0)


def test_layout_mapping_rejects_out_of_range():
    lay = PagedLayout(page_size=4, n_pages=8, n_shards=2)
    for g in (-1, 8, 100):
        with pytest.raises(ValueError):
            lay.shard_of(g)
        with pytest.raises(ValueError):
            lay.local_id(g)
        with pytest.raises(ValueError):
            lay.is_trash(g)
    with pytest.raises(ValueError):
        lay.global_id(2, 0)   # shard out of range
    with pytest.raises(ValueError):
        lay.global_id(0, 4)   # local id out of range


def test_layout_validation():
    with pytest.raises(ValueError):
        PagedLayout(page_size=4, n_pages=10, n_shards=3)  # not divisible
    with pytest.raises(ValueError):
        PagedLayout(page_size=4, n_pages=4, n_shards=4)   # <2 pages/shard


def test_for_slots_sharded_defaults():
    """Default pool sizing must give every slot its worst-case pages even
    after each shard donates a trash page."""
    for ns in (1, 2, 3):
        lay = PagedLayout.for_slots(batch=3, max_seq=17, page_size=4,
                                    n_shards=ns)
        assert lay.n_pages % ns == 0
        assert lay.capacity >= 3 * lay.pages_per_slot(17)


def test_localize_ids_maps_non_owned_to_trash():
    """Owned global ids localize; non-owned ids land on the shard's own
    local trash page 0 with owned=False (vmap axis_name stands in for the
    shard_map axis: element i sees axis_index == i)."""
    ids = jnp.asarray([0, 1, 3, 4, 7, 5])
    shard = PageShard(axis="s", n_shards=2)
    loc, owned = jax.vmap(lambda _: localize_ids(ids, 4, shard),
                          axis_name="s")(jnp.arange(2))
    # shard 0 owns globals [0, 4); shard 1 owns [4, 8)
    np.testing.assert_array_equal(np.asarray(loc[0]), [0, 1, 3, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(owned[0]),
                                  [True, True, True, False, False, False])
    np.testing.assert_array_equal(np.asarray(loc[1]), [0, 0, 0, 0, 3, 1])
    np.testing.assert_array_equal(np.asarray(owned[1]),
                                  [False, False, False, True, True, True])


# ---------------------------------------------------------------------------
# PageAllocator: per-device budgets, affinity, deterministic spill
# ---------------------------------------------------------------------------


def test_sharded_allocator_never_grants_trash_and_conserves():
    a = PageAllocator(12, n_shards=3)
    assert a.capacity == 9 and a.pages_per_shard == 4
    got = a.alloc(9)
    assert got is not None and len(set(got)) == 9
    assert all(g % 4 != 0 for g in got), "granted a trash page"
    assert a.alloc(1) is None
    assert a.pages_in_use_by_shard == [3, 3, 3]
    a.free(got)
    assert a.pages_in_use == 0 and a.pages_free == 9
    assert a.pages_free_by_shard == [3, 3, 3]


def test_sharded_allocator_affinity_and_spill():
    a = PageAllocator(12, n_shards=3)
    # prefer_shard honored when that budget fits
    got = a.alloc(2, prefer_shard=1)
    assert all(a.shard_of(p) == 1 for p in got)
    # no preference -> least-loaded single shard (most free, tie lowest
    # index): shards 0 and 2 tie at 3 free -> shard 0
    got2 = a.alloc(2)
    assert all(a.shard_of(p) == 0 for p in got2)
    # request bigger than any single remaining budget spills, most-free
    # first: free now [1, 1, 3] -> shard 2 then shards 0/1
    got3 = a.alloc(4)
    assert sorted(a.shard_of(p) for p in got3) == [0, 2, 2, 2]
    # frees go back to their own shard's budget
    a.free(got3)
    assert a.pages_free_by_shard == [1, 1, 3]
    a.free(got + got2)
    assert a.pages_free_by_shard == [3, 3, 3]


def test_sharded_allocator_prefer_falls_back_when_full():
    a = PageAllocator(8, n_shards=2)
    a.alloc(3, prefer_shard=0)
    got = a.alloc(2, prefer_shard=0)   # shard 0 exhausted -> shard 1
    assert all(a.shard_of(p) == 1 for p in got)


def test_sharded_allocator_rejects_bad_shapes():
    with pytest.raises(ValueError):
        PageAllocator(10, n_shards=3)
    with pytest.raises(ValueError):
        PageAllocator(4, n_shards=4)


# ---------------------------------------------------------------------------
# sharding rules: kv_pages mapping + axis-absent fallback
# ---------------------------------------------------------------------------


def test_kv_pages_rule_axis_absent_fallback():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1),
                             ("data",))
    assert sharding.mesh_axes_for("kv_pages", mesh) == ()
    assert sharding.mesh_axis_size("kv_pages", mesh) == 1
    spec = sharding.spec_for((2, 8, 4, 4),
                             ("layers", "kv_pages", None, "kv_heads"), mesh)
    assert spec == jax.sharding.PartitionSpec(None, None, None, None)


def test_kv_pages_rule_on_model_mesh():
    out = _run("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel import sharding
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
        assert sharding.mesh_axes_for("kv_pages", mesh) == ("model",)
        assert sharding.mesh_axis_size("kv_pages", mesh) == 2
        # pool spec: kv_pages takes 'model'; kv_heads would too but the
        # axis is already used -> dropped (never double-sharded)
        s = sharding.spec_for((2, 8, 4, 4),
                              ("layers", "kv_pages", None, "kv_heads"), mesh)
        assert s == P(None, "model", None, None), s
        # non-divisible page count falls back to replicated
        s = sharding.spec_for((2, 7, 4, 4),
                              ("layers", "kv_pages", None, "kv_heads"), mesh)
        assert s == P(None, None, None, "model"), s
        # tree_specs agrees leaf-wise
        from repro.models.module import ParamSpec
        import jax.numpy as jnp
        tree = {"k": ParamSpec((2, 8, 4, 4),
                               ("layers", "kv_pages", None, "kv_heads"),
                               "zeros", jnp.int8)}
        ns = sharding.tree_specs(tree, mesh)
        assert ns["k"].spec == P(None, "model", None, None), ns
        # constrain inside the serving shard_map is a no-op (axis Manual)
        def f(x):
            y = sharding.constrain(x, ("kv_pages", None))
            return y * 1.0
        x = jnp.zeros((8, 4))
        r = jax.jit(sharding.shard_map(
            f, mesh, in_specs=P("model", None),
            out_specs=P("model", None)))(x)
        assert r.shape == x.shape
        print("RULES-OK")
    """)
    assert "RULES-OK" in out


# ---------------------------------------------------------------------------
# partial merge numerics (1 device): split ownership == unsharded kernel
# ---------------------------------------------------------------------------


def test_merge_partials_matches_full_kernel():
    """Run the paged-attention kernel over one pool twice with
    complementary page_ok ownership masks, merge the (o, m, l) partials
    with the log-sum-exp rule, and require the full-kernel output —
    including rows whose pages all live on one 'owner' (the bitwise
    single-shard case) and a slot with an all-masked owner."""
    rng = np.random.default_rng(0)
    B, Hq, Hkv, Dh, ps, M = 3, 2, 1, 4, 4, 4
    n_pages = 8
    k = jnp.asarray(rng.normal(size=(n_pages, ps, Hkv * Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n_pages, ps, Hkv * Dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, Hq, Dh)), jnp.float32)
    bt = jnp.asarray([[1, 2, 3, 0],     # split across both owners
                      [4, 5, 0, 0],     # entirely owner-1 pages
                      [6, 7, 1, 2]], jnp.int32)
    lengths = jnp.asarray([11, 6, 15], jnp.int32)
    window = jnp.full((B,), 1 << 30, jnp.int32)

    full = ops.paged_attention(q, k, v, bt, lengths, window)

    own0 = jnp.asarray(np.isin(np.asarray(bt), [1, 2, 3]), jnp.int32)
    own1 = jnp.asarray(np.isin(np.asarray(bt), [4, 5, 6, 7]), jnp.int32)
    parts = [ops.paged_attention(q, k, v, bt, lengths, window,
                                 page_ok=ok, partials=True)
             for ok in (own0, own1)]
    o = jnp.stack([p[0] for p in parts])
    m = jnp.stack([p[1] for p in parts])
    l = jnp.stack([p[2] for p in parts])
    merged = jax.vmap(lambda oo, mm, ll:
                      ops.merge_attn_partials(oo, mm, ll, "owners"),
                      axis_name="owners")(o, m, l)
    # psum/pmax under vmap broadcast the merged state to every element
    np.testing.assert_allclose(np.asarray(merged[0]), np.asarray(full),
                               rtol=1e-6, atol=1e-6)
    # slot 1's pages are all owner-1: its merge must be bitwise the
    # unsharded finalize (owner-0 contributes w*l = 0)
    np.testing.assert_array_equal(np.asarray(merged[0][1]),
                                  np.asarray(full[1]))


# ---------------------------------------------------------------------------
# multi-device integration (subprocess: forced host device counts)
# ---------------------------------------------------------------------------

_ARCHS = {"transformer": "command_r_35b",
          "moe": "qwen3_moe_235b",
          "hybrid": "jamba_1_5_large"}


@pytest.mark.parametrize("family", sorted(_ARCHS))
@pytest.mark.parametrize("kv", ["f32", "coded"])
def test_mesh_engine_token_parity(family, kv):
    """A 2-device mesh engine must emit token-identical streams to the
    1-device engine on the same queue — mixed prompt lengths, shared and
    duplicate prefixes (COW), sampling on — and reclaim every page on
    every shard once the queue drains."""
    out = _run(f"""
        import jax, numpy as np
        from repro import configs
        from repro.core.formats import P8_2, P16_2
        from repro.core.quant import QuantPolicy
        from repro.models import api
        from repro.serve import Request, ServingEngine
        from repro.launch.mesh import make_serving_mesh

        quant = QuantPolicy() if "{kv}" == "f32" else \\
            QuantPolicy(weights=P16_2, kv_cache=P8_2)
        cfg = configs.get_tiny_serving("{_ARCHS[family]}", quant)
        params = api.init(jax.random.key(0), cfg)
        rng = np.random.default_rng(0)
        base = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
        prompts = [
            base.copy(),                              # donor
            base.copy(),                              # exact dup -> COW
            np.concatenate([base[:8], rng.integers(  # shared full pages
                0, cfg.vocab_size, 5).astype(np.int32)]),
            rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
        ]

        def run(mesh):
            eng = ServingEngine(cfg, params, batch_slots=2, max_seq=64,
                                greedy=False, temperature=0.8, top_k=8,
                                mesh=mesh)
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, prompt=p.copy(),
                                   max_new_tokens=6))
            done = eng.run()
            return {{r.rid: list(r.out_tokens) for r in done}}, eng

        ref, e1 = run(None)
        got, e2 = run(make_serving_mesh(2))
        assert e2.n_shards == 2, e2.n_shards
        assert got == ref, (got, ref)
        assert e2.allocator.pages_in_use == 0, \\
            e2.allocator.pages_in_use_by_shard
        assert e2.allocator.pages_in_use_by_shard == [0, 0]
        assert not e2.allocator._refs and not e2._held
        assert all(not p for p in e2.slot_pages)
        print("PARITY-OK")
    """)
    assert "PARITY-OK" in out


def test_mesh_engine_per_device_budget_guard_and_validation():
    """Admission must reject a request that cannot fit the sharded pool
    (capacity loses one trash page per device), name the per-device
    budgets, and the engine must reject meshes whose >1 axes the kv_pages
    rule does not cover."""
    out = _run("""
        import jax, numpy as np
        from repro import configs
        from repro.launch.mesh import make_host_mesh
        from repro.models import api
        from repro.serve import Request, ServingEngine
        from repro.launch.mesh import make_serving_mesh

        cfg = configs.get_tiny_serving("command_r_35b")
        params = api.init(jax.random.key(0), cfg)
        mesh = make_serving_mesh(2)
        ps = cfg.quant.kv_page_size
        # n_pages=4 over 2 devices: capacity 2 (one trash per shard)
        eng = ServingEngine(cfg, params, batch_slots=1, max_seq=4 * ps,
                            n_pages=4, mesh=mesh)
        assert eng.allocator.capacity == 2
        big = Request(rid=0, prompt=np.zeros(2 * ps + 1, np.int32),
                      max_new_tokens=ps)
        try:
            eng.submit(big)
            raise AssertionError("oversized request admitted")
        except ValueError as e:
            assert "per-device budgets" in str(e), e
        # the same pool on 1 device has capacity 3: the request fits
        e1 = ServingEngine(cfg, params, batch_slots=1, max_seq=4 * ps,
                           n_pages=4)
        e1.submit(Request(rid=0, prompt=np.zeros(2 * ps + 1, np.int32),
                          max_new_tokens=ps))

        # a >1 mesh axis kv_pages does not shard over is rejected
        mesh2 = make_host_mesh(data=2, model=1)
        try:
            ServingEngine(cfg, params, batch_slots=1, max_seq=4 * ps,
                          mesh=mesh2)
            raise AssertionError("data-axis mesh accepted")
        except ValueError as e:
            assert "kv_pages" in str(e), e
        # n_pages not divisible by the shard count is rejected
        try:
            ServingEngine(cfg, params, batch_slots=1, max_seq=4 * ps,
                          n_pages=5, mesh=mesh)
            raise AssertionError("indivisible pool accepted")
        except ValueError as e:
            assert "divisible" in str(e) or "n_shards" in str(e), e
        print("GUARD-OK")
    """)
    assert "GUARD-OK" in out


def test_mesh_engine_reclaims_after_oversubscribed_drain():
    """An oversubscribed queue (pool smaller than the queue's total
    demand, forcing admission to wait for reclamation and pages to spill
    across shards) must drain completely: every per-device budget returns
    to full and the prefix index and holds empty out."""
    out = _run("""
        import jax, numpy as np
        from repro import configs
        from repro.core.formats import P8_2, P16_2
        from repro.core.quant import QuantPolicy
        from repro.models import api
        from repro.serve import Request, ServingEngine
        from repro.launch.mesh import make_serving_mesh

        cfg = configs.get_tiny_serving(
            "command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
        params = api.init(jax.random.key(0), cfg)
        ps = cfg.quant.kv_page_size
        rng = np.random.default_rng(3)
        base = rng.integers(0, cfg.vocab_size, 2 * ps).astype(np.int32)
        eng = ServingEngine(cfg, params, batch_slots=2, max_seq=6 * ps,
                            n_pages=8, mesh=make_serving_mesh(2))
        for i in range(6):
            tail = rng.integers(0, cfg.vocab_size,
                                rng.integers(1, 2 * ps)).astype(np.int32)
            prompt = np.concatenate([base, tail]) if i % 2 else tail
            eng.submit(Request(rid=i, prompt=prompt,
                               max_new_tokens=int(rng.integers(1, ps))))
        done = eng.run()
        assert len(done) == 6, len(done)
        a = eng.allocator
        assert a.pages_in_use == 0 and a.pages_free == a.capacity
        assert a.pages_free_by_shard == [a.pages_per_shard - 1] * 2
        assert not a._refs and not eng._held and not eng.prefix_index
        assert all(not p for p in eng.slot_pages)
        occ = eng.execution_summary()["pages_in_use_by_shard"]
        assert occ == [0, 0], occ
        print("DRAIN-OK")
    """)
    assert "DRAIN-OK" in out


# ---------------------------------------------------------------------------
# multi-query (4-D q) partials merge + the fused sharded prefill path
# ---------------------------------------------------------------------------


def test_merge_partials_matches_full_kernel_mq():
    """The owner-split log-sum-exp merge under the multi-query grid:
    complementary page_ok masks over 4-D q [B, T, Hq, Dh] must merge to
    the full-kernel output, bitwise for slots whose pages all live on
    one owner — the decode-side guarantee the sharded engine leans on
    when several new tokens per slot decode in one launch."""
    rng = np.random.default_rng(0)
    B, T, Hq, Hkv, Dh, ps, M = 3, 4, 2, 1, 4, 4, 4
    n_pages = 8
    k = jnp.asarray(rng.normal(size=(n_pages, ps, Hkv * Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n_pages, ps, Hkv * Dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, T, Hq, Dh)), jnp.float32)
    bt = jnp.asarray([[1, 2, 3, 0],     # split across both owners
                      [4, 5, 0, 0],     # entirely owner-1 pages
                      [6, 7, 1, 2]], jnp.int32)
    lengths = jnp.asarray([11, 6, 15], jnp.int32)
    window = jnp.full((B,), 1 << 30, jnp.int32)

    full = ops.paged_attention(q, k, v, bt, lengths, window)

    own0 = jnp.asarray(np.isin(np.asarray(bt), [1, 2, 3]), jnp.int32)
    own1 = jnp.asarray(np.isin(np.asarray(bt), [4, 5, 6, 7]), jnp.int32)
    parts = [ops.paged_attention(q, k, v, bt, lengths, window,
                                 page_ok=ok, partials=True)
             for ok in (own0, own1)]
    o = jnp.stack([p[0] for p in parts])
    m = jnp.stack([p[1] for p in parts])
    l = jnp.stack([p[2] for p in parts])
    merged = jax.vmap(lambda oo, mm, ll:
                      ops.merge_attn_partials(oo, mm, ll, "owners"),
                      axis_name="owners")(o, m, l)
    np.testing.assert_allclose(np.asarray(merged[0]), np.asarray(full),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(merged[0][1]),
                                  np.asarray(full[1]))


def test_mesh_engine_fused_prefill_slot_spanning_all_shards():
    """One long prompt whose pages land on every shard, prefilled through
    the fused global-pool kernel (the default — history pages stream from
    the all-gathered pool by global id): the 2-device stream must be
    token-identical to the 1-device engine running the *decomposed*
    prefill path — crossing both the fused/decomposed and the
    sharded/unsharded boundaries at once — and the slot's pages must
    actually occupy both shards mid-flight."""
    out = _run("""
        import jax, numpy as np
        from repro import configs
        from repro.core.formats import P8_2, P16_2
        from repro.core.quant import QuantPolicy
        from repro.models import api
        from repro.serve import Request, ServingEngine
        from repro.launch.mesh import make_serving_mesh

        cfg = configs.get_tiny_serving(
            "command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
        params = api.init(jax.random.key(0), cfg)
        ps = cfg.quant.kv_page_size
        rng = np.random.default_rng(7)
        # spans > pages_per_shard pages, so one slot must spill shards
        prompt = rng.integers(0, cfg.vocab_size, 5 * ps + 3).astype(np.int32)

        def run(mesh, fused):
            eng = ServingEngine(cfg, params, batch_slots=1,
                                max_seq=8 * ps, n_pages=12, mesh=mesh,
                                fused_prefill=fused)
            eng.submit(Request(rid=0, prompt=prompt.copy(),
                               max_new_tokens=4))
            while eng.pages_in_use == 0:
                eng.step()
            by_shard = eng.allocator.pages_in_use_by_shard
            done = eng.run()
            assert len(done) == 1
            return list(done[0].out_tokens), by_shard, eng

        ref_toks, _, e1 = run(None, fused=False)
        got_toks, by_shard, e2 = run(make_serving_mesh(2), fused=True)
        assert e2.cfg.quant.fused_prefill
        assert e2.execution_summary()["fused_prefill"]
        assert len(by_shard) == 2 and all(n > 0 for n in by_shard), by_shard
        assert got_toks == ref_toks, (got_toks, ref_toks)
        assert e2.allocator.pages_in_use == 0
        print("SPAN-OK", by_shard)
    """)
    assert "SPAN-OK" in out


def test_mesh_engine_long_prompt_multi_chunk_fused_parity():
    """Needle-style long prompt spanning >= 3 flash chunks of streamed
    history on a 2-device mesh: with paged.FLASH_CHUNK shrunk to 16, a
    53-token prompt forces the fused prefill kernel through multiple
    in-kernel flash softmax steps over all-gathered history pages while
    the fused decode epilogue runs each step as ONE device program.  The
    stream must be token-identical to the 1-device fully-decomposed
    engine, for the base prompt and with the needle token flipped."""
    out = _run("""
        import jax, numpy as np
        from repro import configs
        from repro.core.formats import P8_2, P16_2
        from repro.core.quant import QuantPolicy
        from repro.models import api, paged
        from repro.serve import Request, ServingEngine
        from repro.launch.mesh import make_serving_mesh

        paged.FLASH_CHUNK = 16  # page_size 16 divides it: fused gate holds
        cfg = configs.get_tiny_serving(
            "command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
        params = api.init(jax.random.key(0), cfg)
        rng = np.random.default_rng(11)
        needle = rng.integers(0, cfg.vocab_size,
                              3 * paged.FLASH_CHUNK + 5).astype(np.int32)
        flipped = needle.copy()
        flipped[1] = (needle[1] + 1) % cfg.vocab_size

        def run(mesh, fused, prompt):
            eng = ServingEngine(cfg, params, batch_slots=1, max_seq=64,
                                mesh=mesh, fused_prefill=fused,
                                fused_decode=fused)
            eng.submit(Request(rid=0, prompt=prompt.copy(),
                               max_new_tokens=4))
            done = eng.run()
            assert len(done) == 1
            return list(done[0].out_tokens), eng.execution_summary()

        mesh = make_serving_mesh(2)
        for prompt in (needle, flipped):
            ref, s_ref = run(None, False, prompt)
            got, s = run(mesh, True, prompt)
            assert got == ref, (got, ref)
            assert s["fused_prefill"] and s["fused_decode"]
            assert s["prefill_chunks"] == s_ref["prefill_chunks"] > 0
            assert s["prefill_device_programs"] == s["prefill_chunks"]
            assert s_ref["prefill_device_programs"] == \\
                3 * s_ref["prefill_chunks"]
            assert s["decode_device_programs"] == s["decode_steps"]
            assert s_ref["decode_device_programs"] == \\
                2 * s_ref["decode_steps"]
        print("NEEDLE-OK")
    """)
    assert "NEEDLE-OK" in out
