"""Paged posit-KV serving runtime: kernel-vs-reference parity, paged-vs-
dense token parity across model families and KV formats, page reclamation
(no stale-key leakage), prefix sharing (refcounted pages, copy-on-write,
bit-identical to unshared serving), batched cross-slot prefill,
bucketed-prefill compile counts, and the sampler.

All Pallas kernels run in interpret mode on CPU."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import posit
from repro.core.formats import P8_2, P16_1, P16_2
from repro.core.quant import QuantPolicy, policy_by_name
from repro.kernels import ops, ref
from repro.models import api
from repro.models.paged import PagedLayout
from repro.serve import PageAllocator, Request, ServingEngine


# ---------------------------------------------------------------------------
# kernel vs reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt_kv", [None, P8_2, P16_1])
def test_paged_attention_kernel_matches_ref(rng, fmt_kv):
    B, Hq, Hkv, Dh, ps, M, P = 3, 4, 2, 8, 4, 3, 12
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, Dh)).astype(np.float32))
    kf = jnp.asarray(rng.normal(0, 1, (P, ps, Hkv * Dh)).astype(np.float32))
    vf = jnp.asarray(rng.normal(0, 1, (P, ps, Hkv * Dh)).astype(np.float32))
    if fmt_kv is not None:
        kf, vf = posit.pack(kf, fmt_kv), posit.pack(vf, fmt_kv)
    bt = jnp.asarray(rng.permutation(P)[:B * M].reshape(B, M).astype(np.int32))
    lengths = jnp.array([5, 12, 1], jnp.int32)
    window = jnp.array([1 << 30], jnp.int32)
    got = ops.paged_attention(q, kf, vf, bt, lengths, window, fmt_kv=fmt_kv)
    want = ref.paged_attention_ref(q, kf, vf, bt, lengths, window,
                                   fmt_kv=fmt_kv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_paged_attention_window_and_softcap(rng):
    B, Hq, Hkv, Dh, ps, M, P = 2, 4, 2, 8, 4, 4, 9
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, Dh)).astype(np.float32))
    kf = posit.pack(jnp.asarray(
        rng.normal(0, 1, (P, ps, Hkv * Dh)).astype(np.float32)), P8_2)
    vf = posit.pack(jnp.asarray(
        rng.normal(0, 1, (P, ps, Hkv * Dh)).astype(np.float32)), P8_2)
    bt = jnp.asarray(rng.permutation(P - 1)[:B * M].reshape(B, M) + 1,
                     dtype=jnp.int32)
    lengths = jnp.array([13, 7], jnp.int32)
    window = jnp.array([3], jnp.int32)
    got = ops.paged_attention(q, kf, vf, bt, lengths, window,
                              fmt_kv=P8_2, softcap_val=4.0)
    want = ref.paged_attention_ref(q, kf, vf, bt, lengths, window,
                                   fmt_kv=P8_2, softcap_val=4.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_paged_attention_ignores_unallocated_and_stale_pages(rng):
    """Positions >= length are masked, so block-table entries past the
    written prefix (trash page 0, reclaimed garbage) cannot contribute."""
    B, Hq, Hkv, Dh, ps, M, P = 1, 2, 1, 8, 4, 3, 6
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, Dh)).astype(np.float32))
    kf = jnp.asarray(rng.normal(0, 1, (P, ps, Hkv * Dh)).astype(np.float32))
    vf = jnp.asarray(rng.normal(0, 1, (P, ps, Hkv * Dh)).astype(np.float32))
    lengths = jnp.array([3], jnp.int32)  # only page bt[0] partially valid
    window = jnp.array([1 << 30], jnp.int32)
    out1 = ops.paged_attention(q, kf, vf, jnp.array([[2, 4, 5]], jnp.int32),
                               lengths, window)
    # same first page, wildly different (stale) tail pages -> same output
    kf2 = kf.at[4].set(999.0).at[5].set(-999.0)
    vf2 = vf.at[4].set(999.0).at[5].set(-999.0)
    out2 = ops.paged_attention(q, kf2, vf2, jnp.array([[2, 0, 0]], jnp.int32),
                               lengths, window)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# paged cache representation
# ---------------------------------------------------------------------------


def test_paged_cache_specs_shapes():
    cfg = configs.get_smoke("command_r_35b")
    layout = PagedLayout.for_slots(3, 40, 8)
    assert layout.n_pages == 3 * 5 + 1 and layout.pages_per_slot(40) == 5
    specs = api.cache_specs(cfg, 3, 40, layout)
    F = cfg.n_kv_heads * cfg.head_dim
    assert specs["k"].shape == (cfg.n_layers, 16, 8, F)
    assert specs["k"].logical_axes == ("layers", "kv_pages", None, "kv_heads")
    assert specs["block_table"].shape == (3, 5)
    cache = api.init_cache(cfg, 3, 40, layout)
    assert cache["k"].shape == (cfg.n_layers, 16, 8, F)
    # kv_pages participates in the sharding rule table
    from repro.parallel.sharding import DEFAULT_RULES
    assert "kv_pages" in DEFAULT_RULES


def test_page_allocator_free_list():
    a = PageAllocator(6)  # pages 1..5 allocatable, 0 reserved
    assert a.capacity == 5 and a.pages_free == 5
    got = a.alloc(3)
    assert got is not None and 0 not in got and len(set(got)) == 3
    assert a.pages_in_use == 3 and a.peak_in_use == 3
    assert a.alloc(3) is None  # only 2 left
    a.free(got)
    assert a.pages_free == 5 and a.peak_in_use == 3
    assert a.alloc(5) is not None and a.pages_free == 0


# ---------------------------------------------------------------------------
# engine: paged-vs-dense token parity across families and KV formats
# ---------------------------------------------------------------------------


def _tiny(arch, quant):
    return configs.get_tiny_serving(arch, quant)


def _serve(cfg, params, prompts, max_new=3, **kw):
    engine = ServingEngine(cfg, params, batch_slots=2, max_seq=32, **kw)
    for i, p in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=p, max_new_tokens=max_new))
    done = engine.run()
    return {r.rid: r.out_tokens for r in done}, engine


@pytest.mark.parametrize("arch", ["command_r_35b", "mamba2_1_3b",
                                  "jamba_1_5_large", "qwen3_moe_235b"])
@pytest.mark.parametrize("kv", ["f32", "coded"])
def test_paged_vs_dense_token_parity(rng, arch, kv):
    """Same requests, same seeds -> identical output tokens across
    {dense, paged} x {f32, posit-coded} KV, per family."""
    quant = QuantPolicy() if kv == "f32" else \
        QuantPolicy(weights=P16_2, kv_cache=P8_2)
    cfg = _tiny(arch, quant)
    params = api.init(jax.random.key(0), cfg)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 3)]
    out_paged, ep = _serve(cfg, params, prompts, page_size=4)
    out_dense, _ = _serve(cfg, params, prompts, paged=False)
    assert out_paged == out_dense
    assert set(out_paged) == {0, 1, 2}
    assert all(len(t) == 3 for t in out_paged.values())
    if cfg.family != "ssm":
        assert ep.paged and ep.pages_in_use == 0  # all reclaimed
        if kv == "coded":
            assert ep.cache["k"].dtype == jnp.int8  # pages at code width


def test_slot_reuse_after_retirement_no_stale_keys(rng):
    """Page reclamation: a request served through recycled pages must see
    exactly what it would see on a fresh engine (stale keys from retired
    requests never enter its attention)."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(1), cfg)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (12, 7, 9, 4)]
    # one slot, minimal pool: every request recycles its predecessor's pages
    engine = ServingEngine(cfg, params, batch_slots=1, max_seq=32,
                           page_size=4, n_pages=6)
    for i, p in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    recycled = {r.rid: r.out_tokens for r in engine.run()}
    assert engine.pages_in_use == 0
    for i, p in enumerate(prompts):
        fresh = ServingEngine(cfg, params, batch_slots=1, max_seq=32,
                              page_size=4, n_pages=6)
        fresh.submit(Request(rid=i, prompt=p, max_new_tokens=4))
        want = fresh.run()[0].out_tokens
        assert recycled[i] == want, i


def test_oversubscribed_pool_waits_for_reclamation(rng):
    """A pool smaller than the queue's worst case admits lazily (requests
    wait for reclaimed pages) but still serves everything, identically."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 8, 7, 6)]
    full, _ = _serve(cfg, params, prompts, page_size=4)
    # 4 pages: exactly one in-flight request's worth ((9+3-1)//4 + 1 = 3)
    tight, eng = _serve(cfg, params, prompts, page_size=4, n_pages=5)
    assert tight == full
    assert eng.allocator.peak_in_use <= 4


def test_submit_rejects_requests_exceeding_max_seq(rng):
    """Writes past max_seq would wrap into the slot's last page (paged) or
    be silently dropped (dense) — submission must reject them up front."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    engine = ServingEngine(cfg, params, batch_slots=1, max_seq=32,
                           page_size=16)
    with pytest.raises(ValueError, match="max_seq"):
        engine.submit(Request(rid=0, prompt=np.arange(30, dtype=np.int32),
                              max_new_tokens=8))
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit(Request(rid=1, prompt=np.array([], np.int32)))
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(Request(rid=2, prompt=np.arange(4, dtype=np.int32),
                              max_new_tokens=0))
    # the boundary case fits exactly: 25 + 8 - 1 == 32 positions
    engine.submit(Request(rid=3, prompt=np.arange(25, dtype=np.int32),
                          max_new_tokens=8))
    done = engine.run()
    assert len(done) == 1 and len(done[0].out_tokens) == 8


def test_request_larger_than_pool_raises(rng):
    """A request that can never fit the pool fails fast at submit — not
    mid-run after other requests were already served."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    engine = ServingEngine(cfg, params, batch_slots=1, max_seq=32,
                           page_size=4, n_pages=3)
    with pytest.raises(ValueError, match="pages"):
        engine.submit(Request(rid=0, prompt=np.arange(20, dtype=np.int32),
                              max_new_tokens=8))
    assert engine.queue == []


def test_interleaved_chunked_prefill_matches_admission_prefill(rng):
    """prefill_chunks_per_step=1 interleaves prompt chunks with ongoing
    decode; mid-prefill slots must be fully isolated from the decode step
    (recurrent SSM/conv state and pages untouched) — outputs identical to
    completing prefill at admission."""
    for arch in ("command_r_35b", "mamba2_1_3b", "jamba_1_5_large"):
        cfg = _tiny(arch, QuantPolicy(weights=P16_2, kv_cache=P8_2))
        params = api.init(jax.random.key(0), cfg)
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (11, 6, 9)]
        at_admission, _ = _serve(cfg, params, prompts, max_new=4,
                                 page_size=4)
        interleaved, _ = _serve(cfg, params, prompts, max_new=4,
                                page_size=4, prefill_chunks_per_step=1)
        assert interleaved == at_admission, arch


# ---------------------------------------------------------------------------
# prefix sharing: refcounted pages, copy-on-write, token parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["command_r_35b", "qwen3_moe_235b",
                                  "jamba_1_5_large"])
@pytest.mark.parametrize("kv", ["f32", "coded"])
def test_prefix_sharing_token_parity(rng, arch, kv):
    """Requests sharing a prompt prefix map the donor's pages (refcounted)
    and produce bit-identical tokens to unshared serving, across attention
    families and KV formats.  Chain: sharing stops at boundaries of the
    request's own chunk decomposition, so the tail's chunking — and every
    logit — matches an unshared run exactly."""
    quant = QuantPolicy(weights=P16_2) if kv == "f32" else \
        QuantPolicy(weights=P16_2, kv_cache=P8_2)
    cfg = _tiny(arch, quant)
    params = api.init(jax.random.key(0), cfg)
    base = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    prompts = [np.concatenate([base, rng.integers(0, cfg.vocab_size, t)
                               .astype(np.int32)]) for t in (3, 5)]
    prompts.append(prompts[0].copy())  # exact duplicate
    kw = dict(max_new=4, page_size=4, prefill_buckets=(4, 1))
    shared, es = _serve(cfg, params, prompts, **kw)
    unshared, eu = _serve(cfg, params, prompts, prefix_sharing=False, **kw)
    assert shared == unshared
    assert es.stats["shared_admissions"] >= 2
    assert es.stats["pages_shared"] >= 4
    # the whole point: fewer fresh page grants than unshared serving
    assert es.allocator.total_allocs < eu.allocator.total_allocs
    # everything reclaims: refcounts, holds, and index all drain
    assert es.pages_in_use == 0 and not es.prefix_index and not es._held


def test_cow_fork_never_mutates_shared_page(rng):
    """An exact-duplicate request maps the donor's partially-filled tail
    page; its divergent write (last prompt token, then decode) must fork a
    private copy and leave the donor's page bit-identical — pinned with a
    direct page-pool readback, not just token parity."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    prompt = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)

    engine = ServingEngine(cfg, params, batch_slots=2, max_seq=32,
                           page_size=4, prefill_buckets=(4, 1))
    engine.submit(Request(rid=0, prompt=prompt, max_new_tokens=12))
    engine.step()  # donor prefilled + decoding; index holds its pages
    donor_pages = list(engine.slot_pages[0])
    tail_page = donor_pages[2]  # positions 8..11: prompt tail + decode
    snap_k = np.asarray(engine.cache["k"][:, tail_page])
    snap_v = np.asarray(engine.cache["v"][:, tail_page])

    engine.submit(Request(rid=1, prompt=prompt, max_new_tokens=3))
    engine.step()  # sharer admitted, COW-forks the tail page, decodes
    assert engine.stats["cow_forks"] == 1
    # the sharer's block table diverged from the donor's on the tail page
    assert engine.block_tables[1, 2] != tail_page
    assert engine.block_tables[1, 0] == donor_pages[0]
    assert engine.block_tables[1, 1] == donor_pages[1]
    # direct pool readback: the shared page holds exactly the donor's KV
    # below the sharer's trusted range (positions 8..9 of the prompt);
    # the donor keeps appending its own decode KV in place past it
    ps = engine.layout.page_size
    tail_lo = 2 * ps
    valid = min(int(engine.lengths[0]), 11) - tail_lo  # prompt rows only
    np.testing.assert_array_equal(
        np.asarray(engine.cache["k"][:, tail_page, :2]), snap_k[:, :2])
    np.testing.assert_array_equal(
        np.asarray(engine.cache["v"][:, tail_page, :2]), snap_v[:, :2])
    assert valid >= 2

    out = {r.rid: r.out_tokens for r in engine.run()}
    for rid, mn in ((0, 12), (1, 3)):
        fresh = ServingEngine(cfg, params, batch_slots=2, max_seq=32,
                              page_size=4, prefill_buckets=(4, 1))
        fresh.submit(Request(rid=rid, prompt=prompt, max_new_tokens=mn))
        assert out[rid] == fresh.run()[0].out_tokens, rid
    assert engine.pages_in_use == 0


def test_shared_prefix_pages_allocated_once(rng):
    """N requests with the same prompt allocate the shared-prefix pages
    once: total fresh grants stay near a single request's demand (the
    acceptance bar the bench gate also checks)."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    # 46-token prompt over 4-token pages: 11 full prefix pages share, the
    # tail page is COW-forked per sharer, decode stays inside it
    prompt = rng.integers(0, cfg.vocab_size, 46).astype(np.int32)

    def allocs(n_req, sharing):
        eng = ServingEngine(cfg, params, batch_slots=2, max_seq=48,
                            page_size=4, prefill_buckets=(16, 4, 1),
                            prefix_sharing=sharing)
        for i in range(n_req):
            eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=2))
        done = eng.run()
        assert len(done) == n_req and eng.pages_in_use == 0
        return eng.allocator.total_allocs

    single = allocs(1, True)
    assert allocs(4, True) < 1.5 * single < allocs(4, False)


def test_held_prefix_pages_yield_to_blocked_admission(rng):
    """Pages held for a queued sharer must not starve a non-sharing
    request that needs the whole pool: when admission stalls with nothing
    in flight, holds yield (liveness over sharing) and every request still
    serves, token-identical to fresh runs."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    donor = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    sharer = np.concatenate([donor[:8],
                             rng.integers(0, cfg.vocab_size, 2)
                             .astype(np.int32)])
    big = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)  # 6 pages

    engine = ServingEngine(cfg, params, batch_slots=1, max_seq=32,
                           page_size=4, n_pages=7, prefill_buckets=(4, 1))
    engine.submit(Request(rid=0, prompt=donor, max_new_tokens=2))
    # donor retires first; its prefix pages are held for rid=2's benefit
    # while rid=1 (queued ahead) needs the entire pool
    engine.submit(Request(rid=1, prompt=big, max_new_tokens=4))
    engine.submit(Request(rid=2, prompt=sharer, max_new_tokens=2))
    out = {r.rid: r.out_tokens for r in engine.run()}
    assert set(out) == {0, 1, 2}
    for rid, prompt, mn in ((0, donor, 2), (1, big, 4), (2, sharer, 2)):
        fresh = ServingEngine(cfg, params, batch_slots=1, max_seq=32,
                              page_size=4, n_pages=7,
                              prefill_buckets=(4, 1))
        fresh.submit(Request(rid=rid, prompt=prompt, max_new_tokens=mn))
        assert out[rid] == fresh.run()[0].out_tokens, rid
    assert engine.pages_in_use == 0 and not engine._held


def test_page_allocator_refcounts():
    """Sharing takes references, free drops them, recycle only at zero;
    double frees and shares of free pages raise instead of corrupting."""
    a = PageAllocator(6)
    got = a.alloc(2)
    assert a.total_allocs == 2 and all(a.refcount(p) == 1 for p in got)
    a.share(got)
    assert all(a.refcount(p) == 2 for p in got)
    assert a.free(got) == []          # refs survive: nothing recycled
    assert a.pages_in_use == 2
    assert sorted(a.free(got)) == sorted(got)  # last ref: recycled
    assert a.pages_in_use == 0
    with pytest.raises(ValueError, match="double free"):
        a.free([got[0]])
    with pytest.raises(ValueError, match="share free"):
        a.share([got[0]])


def test_joint_oversubscription_with_sharing(rng):
    """Two requests that individually fit but jointly oversubscribe the
    pool: admission accounts the full private demand (including the
    copy-on-write fork reserve) up front instead of checking each request
    in isolation, so the sharer never allocates mid-flight — both serve to
    completion, token-identical to fresh runs, and the pool drains."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    prompt = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    # donor needs 4 pages (11 + 6 - 2 -> positions 0..15), duplicate needs
    # 4 alone: jointly 8 > capacity 6, individually 4 <= 6
    engine = ServingEngine(cfg, params, batch_slots=2, max_seq=16,
                           page_size=4, n_pages=7, prefill_buckets=(4, 1))
    engine.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    engine.submit(Request(rid=1, prompt=prompt, max_new_tokens=6))
    assert engine.pages_promised == 8 > engine.allocator.capacity
    out = {r.rid: r.out_tokens for r in engine.run()}
    assert len(out) == 2
    fresh = ServingEngine(cfg, params, batch_slots=1, max_seq=16,
                          page_size=4, n_pages=7, prefill_buckets=(4, 1))
    fresh.submit(Request(rid=9, prompt=prompt, max_new_tokens=6))
    want = fresh.run()[0].out_tokens
    assert out[0] == want and out[1] == want
    assert engine.pages_in_use == 0 and not engine._held


# ---------------------------------------------------------------------------
# bucketed prefill: compile count O(#buckets), not O(#lengths)
# ---------------------------------------------------------------------------


def test_prefill_compiles_per_bucket_not_per_length(rng):
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    engine = ServingEngine(cfg, params, batch_slots=2, max_seq=32,
                           page_size=4, prefill_buckets=(16, 4, 1),
                           batched_prefill=False)
    lengths = [3, 5, 7, 9, 11, 13, 6, 10, 14, 8]  # 10 distinct lengths
    for i, n in enumerate(lengths):
        engine.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=2))
    done = engine.run()
    assert len(done) == len(lengths)
    assert engine._chunk._cache_size() <= len(engine.prefill_buckets)


def test_batched_prefill_compiles_per_bucket_not_per_slot_count(rng):
    """Cross-slot batched prefill keeps the compile count O(#buckets): the
    [batch_slots, chunk] program shape is fixed however many slots fill
    per step (non-group rows are masked), so a mixed-length queue over 4
    slots with variable group sizes traces at most one program per
    bucket — and the fleet actually batches (multi-slot groups occur)."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    engine = ServingEngine(cfg, params, batch_slots=4, max_seq=32,
                           page_size=4, prefill_buckets=(16, 4, 1),
                           prefill_chunks_per_step=1)
    lengths = [3, 5, 7, 9, 11, 13, 6, 10, 14, 8, 12, 4]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    for i, p in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=p, max_new_tokens=2))
    done = engine.run()
    assert len(done) == len(lengths)
    assert engine._chunk_batched._cache_size() <= len(engine.prefill_buckets)
    assert engine._chunk._cache_size() == 0  # per-slot path never used
    assert max(engine.stats["prefill_batch_sizes"]) > 1  # real batching
    # parity: the batched fleet decodes exactly what per-slot serving does
    per_slot = ServingEngine(cfg, params, batch_slots=4, max_seq=32,
                             page_size=4, prefill_buckets=(16, 4, 1),
                             prefill_chunks_per_step=1,
                             batched_prefill=False)
    for i, p in enumerate(prompts):
        per_slot.submit(Request(rid=i, prompt=p, max_new_tokens=2))
    got = {r.rid: r.out_tokens for r in per_slot.run()}
    assert got == {r.rid: r.out_tokens for r in done}


@pytest.mark.parametrize("batched", [True, False])
def test_chunk_programs_write_the_pool_in_place(rng, batched):
    """A long prompt's chunk programs queue back to back; each donates the
    page pool it is given, so the queue holds one pool and not a copy per
    program."""
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    engine = ServingEngine(cfg, params, batch_slots=2, max_seq=32,
                           page_size=4, prefill_buckets=(4,),
                           batched_prefill=batched)
    name = "_chunk_batched" if batched else "_chunk"
    chunk, pools = getattr(engine, name), []

    def logged(params, tokens, cache, *rest):
        pools.append((cache["k"], cache["v"]))
        return chunk(params, tokens, cache, *rest)
    setattr(engine, name, logged)
    engine.submit(Request(
        rid=0, prompt=rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
        max_new_tokens=2))
    done = engine.run()
    assert len(done) == 1 and len(pools) == 3
    assert all(k.is_deleted() and v.is_deleted() for k, v in pools)
    assert not engine.cache["k"].is_deleted()


def test_batched_prefill_auto_disabled_for_droppy_moe_capacity():
    """Routed-MoE capacity is computed over the whole batched chunk, so
    batch composition could displace active tokens when the capacity
    factor is not drop-proof — the engine falls back to per-slot prefill
    there unless explicitly overridden; drop-proof configs keep batching."""
    droppy = configs.get_smoke("qwen3_moe_235b").replace(
        quant=QuantPolicy(weights=P16_2, kv_cache=P8_2))
    assert droppy.capacity_factor * droppy.top_k < droppy.n_experts
    params = api.init(jax.random.key(0), droppy)
    eng = ServingEngine(droppy, params, batch_slots=2, max_seq=32,
                        page_size=4)
    assert eng.batched_prefill is False
    forced = ServingEngine(droppy, params, batch_slots=2, max_seq=32,
                           page_size=4, batched_prefill=True)
    assert forced.batched_prefill is True
    proof = _tiny("qwen3_moe_235b", QuantPolicy(weights=P16_2,
                                                kv_cache=P8_2))
    assert proof.capacity_factor * proof.top_k >= proof.n_experts
    eng2 = ServingEngine(proof, api.init(jax.random.key(0), proof),
                         batch_slots=2, max_seq=32, page_size=4)
    assert eng2.batched_prefill is True


def test_ssm_buckets_respect_ssd_chunk():
    cfg = _tiny("mamba2_1_3b", QuantPolicy())  # ssm_chunk == 8
    params = api.init(jax.random.key(0), cfg)
    engine = ServingEngine(cfg, params, batch_slots=1, max_seq=32,
                           prefill_buckets=(48, 12, 4))
    # 48 = 6*8 kept, 12 dropped (not <= 8, not divisible), 4 kept, 1 added
    assert engine.prefill_buckets == (48, 4, 1)


# ---------------------------------------------------------------------------
# sampling: the greedy knob is honored, non-greedy is reproducible
# ---------------------------------------------------------------------------


def test_sampling_reproducible_and_seeded(rng):
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 9)]
    kw = dict(greedy=False, temperature=30.0, top_k=16, max_new=6)
    s1, _ = _serve(cfg, params, prompts, **kw)
    s2, _ = _serve(cfg, params, prompts, **kw)
    assert s1 == s2  # fixed per-request seed -> byte-identical streams
    g, _ = _serve(cfg, params, prompts, max_new=6)
    assert s1 != g  # at temperature 30 sampling actually explores
    s3, _ = _serve(cfg, params, prompts, base_seed=1234, **kw)
    assert s1 != s3  # a different engine seed moves the streams
    # sampling is layout-independent: paged and dense draw the same tokens
    s_dense, _ = _serve(cfg, params, prompts, paged=False, **kw)
    assert s1 == s_dense


def test_request_seed_overrides_rid(rng):
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)

    def one(rid, seed):
        e = ServingEngine(cfg, params, batch_slots=1, max_seq=32,
                          greedy=False, temperature=30.0, top_k=16)
        e.submit(Request(rid=rid, prompt=prompt, max_new_tokens=6, seed=seed))
        return e.run()[0].out_tokens

    assert one(0, seed=42) == one(99, seed=42)  # seed pins the stream


# ---------------------------------------------------------------------------
# storage accounting
# ---------------------------------------------------------------------------


def test_kv_cache_summary_splits_metadata(rng):
    cfg = _tiny("command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
    params = api.init(jax.random.key(0), cfg)
    engine = ServingEngine(cfg, params, batch_slots=2, max_seq=32,
                           page_size=4)
    s = engine.kv_cache_summary()
    assert s["kv_bytes"] == int(engine.cache["k"].nbytes
                                + engine.cache["v"].nbytes)
    assert s["metadata_bytes"] == int(engine.cache["length"].nbytes
                                      + engine.cache["block_table"].nbytes)
    assert s["total_bytes"] == s["kv_bytes"] + s["metadata_bytes"]
    assert s["kv_bytes_in_use"] == 0  # nothing admitted yet
    assert engine.kv_cache_bytes() == s["total_bytes"]
    engine.submit(Request(rid=0, prompt=rng.integers(0, 64, 6).astype(np.int32),
                          max_new_tokens=8))
    engine.step()
    used = engine.kv_cache_summary()["kv_bytes_in_use"]
    page_bytes = s["kv_bytes"] // engine.layout.n_pages
    assert used == engine.pages_in_use * page_bytes > 0
    summary = engine.execution_summary()
    assert summary["paged"] is True and summary["page_size"] == 4
    assert summary["pages_in_use"] == engine.pages_in_use
    assert summary["kv_bytes"] + summary["metadata_bytes"] \
        == summary["kv_cache_bytes"]
