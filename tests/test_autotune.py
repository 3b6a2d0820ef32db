"""Kernel autotune cache: keys, persistence, dispatch resolution, sweep.

The cache (kernels/autotune.py) maps (kernel, bucketed shape, posit
formats, backend) -> launch params; ops.py resolves unspecified launch
params through it at dispatch time.  Every tuned parameter is
value-neutral by construction (tile sizes / query-tile splits that never
change the math), so these tests assert that resolution through any
cache contents — committed, injected, or absent — leaves kernel outputs
bitwise unchanged while the hit/miss accounting observes the lookups.
"""
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import posit
from repro.core.formats import P8_2, P16_1, P16_2
from repro.kernels import autotune, ops, posit_codec


@pytest.fixture
def scratch_cache():
    """Restore the process-wide cache after a test swaps it out."""
    yield
    autotune.reset_cache(None)


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------


def test_shape_bucket_rounds_up_pow2_min8():
    assert autotune.shape_bucket((1, 8, 9, 1000)) == (8, 8, 16, 1024)
    assert autotune.shape_bucket((256,)) == (256,)


def test_make_key_canonical():
    assert autotune.make_key((200, 300, 100), (P16_2, None)) == \
        {"shape": [256, 512, 128], "fmts": ["P16_2", "f32"]}


def test_key_digest_stable_and_discriminating():
    key = autotune.make_key((200, 300, 100), (P16_2, P16_2))
    d = autotune.key_digest("posit_matmul", "cpu", key)
    # same bucket -> same digest
    same = autotune.make_key((129, 257, 65), (P16_2, P16_2))
    assert autotune.key_digest("posit_matmul", "cpu", same) == d
    # kernel, backend, format, and bucket each discriminate
    assert autotune.key_digest("posit_matmul_grouped", "cpu", key) != d
    assert autotune.key_digest("posit_matmul", "tpu", key) != d
    other_fmt = autotune.make_key((200, 300, 100), (P16_1, P16_2))
    assert autotune.key_digest("posit_matmul", "cpu", other_fmt) != d
    other_shape = autotune.make_key((300, 300, 100), (P16_2, P16_2))
    assert autotune.key_digest("posit_matmul", "cpu", other_shape) != d


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------


def test_cache_roundtrip_and_hit_accounting(tmp_path):
    c = autotune.AutotuneCache()
    c.put("paged_attention", (4, 8, 8, 16, 128), {"t_block": 2},
          fmts=(P16_1,), ms=1.0, oracle_ms=0.5)
    path = c.save(str(tmp_path / "cache.json"))
    loaded = autotune.AutotuneCache.load(path)
    # any shape in the same bucket resolves to the stored params
    assert loaded.lookup("paged_attention", (3, 5, 7, 9, 100),
                         (P16_1,)) == {"t_block": 2}
    assert loaded.lookup("paged_attention", (3, 5, 7, 9, 100),
                         (P8_2,)) is None
    assert loaded.report() == {"paged_attention": {"hits": 1, "misses": 1}}


def test_cache_version_bump_invalidates_wholesale(tmp_path):
    c = autotune.AutotuneCache()
    c.put("posit_matmul", (256, 256, 256), {"bm": 128, "bn": 128, "bk": 256},
          fmts=(P16_2, P16_2))
    path = c.save(str(tmp_path / "cache.json"))
    with open(path) as f:
        raw = json.load(f)
    raw["version"] = autotune.CACHE_VERSION + 1
    with open(path, "w") as f:
        json.dump(raw, f)
    assert autotune.AutotuneCache.load(path).entries == {}


def test_missing_file_loads_empty(tmp_path):
    assert autotune.AutotuneCache.load(str(tmp_path / "nope.json")).entries \
        == {}


def test_env_var_cache_path_and_off(tmp_path, monkeypatch, scratch_cache):
    c = autotune.AutotuneCache()
    c.put("posit_codec.decode", (64, 128), {"block_r": 64, "block_c": 128},
          fmts=(P16_2,))
    path = c.save(str(tmp_path / "cache.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    autotune.reset_cache(None)  # force a reload from the env path
    assert autotune.lookup("posit_codec.decode", (64, 128), (P16_2,)) == \
        {"block_r": 64, "block_c": 128}
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert autotune.lookup("posit_codec.decode", (64, 128), (P16_2,)) is None


def test_committed_cache_is_well_formed():
    """The committed CI-host cache must load under the current schema and
    only carry params from each kernel's declared tunable space."""
    cache = autotune.AutotuneCache.load(autotune.DEFAULT_CACHE_PATH)
    with open(autotune.DEFAULT_CACHE_PATH) as f:
        raw = json.load(f)
    assert raw["version"] == autotune.CACHE_VERSION
    assert len(raw["entries"]) > 0
    for digest, ent in raw["entries"].items():
        space = autotune.TUNABLES[ent["kernel"]]
        assert set(ent["params"]) == set(space)
        for name, val in ent["params"].items():
            assert val in space[name]
        # the stored digest must reproduce from the stored key contents
        assert digest == autotune.key_digest(ent["kernel"], raw["backend"],
                                             ent["key"])
    del cache


# ---------------------------------------------------------------------------
# dispatch-time resolution through ops.py
# ---------------------------------------------------------------------------


def test_ops_resolution_uses_injected_cache(scratch_cache):
    rng = np.random.default_rng(11)
    vals = jnp.asarray(rng.normal(0, 1, (32, 48)), jnp.float32)
    codes = posit.pack(vals, P16_2)
    autotune.reset_cache(autotune.AutotuneCache())  # empty: all misses
    want = ops.decode(codes, P16_2)
    c = autotune.AutotuneCache()
    c.put("posit_codec.decode", codes.shape, {"block_r": 256, "block_c": 512},
          fmts=(P16_2,))
    autotune.reset_cache(c)
    got = ops.decode(codes, P16_2)
    # tuned tiling resolved (a recorded hit) and value-neutral
    assert c.hits.get("posit_codec.decode", 0) >= 1
    assert bool(jnp.all(got == want))


def test_ops_explicit_params_win_over_cache(scratch_cache):
    rng = np.random.default_rng(12)
    vals = jnp.asarray(rng.normal(0, 1, (32, 48)), jnp.float32)
    codes = posit.pack(vals, P16_2)
    c = autotune.AutotuneCache()
    c.put("posit_codec.decode", codes.shape, {"block_r": 256, "block_c": 512},
          fmts=(P16_2,))
    autotune.reset_cache(c)
    got = ops.decode(codes, P16_2, block_r=8, block_c=16)
    assert bool(jnp.all(got == posit.unpack(codes, P16_2)))
    assert bool(jnp.all(got == ops.decode(codes, P16_2)))


def test_largest_divisor_fallback():
    """Dispatch-time degrade rule for cached tiles that don't divide the
    live launch dim: largest divisor at or below the cached value."""
    assert ops._largest_divisor(6, 4) == 3
    assert ops._largest_divisor(7, 4) == 1
    assert ops._largest_divisor(8, 8) == 8
    assert ops._largest_divisor(8, 16) == 8
    assert ops._largest_divisor(48, 32) == 24


def test_degrade_tile_prime_dim_drops_cached_value():
    """A cached tile facing a prime live dim must NOT collapse to a
    1-element-per-program grid; the resolver drops it (None) so the
    kernel's untuned default applies instead."""
    assert ops._degrade_tile(6, 4) == 3        # degrades to a divisor
    assert ops._degrade_tile(8, 4) == 4        # divides: kept as-is
    assert ops._degrade_tile(7, 4) is None     # prime: dropped, not 1
    assert ops._degrade_tile(13, 8) is None    # prime: dropped, not 1
    assert ops._degrade_tile(1, 4) == 1        # dim 1: trivially exact
    assert ops._degrade_tile(7, None) is None  # no cached value at all


def test_ops_paged_prime_t_falls_back_to_untuned_default(scratch_cache):
    """Regression: a cached t_block over a prime multi-query span used to
    collapse to t_block=1 via _largest_divisor; it must instead drop to
    the kernel's untuned default — and stay value-neutral."""
    rng = np.random.default_rng(31)
    B, T, Hq, Hkv, Dh, ps, M = 2, 5, 4, 2, 8, 4, 4  # T=5 prime
    fmt = P16_1
    n_pages = 1 + B * M
    kp = jnp.asarray(rng.integers(0, 1 << fmt.n, (n_pages, ps, Hkv * Dh)),
                     jnp.int32)
    kp = jnp.where(kp == fmt.nar_code, 0, kp).astype(jnp.int16)
    vp = jnp.asarray(rng.integers(0, 1 << fmt.n, (n_pages, ps, Hkv * Dh)),
                     jnp.int32)
    vp = jnp.where(vp == fmt.nar_code, 0, vp).astype(jnp.int16)
    bt = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
    lengths = jnp.asarray([6, 9], jnp.int32)
    win = jnp.full((1,), 2 ** 30, jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, T, Hq, Dh)), jnp.float32)
    autotune.reset_cache(autotune.AutotuneCache())  # untuned default
    want = ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=fmt)
    c = autotune.AutotuneCache()
    c.put("paged_attention", (B, T, M, ps, Hkv * Dh), {"t_block": 4},
          fmts=(fmt,))
    autotune.reset_cache(c)
    got = ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=fmt)
    assert c.hits.get("paged_attention", 0) >= 1
    assert bool(jnp.all(got == want))


def test_ops_decode_sample_prime_vocab_falls_back(scratch_cache):
    """Regression companion for the fused decode epilogue: a cached
    v_block over a prime vocab runs with a masked partial last tile
    instead of a 1-column grid — sampled tokens bitwise unchanged."""
    rng = np.random.default_rng(32)
    B, D, V = 3, 16, 47  # V=47 prime
    x = jnp.asarray(rng.normal(0, 1, (B, D)), jnp.float32)
    w = posit.pack(jnp.asarray(rng.normal(0, 1, (D, V)), jnp.float32), P16_2)
    noise = jnp.asarray(rng.gumbel(size=(B, V)), jnp.float32)
    temp = jnp.float32(0.7)
    autotune.reset_cache(autotune.AutotuneCache())
    want = ops.decode_sample(x, w, noise, temp, plan="fused", fmt_w=P16_2,
                             top_k=5)
    c = autotune.AutotuneCache()
    c.put("decode_sample", (B, D, V), {"v_block": 32}, fmts=(P16_2,))
    autotune.reset_cache(c)
    got = ops.decode_sample(x, w, noise, temp, plan="fused", fmt_w=P16_2,
                            top_k=5)
    assert c.hits.get("decode_sample", 0) >= 1
    assert bool(jnp.all(got == want))


def test_ops_paged_rejects_nondividing_t_block(scratch_cache):
    """A cached t_block that doesn't divide this launch's T must degrade
    to the largest divisor of T below it, not crash the kernel — and the
    degraded tiling stays value-neutral."""
    rng = np.random.default_rng(13)
    B, T, Hq, Hkv, Dh, ps, M = 2, 3, 4, 2, 8, 4, 4
    fmt = P16_1
    n_pages = 1 + B * M
    kp = jnp.asarray(rng.integers(0, 1 << fmt.n, (n_pages, ps, Hkv * Dh)),
                     jnp.int32)
    kp = jnp.where(kp == fmt.nar_code, 0, kp).astype(jnp.int16)
    vp = jnp.asarray(rng.integers(0, 1 << fmt.n, (n_pages, ps, Hkv * Dh)),
                     jnp.int32)
    vp = jnp.where(vp == fmt.nar_code, 0, vp).astype(jnp.int16)
    bt = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
    lengths = jnp.asarray([7, 11], jnp.int32)
    win = jnp.full((1,), 2 ** 30, jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, T, Hq, Dh)), jnp.float32)
    default = ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=fmt)
    c = autotune.AutotuneCache()
    c.put("paged_attention", (B, T, M, ps, Hkv * Dh), {"t_block": 2},
          fmts=(fmt,))
    autotune.reset_cache(c)
    got = ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=fmt)
    assert bool(jnp.all(got == default))


def test_ops_decode_sample_resolves_v_block(scratch_cache):
    """Cached vocab tiles for the fused decode epilogue resolve at
    dispatch and never change the sampled token: the 0 sentinel collapses
    the vocab grid, and a non-dividing tile masks its partial last tile."""
    rng = np.random.default_rng(21)
    B, D, V = 3, 16, 48
    x = jnp.asarray(rng.normal(0, 1, (B, D)), jnp.float32)
    w = posit.pack(jnp.asarray(rng.normal(0, 1, (D, V)), jnp.float32), P16_2)
    noise = jnp.asarray(rng.gumbel(size=(B, V)), jnp.float32)
    temp = jnp.float32(0.7)
    autotune.reset_cache(autotune.AutotuneCache())  # empty: all misses
    want = ops.decode_sample(x, w, noise, temp, plan="fused", fmt_w=P16_2,
                             top_k=5)
    for vb in (0, 32):  # whole-vocab sentinel; 32 leaves a 16-wide tail
        c = autotune.AutotuneCache()
        c.put("decode_sample", (B, D, V), {"v_block": vb}, fmts=(P16_2,))
        autotune.reset_cache(c)
        got = ops.decode_sample(x, w, noise, temp, plan="fused",
                                fmt_w=P16_2, top_k=5)
        assert c.hits.get("decode_sample", 0) >= 1
        assert bool(jnp.all(got == want))


@pytest.mark.parametrize("transpose", [False, True], ids=["dv", "tied_vd"])
@pytest.mark.parametrize("top_k", [0, 5], ids=["greedy", "top_k"])
def test_decode_sample_masked_last_tile(top_k, transpose):
    """A vocab tile that does not divide V leaves a partial last tile; its
    columns past V are masked, so the sampled tokens are bitwise those of
    the whole-vocab grid."""
    from repro.kernels import paged_attention as pa
    rng = np.random.default_rng(33)
    B, D, V = 3, 16, 47  # 3 tiles of 16, the last one 15 wide
    x = jnp.asarray(rng.normal(0, 1, (B, D)), jnp.float32)
    w = posit.pack(jnp.asarray(rng.normal(0, 1, (D, V)), jnp.float32), P16_2)
    if transpose:
        w = w.T
    noise = jnp.asarray(rng.gumbel(size=(B, V)), jnp.float32)
    kw = dict(plan="fused", fmt_w=P16_2, transpose=transpose,
              greedy=top_k == 0, top_k=top_k, interpret=True)
    want = pa.decode_sample(x, w, noise, jnp.float32(0.7), v_block=V, **kw)
    got = pa.decode_sample(x, w, noise, jnp.float32(0.7), v_block=16, **kw)
    assert bool(jnp.all(got == want))


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_default_v_block_fits_vmem(name):
    """The untuned vocab tile of every published head fits the VMEM tile
    budget (or is the narrowest lane-aligned tile), and never spans the
    whole vocabulary of a head too large for one tile."""
    from repro.kernels import paged_attention as pa
    cfg = configs.get(name)
    V, D = cfg.vocab_size, cfg.d_model
    vb = pa.default_v_block(V, D)
    if V * D * 4 <= pa._V_TILE_BYTES:
        assert vb == V
    else:
        assert vb < V and vb % 128 == 0
        assert vb * D * 4 <= pa._V_TILE_BYTES or vb == 128


def test_ops_prefill_resolves_launch_knobs(scratch_cache):
    """Cached TPU launch knobs (dimension_semantics / VMEM budget) for the
    fused prefill kernel resolve at dispatch and are value-neutral."""
    rng = np.random.default_rng(22)
    B, C, Hq, Hkv, Dh, ps, M = 2, 4, 4, 2, 8, 4, 2
    fmt = P16_1
    F = Hkv * Dh
    n_pages = 1 + B * M
    q = jnp.asarray(rng.normal(0, 1, (B, C, Hq, Dh)), jnp.float32)
    kc = jnp.asarray(rng.normal(0, 1, (B, C, Hkv, Dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(0, 1, (B, C, Hkv, Dh)), jnp.float32)
    kp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                jnp.float32), fmt)
    vp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                jnp.float32), fmt)
    bt = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
    starts = jnp.full((B,), ps, jnp.int32)
    win = jnp.full((1,), 2 ** 30, jnp.int32)
    autotune.reset_cache(autotune.AutotuneCache())
    want = ops.prefill_attention_paged(q, kc, vc, kp, vp, bt, starts, win,
                                       fmt_kv=fmt)
    c = autotune.AutotuneCache()
    c.put("prefill_attention", (B, C, M, ps, F),
          {"dimension_semantics": "arbitrary", "vmem_limit_mb": 64},
          fmts=(fmt,))
    autotune.reset_cache(c)
    got = ops.prefill_attention_paged(q, kc, vc, kp, vp, bt, starts, win,
                                      fmt_kv=fmt)
    assert c.hits.get("prefill_attention", 0) >= 1
    for a, b in zip(got, want):
        assert bool(jnp.all(a == b))


# ---------------------------------------------------------------------------
# the sweep itself
# ---------------------------------------------------------------------------


def test_sweep_smoke_codec():
    rng = np.random.default_rng(14)
    vals = jnp.asarray(rng.normal(0, 1, (64, 128)), jnp.float32)
    codes = posit.pack(vals, P16_2)

    def run(params):
        return lambda: posit_codec.decode(codes, P16_2, interpret=True,
                                          **params)

    params, ms, table = autotune.sweep("posit_codec.decode", (64, 128), run,
                                       fmts=(P16_2,), reps=1)
    assert params in list(autotune.candidates("posit_codec.decode"))
    assert ms > 0
    assert len(table) == 16  # full 4x4 codec grid, pruned or timed
    timed = [t for t in table if t["ms"] is not None]
    assert timed and all(not t["pruned"] for t in timed)
    # the winner must be bitwise the default tiling's output
    got = posit_codec.decode(codes, P16_2, interpret=True, **params)
    assert bool(jnp.all(got == posit_codec.decode(codes, P16_2,
                                                  interpret=True)))


def test_oracle_cost_positive_finite():
    import math
    for kernel in autotune.TUNABLES:
        shape = {"posit_codec.decode": (512, 512),
                 "posit_codec.encode": (512, 512),
                 "posit_matmul": (256, 256, 256),
                 "posit_matmul_grouped": (4, 128, 128, 128),
                 "paged_attention": (4, 8, 8, 16, 128),
                 "prefill_attention": (2, 64, 8, 16, 128),
                 "decode_sample": (4, 256, 4096)}[kernel]
        fmts = {"posit_matmul": (P16_2, P16_2),
                "posit_matmul_grouped": (None, P16_2)}.get(kernel, (P16_2,))
        for params in autotune.candidates(kernel):
            cost = autotune.oracle_cost(kernel, shape, params, fmts)
            assert math.isfinite(cost) and cost > 0
