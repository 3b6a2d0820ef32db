"""Pallas kernel sweeps (interpret mode on CPU) vs the pure-jnp oracles:
shapes x dtypes x formats, asserting bit identity (codec/pdpu) or
allclose (fused matmul f32 path)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import posit_np
from repro.core.formats import P8_2, P13_2, P16_1, P16_2, PDPUConfig
from repro.kernels import ops, ref

SHAPES_ELTWISE = [(8, 128), (256, 512), (300, 700), (17, 129), (1000,),
                  (3, 5, 257), (1, 1)]
FORMATS = [P8_2, P13_2, P16_2]


@pytest.mark.parametrize("fmt", FORMATS, ids=str)
@pytest.mark.parametrize("shape", SHAPES_ELTWISE, ids=str)
def test_decode_kernel_sweep(fmt, shape, rng):
    # against the numpy codec, bitwise: kernels/ref.py shares the kernel's
    # decode body, so it cannot check it
    codes = rng.integers(0, 1 << fmt.n, shape)
    got = np.asarray(ops.decode(jnp.asarray(codes, jnp.int32), fmt))
    want = posit_np.decode_np(codes, fmt).astype(np.float32)
    assert np.array_equal(got.view(np.int32), want.view(np.int32))


@pytest.mark.parametrize("fmt", FORMATS, ids=str)
@pytest.mark.parametrize("shape", SHAPES_ELTWISE, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
def test_encode_kernel_sweep(fmt, shape, dtype, rng):
    x = jnp.asarray(rng.normal(0, 2, shape), dtype)
    got = np.asarray(ops.encode(x, fmt))
    want = np.asarray(ref.encode_ref(x, fmt))
    assert (got == want).all()
    assert got.dtype == want.dtype  # storage container dtype


MM_CASES = [
    ((64, 128, 96), P16_2, P16_2, P16_2, (32, 32, 64)),
    ((130, 260, 70), P13_2, P13_2, P16_2, (64, 64, 128)),
    ((32, 64, 32), P8_2, P8_2, None, (32, 32, 32)),
    ((257, 129, 65), P13_2, P16_2, P13_2, (64, 64, 64)),  # mixed in-formats
    ((8, 512, 8), P16_2, P16_2, None, (8, 8, 128)),
]


@pytest.mark.parametrize("case", MM_CASES, ids=lambda c: f"{c[0]}-{c[1]}-{c[3]}")
def test_fused_matmul_sweep(case, rng):
    (M, K, N), fa, fb, fo, (bm, bn, bk) = case
    a = jnp.asarray(rng.integers(0, 1 << fa.n, (M, K)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 1 << fb.n, (K, N)), jnp.int32)
    a = jnp.where(a == fa.nar_code, 0, a)
    b = jnp.where(b == fb.nar_code, 0, b)
    got = ops.fused_matmul(a, b, fa, fb, fo, bm=bm, bn=bn, bk=bk)
    want = ref.posit_matmul_ref(a, b, fa, fb, fo, bk=bk)
    if fo is None:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    else:
        assert (np.asarray(got) == np.asarray(want)).all()


def test_fused_matmul_single_rounding_property(rng):
    """Kernel output == encode(f32 matmul of decoded inputs): exactly one
    rounding (the fused property).

    Two separately compiled f32 dots may reduce in different orders, so a
    value sitting exactly on a posit rounding boundary can land one code
    apart — allow off-by-one codes on <0.5% of outputs, nothing more."""
    fa = fo = P16_2
    a = jnp.asarray(rng.integers(0, 1 << 16, (48, 64)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 1 << 16, (64, 32)), jnp.int32)
    a = jnp.where(a == fa.nar_code, 0, a)
    b = jnp.where(b == fa.nar_code, 0, b)
    from repro.core import posit
    manual = posit.pack(
        jnp.dot(posit.decode(a, fa), posit.decode(b, fa),
                preferred_element_type=jnp.float32), fo)
    got = np.asarray(ops.fused_matmul(a, b, fa, fa, fo, bm=16, bn=16, bk=64))
    manual = np.asarray(manual)
    diff = np.abs(got.astype(np.int64) - manual.astype(np.int64))
    assert diff.max() <= 1, "more than one code apart => extra rounding"
    assert (diff != 0).mean() < 0.005


PDPU_GEMM_CASES = [
    (PDPUConfig(P13_2, P16_2, N=4, w_m=14), (24, 16, 40), (16, 32)),
    (PDPUConfig(P8_2, P8_2, N=4, w_m=10), (16, 8, 16), (8, 16)),
    (PDPUConfig(P16_2, P16_2, N=8, w_m=14), (8, 16, 8), (8, 8)),
]


@pytest.mark.parametrize("case", PDPU_GEMM_CASES,
                         ids=lambda c: f"{c[0].name}-{c[1]}")
def test_pdpu_gemm_kernel_bit_exact(case, rng):
    cfg, (M, K, N), (bm, bn) = case
    a = jnp.asarray(rng.integers(0, 1 << cfg.fmt_in.n, (M, K)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 1 << cfg.fmt_in.n, (K, N)), jnp.int32)
    got = np.asarray(ops.pdpu_matmul(a, b, cfg, bm=bm, bn=bn))
    want = np.asarray(ref.pdpu_matmul_ref(a, b, cfg))
    assert (got == want).all()


def test_matmul_posit_weights_path(rng):
    from repro.core import posit
    x = jnp.asarray(rng.normal(0, 1, (16, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.1, (64, 32)).astype(np.float32))
    w_codes = posit.pack(w, P16_2)
    got = ops.matmul_posit_weights(x, w_codes, P16_2)
    want = jnp.dot(x, posit.unpack(w_codes, P16_2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# multi-query paged decode: the 4-D q [B, T, Hq, Dh] grid
# ---------------------------------------------------------------------------


def _mq_setup(seed=1, B=3, T=4, Hq=4, Hkv=2, Dh=8, ps=4, M=5, fmt=P16_1,
              lengths=(7, 15, 12)):
    """Coded page pool (valid random codes — recycled-page garbage), one
    distinct page run per slot, and a [B, T, Hq, Dh] query block.
    `lengths` count all T new tokens as already written.  A local
    generator (not the session rng fixture): these tests must not shift
    the shared stream other test files' draws come from."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + B * M
    F = Hkv * Dh
    dt = {8: jnp.int8, 16: jnp.int16}[fmt.storage_bits]
    kp = jnp.asarray(rng.integers(0, 1 << fmt.n, (n_pages, ps, F)), jnp.int32)
    kp = jnp.where(kp == fmt.nar_code, 0, kp).astype(dt)
    vp = jnp.asarray(rng.integers(0, 1 << fmt.n, (n_pages, ps, F)), jnp.int32)
    vp = jnp.where(vp == fmt.nar_code, 0, vp).astype(dt)
    bt = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, T, Hq, Dh)), jnp.float32)
    return q, kp, vp, bt, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("t_block", [1, 2, 4, None])
def test_paged_attention_mq_matches_ref(t_block):
    q, kp, vp, bt, lengths = _mq_setup()
    win = jnp.full((1,), 2 ** 30, jnp.int32)
    got = ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=P16_1,
                              softcap_val=20.0, t_block=t_block)
    want = ref.paged_attention_mq_ref(q, kp, vp, bt, lengths, win,
                                      fmt_kv=P16_1, softcap_val=20.0)
    # streaming softmax vs dense softmax over garbage-code magnitudes
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-5)


def test_paged_attention_mq_window_plus_softcap():
    q, kp, vp, bt, lengths = _mq_setup()
    win = jnp.full((1,), 5, jnp.int32)
    got = ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=P16_1,
                              softcap_val=12.0)
    want = ref.paged_attention_mq_ref(q, kp, vp, bt, lengths, win,
                                      fmt_kv=P16_1, softcap_val=12.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-5)


def test_paged_attention_mq_t_block_bitwise_independent():
    """The query-tile split is the autotuned knob: any tiling must be
    bitwise identical (each query row's streaming pass is independent)."""
    q, kp, vp, bt, lengths = _mq_setup()
    win = jnp.full((1,), 2 ** 30, jnp.int32)
    outs = [ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=P16_1,
                                softcap_val=20.0, t_block=tb)
            for tb in (1, 2, 4)]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))


def test_paged_attention_mq_t1_matches_3d_path_bitwise():
    q, kp, vp, bt, lengths = _mq_setup()
    o3 = ops.paged_attention(q[:, 0], kp, vp, bt, lengths - 3,
                             jnp.full((1,), 2 ** 30, jnp.int32), fmt_kv=P16_1)
    o4 = ops.paged_attention(q[:, :1], kp, vp, bt, lengths - 3,
                             jnp.full((1,), 2 ** 30, jnp.int32), fmt_kv=P16_1)
    np.testing.assert_array_equal(np.asarray(o3), np.asarray(o4[:, 0]))


def test_paged_attention_mq_partials_finalize_matches_direct():
    """partials=True under the 4-D grid: normalizing (o, m, l) must be
    bitwise the direct kernel output (the single-'shard' merge case)."""
    q, kp, vp, bt, lengths = _mq_setup()
    win = jnp.full((1,), 2 ** 30, jnp.int32)
    o, m, l = ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=P16_1,
                                  partials=True)
    direct = ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=P16_1)
    norm = o / jnp.maximum(l, 1e-30)[..., None]
    np.testing.assert_array_equal(np.asarray(norm), np.asarray(direct))


def test_paged_attention_mq_zero_length_slot():
    """A slot with length 0 has every kv position masked: the streaming
    kernel's normalizer stays 0 and finalize yields exact finite zeros
    (NOT the dense reference's uniform softmax over -inf rows).  The
    other slots must still match the reference."""
    q, kp, vp, bt, lengths = _mq_setup(lengths=(7, 15, 0))
    win = jnp.full((1,), 2 ** 30, jnp.int32)
    got = ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=P16_1,
                              softcap_val=20.0)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got[2]),
                                  np.zeros_like(np.asarray(got[2])))
    want = ref.paged_attention_mq_ref(q, kp, vp, bt, lengths, win,
                                      fmt_kv=P16_1, softcap_val=20.0)
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                               rtol=2e-5, atol=1e-5)
