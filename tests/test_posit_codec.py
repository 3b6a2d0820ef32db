"""Posit codec: exhaustive bit-exactness + hypothesis invariants.

Three implementations (exact Fraction oracle / numpy int64 / JAX int32)
must agree everywhere; the JAX codec is the one the kernels lower.
"""
import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import posit as pj
from repro.core import posit_np as pnp
from repro.core import posit_py as ppy
from repro.core.formats import P8_2, P13_2, P16_2, PositFormat
from repro.kernels import posit_codec

FORMATS = [P8_2, PositFormat(8, 0), PositFormat(8, 1), P13_2, P16_2,
           PositFormat(10, 2), PositFormat(12, 3), PositFormat(6, 1),
           PositFormat(16, 1), PositFormat(16, 3)]


def _bits(v):
    """f32 values as their int32 patterns: `==` would hide a -0.0."""
    return np.asarray(v, np.float32).view(np.int32)


@pytest.mark.parametrize("fmt", FORMATS, ids=str)
def test_decode_exhaustive_np_vs_jax(fmt):
    codes = np.arange(1 << fmt.n)
    vn = _bits(pnp.decode_np(codes, fmt))
    vj = _bits(pj.decode(jnp.asarray(codes, jnp.int32), fmt))
    assert np.array_equal(vn, vj), np.where(vn != vj)


@pytest.mark.parametrize("fmt,store", [(P16_2, jnp.int16), (P8_2, jnp.int8)],
                         ids=["P16_2-int16", "P8_2-int8"])
def test_decode_kernel_exhaustive_bitwise(fmt, store):
    """Every code through the Pallas decode kernel (interpret mode), from
    its storage container: 65536 P(16,2) codes as [256, 256] int16, the
    256 P(8,2) codes as int8."""
    codes = np.arange(1 << fmt.n).reshape(-1, 256)
    got = posit_codec.decode(jnp.asarray(codes).astype(store), fmt,
                             interpret=True)
    assert np.array_equal(_bits(got), _bits(pnp.decode_np(codes, fmt)))


def _primitive_count(jaxpr) -> int:
    """Primitive equations of a jaxpr and its sub-jaxprs, broadcasts out."""
    n = 0
    for eqn in jaxpr.eqns:
        subs = [v for v in eqn.params.values()
                if isinstance(v, (jex.core.Jaxpr, jex.core.ClosedJaxpr))]
        if subs:
            n += sum(_primitive_count(getattr(j, "jaxpr", j)) for j in subs)
        elif eqn.primitive.name != "broadcast_in_dim":
            n += 1
    return n


@pytest.mark.parametrize("fmt", [P16_2, P8_2], ids=str)
def test_decode_op_count(fmt):
    """The decode every kernel inlines stays a short straight line: the
    regime's run comes from the f32 convert, not from a select chain
    (`bit_length32` alone traces to 39 ops)."""
    jaxpr = jax.make_jaxpr(lambda c: pj.decode(c, fmt))(
        jnp.zeros((8, 128), jnp.int32))
    assert _primitive_count(jaxpr.jaxpr) <= 40


@pytest.mark.parametrize("fmt", [P8_2, PositFormat(8, 0), PositFormat(6, 1)], ids=str)
def test_decode_exhaustive_vs_oracle(fmt):
    codes = np.arange(1 << fmt.n)
    vn = pnp.decode_np(codes, fmt)
    for c in codes:
        ve = ppy.decode_exact(int(c), fmt)
        if ve is None:
            assert np.isnan(vn[c])
        else:
            assert float(ve) == vn[c], hex(c)


@pytest.mark.parametrize("fmt", FORMATS, ids=str)
def test_roundtrip_exhaustive(fmt):
    codes = np.arange(1 << fmt.n)
    v = pnp.decode_np(codes, fmt)
    assert (pnp.encode_np(v, fmt) == codes).all()
    vj = pj.decode(jnp.asarray(codes, jnp.int32), fmt)
    assert (np.asarray(pj.encode(vj, fmt)) == codes).all()


@pytest.mark.parametrize("fmt", [P16_2, P13_2, P8_2], ids=str)
def test_encode_jax_matches_numpy_random(fmt, rng):
    xs = np.concatenate([
        rng.normal(0, 1, 4000), rng.normal(0, 1e-7, 1000),
        rng.normal(0, 1e7, 1000),
        [0.0, -0.0, np.inf, -np.inf, np.nan, 1e-42, -1e-44],
    ]).astype(np.float32)
    cn = pnp.encode_np(xs.astype(np.float64), fmt)
    cj = np.asarray(pj.encode(jnp.asarray(xs), fmt))
    assert (cn == cj).all()


@pytest.mark.parametrize("fmt", [P8_2, PositFormat(6, 1)], ids=str)
def test_encode_matches_oracle_random(fmt, rng):
    xs = np.concatenate([rng.normal(0, 1, 300), rng.normal(0, 1e-6, 150),
                         rng.normal(0, 1e6, 150)])
    cn = pnp.encode_np(xs, fmt)
    for x, c in zip(xs, cn):
        assert ppy.from_float(float(x), fmt) == c, x


def test_pattern_rounding_regime_gap():
    """Regression: posit RNE is pattern-space, not linear nearest-value.

    In P(8,2), between code 1 (2^-24) and code 2 (2^-20) the pattern
    midpoint is 2^-22; a value just above it must round UP even though it
    is linearly closer to 2^-24."""
    x = 4.19e-7  # > 2^-22 = 2.38e-7, but linearly nearer to 5.96e-8
    assert ppy.from_float(x, P8_2) == 0x2
    assert int(pnp.encode_np(np.array([x]), P8_2)[0]) == 0x2


# ---------------------------------------------------------------------------
# hypothesis property tests
# ---------------------------------------------------------------------------

fmt_strategy = st.sampled_from([P8_2, P13_2, P16_2, PositFormat(10, 2),
                                PositFormat(8, 0)])


@given(fmt=fmt_strategy, data=st.data())
@settings(max_examples=200, deadline=None)
def test_negation_symmetry(fmt, data):
    c = data.draw(st.integers(0, fmt.mask))
    if c in (0, fmt.nar_code):
        return
    neg = (-c) & fmt.mask
    v = pnp.decode_np(np.array([c, neg]), fmt)
    assert v[0] == -v[1]


@given(fmt=fmt_strategy, data=st.data())
@settings(max_examples=200, deadline=None)
def test_monotonic_codes(fmt, data):
    """Posit codes, read as signed n-bit ints, order exactly like values."""
    c1 = data.draw(st.integers(0, fmt.mask))
    c2 = data.draw(st.integers(0, fmt.mask))
    if fmt.nar_code in (c1, c2):
        return
    def signed(c):
        return c - (1 << fmt.n) if c & fmt.sign_mask else c
    v = pnp.decode_np(np.array([c1, c2]), fmt)
    if signed(c1) < signed(c2):
        assert v[0] < v[1]
    elif signed(c1) > signed(c2):
        assert v[0] > v[1]


@given(fmt=fmt_strategy,
       x=st.floats(min_value=-1e30, max_value=1e30,
                   allow_nan=False, allow_infinity=False))
@settings(max_examples=300, deadline=None)
def test_encode_is_clamping_total(fmt, x):
    """Every finite float encodes to a finite posit (never NaR), and a
    non-zero float never encodes to zero (posit has no underflow)."""
    c = int(pnp.encode_np(np.array([x]), fmt)[0])
    assert c != fmt.nar_code
    if x != 0:
        assert c != 0
    v = float(pnp.decode_np(np.array([c]), fmt)[0])
    maxpos = float(pnp.decode_np(np.array([fmt.maxpos_code]), fmt)[0])
    assert abs(v) <= maxpos


@given(fmt=fmt_strategy, data=st.data())
@settings(max_examples=200, deadline=None)
def test_encode_monotonic_in_value(fmt, data):
    x = data.draw(st.floats(-1e20, 1e20, allow_nan=False))
    y = data.draw(st.floats(-1e20, 1e20, allow_nan=False))
    if x > y:
        x, y = y, x
    cx, cy = (int(v) for v in pnp.encode_np(np.array([x, y]), fmt))
    def signed(c):
        return c - (1 << fmt.n) if c & fmt.sign_mask else c
    assert signed(cx) <= signed(cy)


def test_pack_unpack_storage_dtypes():
    x = jnp.asarray(np.linspace(-3, 3, 64, dtype=np.float32))
    for fmt, dt in [(P8_2, jnp.int8), (P16_2, jnp.int16), (P13_2, jnp.int16)]:
        codes = pj.pack(x, fmt)
        assert codes.dtype == dt
        y = pj.unpack(codes, fmt)
        assert np.allclose(np.asarray(y), np.asarray(pj.quantize(x, fmt)))


def test_quantize_ste_gradient_is_identity():
    import jax
    x = jnp.asarray(np.linspace(-2, 2, 32, dtype=np.float32))
    g = jax.grad(lambda t: jnp.sum(pj.quantize_ste(t, P13_2) ** 2))(x)
    # STE: d/dx sum(q(x)^2) == 2*q(x) (identity through the quantizer)
    assert np.allclose(np.asarray(g), 2 * np.asarray(pj.quantize(x, P13_2)))
