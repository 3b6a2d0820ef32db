"""Property-based codec hardening (hypothesis): the invariants the
dispatch layer leans on, pinned across every supported format.

Low-bit posit inference lives or dies on exact encode/decode behavior
(Deep Positron; Lu et al.), so the codec properties the execution plans
assume — round-trip identity on representable values, order preservation,
pack/unpack inverse — are pinned here as laws over the whole P(n<=16)
format space rather than point checks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import posit
from repro.core.formats import P8_2, P13_2, P16_2, PositFormat

# every (n, es) corner the framework supports: p8/p16 containers, es 0..3
FORMATS = [P8_2, PositFormat(8, 0), PositFormat(8, 1), PositFormat(10, 2),
           P13_2, PositFormat(12, 3), P16_2, PositFormat(16, 0),
           PositFormat(6, 1)]

fmt_strategy = st.sampled_from(FORMATS)

_STORAGE = {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}


def _codes(fmt, data, size=16):
    return np.array([data.draw(st.integers(0, fmt.mask)) for _ in range(size)])


# ---------------------------------------------------------------------------
# round-trip identity on representable values
# ---------------------------------------------------------------------------


@given(fmt=fmt_strategy, data=st.data())
@settings(max_examples=150, deadline=None)
def test_roundtrip_identity_on_codes(fmt, data):
    """encode(decode(c)) == c for every code: decoded posit values are
    exactly representable, so re-encoding is the identity (NaR included —
    decode gives nan, encode maps nan back to the NaR code)."""
    c = jnp.asarray(_codes(fmt, data), jnp.int32)
    v = posit.decode(c, fmt)
    back = np.asarray(posit.encode(v, fmt)) & fmt.mask
    assert (back == np.asarray(c)).all()


@given(fmt=fmt_strategy, data=st.data())
@settings(max_examples=150, deadline=None)
def test_quantize_is_idempotent(fmt, data):
    """quantize(quantize(x)) == quantize(x): one rounding, then a fixpoint.
    This is what lets pack_params replace on-the-fly fake_quant."""
    x = np.array([data.draw(st.floats(-1e8, 1e8, allow_nan=False, width=32))
                  for _ in range(16)], np.float32)
    q1 = posit.quantize(jnp.asarray(x), fmt)
    q2 = posit.quantize(q1, fmt)
    assert (np.asarray(q1) == np.asarray(q2)).all()


# ---------------------------------------------------------------------------
# monotonicity
# ---------------------------------------------------------------------------


def _signed(c, fmt):
    return c - (1 << fmt.n) if c & fmt.sign_mask else c


@given(fmt=fmt_strategy, data=st.data())
@settings(max_examples=150, deadline=None)
def test_decode_monotonic_jax(fmt, data):
    """The JAX codec (the one the Pallas kernels lower) orders codes-as-
    signed-ints exactly like decoded values."""
    c1 = data.draw(st.integers(0, fmt.mask))
    c2 = data.draw(st.integers(0, fmt.mask))
    if fmt.nar_code in (c1, c2):
        return
    v = np.asarray(posit.decode(jnp.asarray([c1, c2], jnp.int32), fmt))
    s1, s2 = _signed(c1, fmt), _signed(c2, fmt)
    if s1 < s2:
        assert v[0] < v[1]
    elif s1 > s2:
        assert v[0] > v[1]
    else:
        assert v[0] == v[1]


@given(fmt=fmt_strategy, data=st.data())
@settings(max_examples=150, deadline=None)
def test_encode_monotonic_jax(fmt, data):
    """encode is monotone in the float value (never reorders operands —
    what keeps fake_quant and fused rankings consistent)."""
    # width=32 bounds must themselves be float32 values
    lim = float(np.float32(1e20))
    x = data.draw(st.floats(-lim, lim, allow_nan=False, width=32))
    y = data.draw(st.floats(-lim, lim, allow_nan=False, width=32))
    if x > y:
        x, y = y, x
    cx, cy = (int(c) & fmt.mask for c in
              np.asarray(posit.encode(jnp.asarray([x, y], jnp.float32), fmt)))
    assert _signed(cx, fmt) <= _signed(cy, fmt)


# ---------------------------------------------------------------------------
# pack / unpack inverse (the storage layer the checkpoints rely on)
# ---------------------------------------------------------------------------


@given(fmt=fmt_strategy, data=st.data())
@settings(max_examples=150, deadline=None)
def test_pack_unpack_inverse(fmt, data):
    """unpack(pack(x)) == quantize(x), pack lands in the narrowest
    container, and re-packing the unpacked values is code-identical
    (no second rounding)."""
    x = np.array([data.draw(st.floats(-1e6, 1e6, allow_nan=False, width=32))
                  for _ in range(16)], np.float32)
    codes = posit.pack(jnp.asarray(x), fmt)
    assert codes.dtype == _STORAGE[fmt.storage_bits]
    v = posit.unpack(codes, fmt)
    assert (np.asarray(v) == np.asarray(posit.quantize(jnp.asarray(x), fmt))).all()
    again = posit.pack(v, fmt)
    assert (np.asarray(again) == np.asarray(codes)).all()


@given(fmt=st.sampled_from([P8_2, PositFormat(8, 0), P13_2, P16_2,
                            PositFormat(16, 1)]),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_pack_params_posit_unpack_inverse(fmt, seed):
    """pack_params -> posit.unpack recovers exactly the quantized masters
    for every packable leaf, across formats — the checkpoint conversion
    adds no rounding beyond the one fake_quant applies."""
    from repro import configs
    from repro.core.quant import QuantPolicy
    from repro.models import api, packing

    cfg = configs.get_smoke("qwen3_moe_235b").replace(
        n_layers=1, d_model=16, n_heads=2, n_kv_heads=1, head_dim=8,
        n_experts=4, top_k=2, moe_d_ff=8, vocab_size=32,
        quant=QuantPolicy(weights=fmt))
    params = api.init(jax.random.key(seed), cfg)
    packed = api.pack_params(params, cfg)
    for path in packing.packable_paths(cfg):
        leaf = params
        code = packed
        for k in path:
            leaf, code = leaf[k], code[k]
        want = posit.quantize(jnp.asarray(leaf, jnp.float32), fmt)
        got = posit.unpack(code, fmt)
        assert code.dtype == _STORAGE[fmt.storage_bits], path
        assert (np.asarray(got) == np.asarray(want)).all(), path
