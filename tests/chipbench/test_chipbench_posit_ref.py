"""The benchmark's own posit codec against the program's numpy codec:
the reference decodes weights and rounds KV with it, so it must agree
with the format's definition on every code."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import posit_ref
from repro.core import posit_np
from repro.core.formats import P8_2, P16_2


def _signed(codes, n):
    return codes - (codes >= 1 << (n - 1)) * (1 << n)


@pytest.mark.parametrize("fmt,dtype", [(P16_2, np.int16), (P8_2, np.int8)])
def test_decode_every_code(fmt, dtype):
    codes = np.arange(1 << fmt.n)
    want = posit_np.decode_np(codes, fmt)
    nar = np.isnan(want)
    table = posit_ref.decode_table(fmt.n, fmt.es)
    assert np.array_equal(np.isnan(table), nar)
    assert np.array_equal(table[~nar], want[~nar])
    got = np.asarray(posit_ref.decode(
        jnp.asarray(_signed(codes, fmt.n).astype(dtype)), fmt.n, fmt.es),
        np.float64)
    assert np.array_equal(got[~nar], want[~nar]) and np.isnan(got[nar]).all()


def test_codes_from_values_truncate():
    x = np.random.default_rng(0).standard_normal(50000).astype(np.float32)
    x = x * 0.03
    x[:3] = 0.0
    codes = posit_ref.codes_from_values(jnp.asarray(x), 16, 2)
    assert codes.dtype == jnp.int16
    v = np.asarray(posit_ref.decode(codes, 16, 2), np.float64)
    # a code is a posit value: re-encoding it is the identity
    re = posit_np.encode_np(v, P16_2)
    assert np.array_equal(re, np.asarray(codes).astype(np.int64) & 0xFFFF)
    # truncation: same sign, never larger in magnitude, within one ulp
    nz = x != 0
    assert np.all(np.sign(v[nz]) == np.sign(x[nz]))
    assert np.all(np.abs(v) <= np.abs(x.astype(np.float64)) + 1e-12)
    assert np.all(v[~nz] == 0)


def test_round_to_format_matches_rne():
    rng = np.random.default_rng(1)
    y = (rng.standard_normal(50000) * np.exp(rng.uniform(-25, 25, 50000)))
    y = y.astype(np.float32)
    got = np.asarray(posit_ref.round_to_format(jnp.asarray(y), 8, 2))
    want = posit_np.decode_np(posit_np.encode_np(y.astype(np.float64), P8_2),
                              P8_2)
    assert np.array_equal(got, want)
    # saturation: never to zero, never past maxpos
    big = np.asarray(posit_ref.round_to_format(jnp.asarray([1e30, -1e-30]),
                                               8, 2))
    assert big[0] == 2.0 ** 24 and big[1] == -(2.0 ** -24)
