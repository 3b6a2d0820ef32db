"""Vectorized, jittable posit codec in JAX (int32/uint32 datapath).

Bit-for-bit identical to the numpy reference (`posit_np`) and the exact
Fraction oracle (`posit_py`) — enforced by exhaustive tests.  Supports
n <= 16 (the paper's entire design space) with an exact float32 bridge:
every P(n<=16, es<=2) value has <= 14 significand bits and |scale| <= 60,
so decode -> f32 is lossless and the MXU can compute on decoded values
with zero representation error.

These functions are also the building blocks of the Pallas kernels
(`repro.kernels`): the same int32 bit manipulation lowers to TPU VPU ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .formats import PositFormat

_I32 = jnp.int32
_U32 = jnp.uint32


def _check_jax_fmt(fmt: PositFormat):
    if fmt.n > 16:
        raise ValueError("JAX posit codec supports n <= 16 (int32 datapath)")
    if fmt.max_scale > 120:
        raise ValueError("format scale range exceeds the exact float32 bridge")


def bit_length32(x):
    """Vectorized bit_length for non-negative int32/uint32 (0 -> 0).

    Select-chain binary search — only shifts/compares, so it lowers inside
    Pallas TPU kernels (unlike lax.clz) for any 31-bit input.  It serves
    `decode_unpacked` (and through it the bit-exact PDPU datapath) and the
    PDPU normalizer; `decode` needs no bit length this wide and finds its
    regime with the f32 convert instead."""
    v = x.astype(_U32)
    out = jnp.zeros(v.shape, _I32)
    for s in (16, 8, 4, 2, 1):
        ge = v >= (_U32(1) << s)
        out = out + jnp.where(ge, _I32(s), 0)
        v = jnp.where(ge, v >> s, v)
    return out + (x != 0).astype(_I32)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_unpacked(codes, fmt: PositFormat):
    """codes -> (is_zero, is_nar, sign, scale, frac); frac in
    [2**fb, 2**(fb+1)) for finite non-zero values, fb = fmt.frac_bits.

    All outputs int32 (flags bool).  NaR/zero rows return sign=scale=frac=0.
    """
    _check_jax_fmt(fmt)
    n, es = fmt.n, fmt.es
    x = codes.astype(_U32) & _U32(fmt.mask)
    is_zero = x == 0
    is_nar = x == _U32(fmt.nar_code)
    sign = ((x >> (n - 1)) & 1).astype(_I32)
    xa = jnp.where(sign == 1, (_U32(0) - x) & _U32(fmt.mask), x)
    # left-align the n-1 post-sign bits so the first regime bit sits at bit 30
    body = (xa << (32 - n)) & _U32(0x7FFFFFFF)
    r0 = (body >> 30) & 1
    inv = jnp.where(r0 == 1, ~body, body) & _U32(0x7FFFFFFF)
    lz = 31 - bit_length32(inv)  # leading run length from bit 30
    m = jnp.minimum(lz, n - 1)
    k = jnp.where(r0 == 1, m - 1, -m)
    rem = (body << (m + 1).astype(_U32)) & _U32(0x7FFFFFFF)
    if es > 0:
        e = (rem >> (31 - es)).astype(_I32)
    else:
        e = jnp.zeros_like(k)
    fb = fmt.frac_bits
    if fb > 0:
        mant = (((rem << es) & _U32(0x7FFFFFFF)) >> (31 - fb)).astype(_I32)
    else:
        mant = jnp.zeros_like(k)
    frac = (_I32(1) << fb) | mant
    scale = k * (1 << es) + e
    valid = ~(is_zero | is_nar)
    return (
        is_zero,
        is_nar,
        jnp.where(valid, sign, 0),
        jnp.where(valid, scale, 0),
        jnp.where(valid, frac, 0),
    )


def decode(codes, fmt: PositFormat, dtype=jnp.float32):
    """codes (low n bits of any int container) -> float values (NaR -> nan,
    zero -> +0.0).  Bitwise equal to `posit_np.decode_np` on every code.

    The f32 word is assembled directly from the code, so this lowers inside
    Pallas TPU kernels, the body of every in-kernel decode.  It is exact:
    the n-1 bits after the sign are below 2**15 (n <= 16), so their int ->
    f32 conversion is exact and its exponent field is their bit length,
    which gives the regime's run; and |scale| <= max_scale <= 120 keeps
    every value a normal f32, out of reach of a denormal flush."""
    _check_jax_fmt(fmt)
    n, es = fmt.n, fmt.es
    body_mask = (1 << (n - 1)) - 1
    xs = (codes.astype(_I32) << (32 - n)) >> (32 - n)  # sign-extended code
    xa = jnp.abs(xs)  # NaR -> 2**(n-1), outside the body
    r0 = (xa << (33 - n)) >> 31  # first regime bit, as 0 or -1
    inv = xa ^ (r0 & body_mask)  # regime run -> leading zeros of the body
    top = jax.lax.bitcast_convert_type(inv.astype(jnp.float32), _I32) >> 23
    bl = jnp.maximum(top - 126, 0)  # bit length of inv, 0 for 0
    # k = -run = bl - (n-1), or run - 1 = (n-2) - bl = ~(bl - (n-1)) if r0
    k = (bl - (n - 1)) ^ r0
    # the bits after the terminator: exponent at bit 23, fraction below
    fld = (xa << (24 + es - bl)) & ((1 << (23 + es)) - 1)
    bits = ((k << (23 + es)) + ((127 << 23) + fld)) | (xs & _I32(-(1 << 31)))
    val = jax.lax.bitcast_convert_type(bits, jnp.float32)
    val = jnp.where(xs == 0, 0.0, val)
    val = jnp.where(xs == -(1 << (n - 1)), jnp.nan, val)
    return val.astype(dtype)


# ---------------------------------------------------------------------------
# encode
# ---------------------------------------------------------------------------

def encode_core(sign, scale, frac, F, sticky, fmt: PositFormat):
    """Round/pack unpacked values into posit codes (posit-2022 pattern RNE).

    sign/scale/frac: int32 arrays. frac must be 0 (-> code 0) or normalized
    in [2**F, 2**(F+1)).  F may be a python int or a per-element int32 array
    (the PDPU normalizer produces per-element widths).  ``sticky`` marks
    non-zero bits already discarded strictly below frac's LSB.
    """
    _check_jax_fmt(fmt)
    n, es = fmt.n, fmt.es
    sign = sign.astype(_I32)
    scale = scale.astype(_I32)
    frac = frac.astype(_I32)
    is_zero = frac == 0

    # normalize the fraction register to a fixed Fp = n - es fraction bits;
    # with the minimum regime length 2 this guarantees the final rounding
    # cut lands at shift >= 1 and the packed body fits in 31 bits.
    Fp = n - es
    F = jnp.asarray(F, dtype=_I32)
    drop = jnp.clip(F - Fp, 0, 31)
    up = jnp.clip(Fp - F, 0, 31)
    sticky = jnp.asarray(sticky, dtype=bool) | ((frac & ((_I32(1) << drop) - 1)) != 0)
    frac = (frac >> drop) << up

    k = scale >> es  # arithmetic shift = floor division
    e = scale & ((1 << es) - 1) if es > 0 else jnp.zeros_like(scale)

    sat_hi = k >= n - 2
    sat_lo = k <= -(n - 1)
    k_c = jnp.clip(k, -(n - 2), n - 3)
    e = jnp.where(sat_hi | sat_lo, 0, e)

    rlen = jnp.where(k_c >= 0, k_c + 2, 1 - k_c)
    reg = jnp.where(k_c >= 0, ((_I32(1) << (k_c + 1)) - 1) << 1, _I32(1))
    body_hi = (reg << es) | e
    body = (body_hi << Fp) | (frac & ((1 << Fp) - 1))
    shift = rlen + es + Fp - (n - 1)  # >= 1 by construction

    g = (body >> (shift - 1)) & 1
    st = sticky | ((body & ((_I32(1) << (shift - 1)) - 1)) != 0)
    base = body >> shift
    roundup = ((g == 1) & (st | ((base & 1) == 1))).astype(_I32)
    code_abs = base + roundup

    code_abs = jnp.where(sat_hi, fmt.maxpos_code, code_abs)
    code_abs = jnp.where(sat_lo, fmt.minpos_code, code_abs)
    code = jnp.where(sign == 1, (-code_abs) & fmt.mask, code_abs)
    return jnp.where(is_zero, 0, code).astype(_I32)


def encode(values, fmt: PositFormat):
    """float (f32/bf16/f16) -> posit codes (int32; low n bits valid).

    Exact pattern-RNE from the float value (nan/inf -> NaR).  Decomposes the
    f32 bit pattern directly (no frexp), so it lowers inside Pallas TPU
    kernels.  f32 subnormals sit far below minpos of every supported format
    and saturate to minpos via a forced out-of-range scale."""
    _check_jax_fmt(fmt)
    v = values.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(v, _I32)
    sign = (bits >> 31) & 1
    exp8 = (bits >> 23) & 0xFF
    mant = bits & 0x7FFFFF
    is_nar = exp8 == 255  # inf / nan
    is_zero = (exp8 == 0) & (mant == 0)
    subnormal = (exp8 == 0) & (mant != 0)
    scale = jnp.where(subnormal, -130, exp8 - 127)
    frac = jnp.where(is_zero, 0, (_I32(1) << 23) | mant)
    code = encode_core(sign, scale, frac, 23, jnp.zeros(v.shape, bool), fmt)
    return jnp.where(is_nar, fmt.nar_code, code).astype(_I32)


# ---------------------------------------------------------------------------
# storage + quantization helpers
# ---------------------------------------------------------------------------

def pack(values, fmt: PositFormat):
    """float -> posit codes in the narrowest container dtype (int8/int16)."""
    code = encode(values, fmt)
    dt = {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[fmt.storage_bits]
    return code.astype(dt)


def unpack(codes, fmt: PositFormat, dtype=jnp.float32):
    """posit codes (any int container) -> float values."""
    return decode(codes.astype(_I32) & fmt.mask, fmt, dtype=dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def quantize_ste(x, fmt: PositFormat):
    """Fake-quantize through the posit format with a straight-through grad.

    Forward: decode(encode(x)) — the exact value a posit pipeline would see.
    Backward: identity (STE), the standard recipe for quantization-aware
    training (paper §III-B mixed-precision motivation / PositNN [26]).
    """
    return unpack(encode(x, fmt), fmt, dtype=x.dtype)


def _quantize_fwd(x, fmt):
    return quantize_ste(x, fmt), None


def _quantize_bwd(fmt, _, g):
    return (g,)


quantize_ste.defvjp(_quantize_fwd, _quantize_bwd)


def quantize(x, fmt: PositFormat):
    """Non-differentiable fake-quantization (encode -> decode)."""
    return unpack(encode(x, fmt), fmt, dtype=x.dtype)
