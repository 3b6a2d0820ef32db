"""The benchmark's own posit arithmetic, written apart from the program's.

Three things, all for word sizes n <= 16:

* `decode`: posit codes -> float32, exactly, in plain `jax.numpy` integer
  arithmetic.  The references decode the weight codes with it.
* `codes_from_values`: float32 -> posit codes by truncating the fraction.
  It only decides which codes the seed draws: the weights *are* the codes,
  and both the program and the reference read them.
* `round_to_format`: float -> posit value, saturating at minpos/maxpos as
  posits do.  This is the KV storage rounding the references apply: for
  n <= 8 the standard's round-to-nearest-even of the bit string.

`decode_table` enumerates every code on the host in plain Python, for the
rounding table and for the tests.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def decode_scalar(code: int, n: int, es: int) -> float:
    """One code -> its value (nan for NaR), by the posit definition."""
    code &= (1 << n) - 1
    if code == 0:
        return 0.0
    if code == 1 << (n - 1):
        return math.nan
    neg = code >> (n - 1)
    if neg:
        code = (-code) & ((1 << n) - 1)
    bits = [(code >> i) & 1 for i in range(n - 2, -1, -1)]  # after sign
    first = bits[0]
    run = 1
    while run < len(bits) and bits[run] == first:
        run += 1
    k = run - 1 if first else -run
    rest = bits[run + 1:]
    exp_bits = (rest[:es] + [0] * es)[:es]
    e = int("".join(map(str, exp_bits)), 2) if es else 0
    frac_bits = rest[es:]
    f = sum(b * 2.0 ** -(i + 1) for i, b in enumerate(frac_bits))
    value = (1.0 + f) * 2.0 ** (k * (1 << es) + e)
    return -value if neg else value


def decode_table(n: int, es: int) -> np.ndarray:
    """Value of every code 0 .. 2**n - 1 (float64; NaR as nan)."""
    return np.array([decode_scalar(c, n, es) for c in range(1 << n)])


def decode(codes, n: int, es: int):
    """Posit codes (any integer dtype, any shape) -> float32 values."""
    mask = (1 << n) - 1
    c = codes.astype(jnp.int32) & mask
    neg = (c >> (n - 1)) & 1
    mag = jnp.where(neg == 1, (-c) & mask, c)
    # the n-1 bits after the sign, left-aligned in a 32-bit word
    body = jax.lax.shift_left(mag, jnp.int32(33 - n)).astype(jnp.uint32)
    r0 = body >> 31
    run = jnp.where(r0 == 1, jax.lax.clz(~body), jax.lax.clz(body))
    run = jnp.minimum(run.astype(jnp.int32), n - 1)
    k = jnp.where(r0 == 1, run - 1, -run)
    rest = jax.lax.shift_left(body, (run + 1).astype(jnp.uint32))
    e = (rest >> (32 - es)).astype(jnp.int32) if es else 0
    frac_word = jax.lax.shift_left(rest, jnp.uint32(es)) if es else rest
    # at most n - 3 - es <= 13 fraction bits: exact in float32
    f = (frac_word >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)
    val = jnp.ldexp(1.0 + f, k * (1 << es) + e)
    val = jnp.where(neg == 1, -val, val)
    val = jnp.where(c == 0, 0.0, val)
    return jnp.where(c == 1 << (n - 1), jnp.nan, val).astype(jnp.float32)


def codes_from_values(x, n: int, es: int, dtype=jnp.int16):
    """float32 -> posit codes, the fraction truncated (not rounded).

    Magnitudes are clipped into the range where all `es` exponent bits are
    present; that range holds any weight a seed draws."""
    mask = (1 << n) - 1
    useed = 1 << es
    kmax = n - 3 - es - 1          # regime length k + 2 <= n - 1 - es
    kmin = -(n - 2 - es)           # regime length -k + 1 <= n - 1 - es
    a = jnp.abs(x.astype(jnp.float32))
    a = jnp.clip(a, 2.0 ** (kmin * useed), 2.0 ** ((kmax + 1) * useed - 1))
    m, ex = jnp.frexp(a)           # a = m * 2**ex, m in [0.5, 1)
    s = ex - 1
    f1 = 2.0 * m - 1.0
    k = jnp.floor_divide(s, useed)
    e = s - k * useed
    rl = jnp.where(k >= 0, k + 2, 1 - k)
    fb = n - 1 - rl - es
    regime = jnp.where(k >= 0,
                       jax.lax.shift_left(
                           jax.lax.shift_left(1, k + 1) - 1, 1), 1)
    frac = jnp.floor(f1 * jnp.ldexp(1.0, fb)).astype(jnp.int32)
    code = (jax.lax.shift_left(regime, n - 1 - rl)
            | jax.lax.shift_left(e, fb) | frac)
    code = jnp.where(x < 0, (-code) & mask, code)
    code = jnp.where(x == 0, 0, code)
    # two's-complement view in the storage container
    code = jnp.where(code >= 1 << (n - 1), code - (1 << n), code) \
        if jnp.dtype(dtype).itemsize * 8 == n else code
    return code.astype(dtype)


def round_to_format(x, n: int, es: int):
    """Round float values to P(n, es), saturating at +-minpos / +-maxpos;
    exact zero stays zero.  Returns float32.  For n <= 8 this is the
    standard's round-to-nearest-even of the bit string, in integer
    arithmetic; wider formats take the nearest value of the format's
    table (equal to it wherever every exponent bit is present)."""
    if n > 8:
        return _round_nearest_value(x, n, es)
    x = x.astype(jnp.float32)
    a = jnp.abs(x)
    m, e = jnp.frexp(jnp.where(a > 0, a, 1.0))
    s = e - 1                                   # a = 2**s * (1 + f)
    frac = ((2.0 * m - 1.0) * 2.0 ** 23).astype(jnp.uint32)   # exact
    k = jnp.floor_divide(s, 1 << es)
    ex = (s - k * (1 << es)).astype(jnp.uint32)
    big = k >= n - 2                            # at or past maxpos
    small = k < -(n - 2)                        # below minpos
    kc = jnp.clip(k, -(n - 2), n - 3)
    rl = jnp.where(kc >= 0, kc + 2, 1 - kc).astype(jnp.uint32)
    regime = jnp.where(kc >= 0,
                       jax.lax.shift_left(
                           jax.lax.shift_left(jnp.uint32(1),
                                              (kc + 1).astype(jnp.uint32))
                           - 1, jnp.uint32(1)),
                       jnp.uint32(1))
    length = rl + es + 23                       # <= 32 bits for n <= 8
    body = (jax.lax.shift_left(regime, jnp.uint32(es + 23))
            | jax.lax.shift_left(ex, jnp.uint32(23)) | frac)
    shift = length - (n - 1)
    kept = jax.lax.shift_right_logical(body, shift)
    rem = body & (jax.lax.shift_left(jnp.uint32(1), shift) - 1)
    half = jax.lax.shift_left(jnp.uint32(1), shift - 1)
    up = (rem > half) | ((rem == half) & ((kept & 1) == 1))
    code = kept.astype(jnp.int32) + up.astype(jnp.int32)
    code = jnp.clip(code, 1, (1 << (n - 1)) - 1)
    code = jnp.where(big, (1 << (n - 1)) - 1, jnp.where(small, 1, code))
    q = decode(code, n, es)
    q = jnp.where(a == 0, 0.0, q)
    return jnp.where(x < 0, -q, q)


def _round_nearest_value(x, n: int, es: int):
    pos = decode_table(n, es)[1:1 << (n - 1)]          # minpos .. maxpos
    mids = jnp.asarray((pos[:-1] + pos[1:]) / 2.0, jnp.float32)
    grid = jnp.asarray(pos, jnp.float32)
    a = jnp.abs(x.astype(jnp.float32))
    q = grid[jnp.searchsorted(mids, a, side="right")]
    q = jnp.where(a == 0, 0.0, q)
    return jnp.where(x < 0, -q, q)
