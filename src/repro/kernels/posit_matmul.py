"""Pallas TPU kernel: fused posit matmul — the PDPU's TPU-native form.

The paper's fused architecture does per dot product: decode all inputs once,
accumulate in one wide aligned register, encode the result once.  The
TPU-native realization tiles a GEMM over (M/bm, N/bn, K/bk):

  * decode: posit tiles (int16/int8 in HBM -> VMEM) are decoded to exact
    f32 *inside* the kernel (VPU bit ops) — never materialized in HBM.
    2 decodes per input element, total; no discrete-unit re-decoding.
  * accumulate: the MXU matmul (at f32 operand precision) accumulates in
    an f32 VMEM scratch across the K grid dimension — the W_m-wide aligned
    accumulator analogue.
  * encode: on the last K step the f32 tile is rounded *once* into the
    output posit format — the single-rounding fused property.

Compared with the discrete alternative (decode kernel -> HBM f32 tensor ->
matmul -> encode kernel), this removes 4 bytes/elem of HBM round-trip per
input and 2 roundings per output, which is exactly the paper's
"remove redundant decode/encode + intermediate rounding" claim mapped onto
the TPU memory hierarchy.  The Pallas grid software-pipelines the HBM->VMEM
DMAs of block k+1 against MXU compute of block k — the 6-stage pipeline's
role (§IV-B) played by double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import posit
from repro.core.formats import PositFormat

# MXU-aligned tile defaults (128x128 systolic array; K tiled for VMEM).
_BM, _BN, _BK = 256, 256, 512

# The MXU's default precision rounds f32 operands to bf16 (8 significant
# bits); a P(16,2) value carries up to 12.  HIGHEST keeps the decoded
# operands whole, so the one rounding is the final encode.
F32 = jax.lax.Precision.HIGHEST


def _fused_matmul_kernel(a_ref, b_ref, out_ref, acc_ref, *,
                         fmt_a: PositFormat, fmt_b: PositFormat,
                         fmt_out, n_k: int, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # S1 (decode) on the VPU — exact f32 values of the posit codes
    a = posit.decode(a_ref[...].astype(jnp.int32) & fmt_a.mask, fmt_a)
    b = posit.decode(b_ref[...].astype(jnp.int32) & fmt_b.mask, fmt_b)
    # S2-S4 (multiply + wide accumulate) on the MXU, at f32 operand
    # precision: the default would round the decoded operands to bf16
    acc_ref[...] += jnp.dot(a, b, precision=F32,
                            preferred_element_type=jnp.float32)

    # S5-S6 (normalize + single rounding/encode) on the final K step
    @pl.when(pl.program_id(2) == n_k - 1)
    def _finalize():
        acc = acc_ref[...]
        if fmt_out is None:
            out_ref[...] = acc.astype(out_dtype)
        else:
            out_ref[...] = posit.encode(acc, fmt_out).astype(out_dtype)


def _grouped_matmul_kernel(a_ref, b_ref, out_ref, acc_ref, *,
                           fmt_a, fmt_b: PositFormat,
                           fmt_out, n_k: int, out_dtype):
    """One (expert, m, n, k) grid cell of the grouped GEMM.

    Identical datapath to `_fused_matmul_kernel`; the leading block dim of
    every ref is the expert (always block size 1).  fmt_a=None means the
    activations arrive as plain f32 (the serving fast path — encoding float
    activations would add a rounding) and skip the in-kernel decode.
    """
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if fmt_a is None:
        a = a_ref[0].astype(jnp.float32)
    else:
        a = posit.decode(a_ref[0].astype(jnp.int32) & fmt_a.mask, fmt_a)
    b = posit.decode(b_ref[0].astype(jnp.int32) & fmt_b.mask, fmt_b)
    acc_ref[...] += jnp.dot(a, b, precision=F32,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _finalize():
        acc = acc_ref[...]
        if fmt_out is None:
            out_ref[0] = acc.astype(out_dtype)
        else:
            out_ref[0] = posit.encode(acc, fmt_out).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fmt_a", "fmt_b", "fmt_out", "bm", "bn", "bk", "interpret"),
)
def posit_matmul_grouped(a, b_codes, fmt_a: PositFormat | None,
                         fmt_b: PositFormat, fmt_out: PositFormat | None = None,
                         bm=None, bn=None, bk=None, interpret=False):
    """Grouped fused GEMM: [E,M,K] x [E,K,N] -> [E,M,N], one expert per
    leading grid dimension.

    The MoE expert-stack shape: E stacked weight matrices, each multiplied
    by its own activation slab.  Each expert reuses the 2-D kernel's tiling
    (bm, bn, bk) with a per-expert f32 VMEM scratch accumulator and a single
    encode on the last K step — the PDPU fused property held per expert.

    fmt_a=None takes `a` as float activations (no decode — the serving fast
    path, where weights are stored as posit codes and decode in-kernel but
    activations stay float); otherwise `a` holds fmt_a posit codes.
    M/N/K pad to tile multiples internally (posit code 0 and f32 0.0 are
    both exact zeros, so padding never perturbs the accumulation).
    """
    bm = _BM if bm is None else bm
    bn = _BN if bn is None else bn
    bk = _BK if bk is None else bk
    E, M, K = a.shape
    Eb, K2, N = b_codes.shape
    if E != Eb or K != K2:
        raise ValueError(f"grouped mismatch {a.shape} x {b_codes.shape}")
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)

    def pad(x, m0, m1):
        p0 = (-x.shape[1]) % m0
        p1 = (-x.shape[2]) % m1
        if p0 or p1:
            x = jnp.pad(x, ((0, 0), (0, p0), (0, p1)))
        return x

    a_p = pad(a, bm_, bk_)
    b_p = pad(b_codes, bk_, bn_)
    _, Mp, Kp = a_p.shape
    _, _, Np = b_p.shape
    n_k = Kp // bk_

    if fmt_out is None:
        out_dtype = jnp.float32
    else:
        out_dtype = {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[fmt_out.storage_bits]

    out = pl.pallas_call(
        functools.partial(
            _grouped_matmul_kernel, fmt_a=fmt_a, fmt_b=fmt_b,
            fmt_out=fmt_out, n_k=n_k, out_dtype=out_dtype,
        ),
        grid=(E, Mp // bm_, Np // bn_, n_k),
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk_, bn_), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm_, bn_), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_p, b_p)
    return out[:, :M, :N]


@functools.partial(
    jax.jit,
    static_argnames=("fmt_a", "fmt_b", "fmt_out", "bm", "bn", "bk", "interpret"),
)
def posit_matmul(a_codes, b_codes, fmt_a: PositFormat, fmt_b: PositFormat,
                 fmt_out: PositFormat | None = None,
                 bm=None, bn=None, bk=None, interpret=False):
    """[M,K] posit codes x [K,N] posit codes -> [M,N].

    fmt_out=None returns f32 (the mixed-precision "higher-precision output"
    path feeding a wider consumer); otherwise returns fmt_out posit codes in
    their storage dtype.  M/N/K are padded to tile multiples internally —
    posit code 0 decodes to 0.0, so zero padding is exact.
    """
    bm = _BM if bm is None else bm
    bn = _BN if bn is None else bn
    bk = _BK if bk is None else bk
    M, K = a_codes.shape
    K2, N = b_codes.shape
    if K != K2:
        raise ValueError(f"contraction mismatch {a_codes.shape} x {b_codes.shape}")
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)

    def pad(x, m0, m1):
        p0 = (-x.shape[0]) % m0
        p1 = (-x.shape[1]) % m1
        if p0 or p1:
            x = jnp.pad(x, ((0, p0), (0, p1)))
        return x

    a_p = pad(a_codes, bm_, bk_)
    b_p = pad(b_codes, bk_, bn_)
    Mp, Kp = a_p.shape
    _, Np = b_p.shape
    n_k = Kp // bk_

    if fmt_out is None:
        out_dtype = jnp.float32
    else:
        out_dtype = {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[fmt_out.storage_bits]

    out = pl.pallas_call(
        functools.partial(
            _fused_matmul_kernel, fmt_a=fmt_a, fmt_b=fmt_b,
            fmt_out=fmt_out, n_k=n_k, out_dtype=out_dtype,
        ),
        grid=(Mp // bm_, Np // bn_, n_k),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_p, b_p)
    return out[:M, :N]
