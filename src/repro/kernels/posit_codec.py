"""Pallas TPU kernels: elementwise posit decode / encode.

These are the S1/S6 stages of the paper as TPU VPU bit-ops over VMEM tiles.
On a real accelerator they run fused into consumers; standalone they serve
(a) the decode-at-load path for posit-stored weights/KV-cache and (b) the
encode-at-store path for posit outputs/checkpoint shards.

Tiling: 2-D grid over (rows/block_r, cols/block_c).  Codes are stored in
int16 (or int8 for n <= 8) — half/quarter the HBM traffic of f32, which is
the memory-roofline win the paper's mixed-precision strategy buys on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import posit
from repro.core.formats import PositFormat

# (sublane, lane)-aligned defaults; int16 native tiling on TPU is (16, 128).
_BLOCK_R = 256
_BLOCK_C = 512


def _decode_kernel(code_ref, out_ref, *, fmt: PositFormat):
    codes = code_ref[...].astype(jnp.int32) & fmt.mask
    out_ref[...] = posit.decode(codes, fmt)


def _encode_kernel(x_ref, out_ref, *, fmt: PositFormat, out_dtype):
    x = x_ref[...]
    out_ref[...] = posit.encode(x, fmt).astype(out_dtype)


def _grid_2d(shape, block_r, block_c):
    r = pl.cdiv(shape[0], block_r)
    c = pl.cdiv(shape[1], block_c)
    return (r, c)


def _as_2d(x):
    """Collapse leading dims; pad is handled by pallas masking semantics
    (block tails are garbage-in/garbage-out and sliced away by pallas)."""
    if x.ndim == 1:
        return x.reshape(1, -1), x.shape
    if x.ndim == 2:
        return x, x.shape
    return x.reshape(-1, x.shape[-1]), x.shape


@functools.partial(jax.jit, static_argnames=("fmt", "block_r", "block_c", "interpret"))
def decode(codes, fmt: PositFormat, block_r=None, block_c=None,
           interpret=False):
    """posit codes (int8/int16/int32, any shape) -> float32 values.

    block_r/block_c default to the module constants; ops.decode resolves
    them through the autotune cache per (shape bucket, fmt, backend)."""
    block_r = _BLOCK_R if block_r is None else block_r
    block_c = _BLOCK_C if block_c is None else block_c
    x2, orig_shape = _as_2d(codes)
    R, C = x2.shape
    br, bc = min(block_r, R), min(block_c, C)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, fmt=fmt),
        grid=_grid_2d(x2.shape, br, bc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=interpret,
        name="decode",
    )(x2)
    return out.reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("fmt", "block_r", "block_c", "interpret"))
def encode(values, fmt: PositFormat, block_r=None, block_c=None,
           interpret=False):
    """float values (any shape) -> posit codes in the storage dtype.

    block_r/block_c default to the module constants; ops.encode resolves
    them through the autotune cache per (shape bucket, fmt, backend)."""
    block_r = _BLOCK_R if block_r is None else block_r
    block_c = _BLOCK_C if block_c is None else block_c
    out_dtype = {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[fmt.storage_bits]
    x2, orig_shape = _as_2d(values.astype(jnp.float32))
    R, C = x2.shape
    br, bc = min(block_r, R), min(block_c, C)
    out = pl.pallas_call(
        functools.partial(_encode_kernel, fmt=fmt, out_dtype=out_dtype),
        grid=_grid_2d(x2.shape, br, bc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        interpret=interpret,
        name="encode",
    )(x2)
    return out.reshape(orig_shape)
