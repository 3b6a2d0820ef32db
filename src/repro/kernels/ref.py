"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's tests sweep shapes/dtypes and assert bit-identity (codec,
pdpu_dot) or allclose (fused matmul) against these references.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core import pdpu as pdpu_core
from repro.core.formats import PDPUConfig, PositFormat


def decode_ref(codes, fmt: PositFormat, dtype=jnp.float32):
    """posit codes -> float values."""
    return posit.decode(codes.astype(jnp.int32) & fmt.mask, fmt, dtype=dtype)


def encode_ref(values, fmt: PositFormat):
    """float values -> posit codes in the storage container dtype."""
    return posit.pack(values, fmt)


def posit_matmul_ref(a_codes, b_codes, fmt_a: PositFormat, fmt_b: PositFormat,
                     fmt_out: PositFormat | None = None, bk: int | None = None):
    """Fused posit matmul semantics: decode once (exact), accumulate wide
    (f32), encode once.  out = encode(decode(A) @ decode(B)) — exactly one
    rounding per output element, the paper's fused property.

    ``bk`` replays the kernel's K-block accumulation order so comparisons
    are bit-identical (f32 addition is order-sensitive)."""
    a = decode_ref(a_codes, fmt_a)
    b = decode_ref(b_codes, fmt_b)
    hi = jax.lax.Precision.HIGHEST  # f32 operands, as in the kernel
    if bk is None or bk >= a.shape[-1]:
        out = jnp.dot(a, b, precision=hi, preferred_element_type=jnp.float32)
    else:
        K = a.shape[-1]
        out = jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)
        for k0 in range(0, K, bk):
            out = out + jnp.dot(a[:, k0:k0 + bk], b[k0:k0 + bk, :],
                                precision=hi,
                                preferred_element_type=jnp.float32)
    if fmt_out is None:
        return out
    return posit.pack(out, fmt_out)


def pdpu_matmul_ref(a_codes, b_codes, cfg: PDPUConfig):
    """Bit-exact chunked-PDPU GEMM (hardware-faithful W_m datapath)."""
    return pdpu_core.pdpu_matmul_exact(
        a_codes.astype(jnp.int32) & cfg.fmt_in.mask,
        b_codes.astype(jnp.int32) & cfg.fmt_in.mask,
        cfg,
    )


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, window,
                        fmt_kv: PositFormat | None = None,
                        softcap_val: float = 0.0):
    """Paged-attention decode semantics, densely: gather each slot's pages
    by block table, decode the posit codes, plain masked softmax.

    Same contract as `paged_attention.paged_attention` — q [B, Hq, Dh],
    pages [n_pages, ps, Hkv*Dh], block_tables [B, M], lengths [B] valid
    counts including the current token, window [1].  Returns [B, Hq, Dh]
    f32."""
    B, Hq, Dh = q.shape
    _, ps, kvd = k_pages.shape
    Hkv = kvd // Dh
    G = Hq // Hkv
    M = block_tables.shape[1]
    S = M * ps
    kg = k_pages[block_tables].reshape(B, S, Hkv, Dh)
    vg = v_pages[block_tables].reshape(B, S, Hkv, Dh)
    if fmt_kv is not None:
        kg = decode_ref(kg, fmt_kv)
        vg = decode_ref(vg, fmt_kv)
    scale = 1.0 / (Dh ** 0.5)
    qg = q.reshape(B, Hkv, G, Dh).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, kg.astype(jnp.float32))
    if softcap_val > 0:
        s = softcap_val * jnp.tanh(s / softcap_val)
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]          # [1, S]
    q_pos = (lengths - 1)[:, None]
    mask = (pos < lengths[:, None]) & ((q_pos - pos) < window[0])
    s = jnp.where(mask[:, None, None, :], s, -2.0e38)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, vg.astype(jnp.float32))
    return o.reshape(B, Hq, Dh)


def paged_attention_mq_ref(q, k_pages, v_pages, block_tables, lengths,
                           window, fmt_kv: PositFormat | None = None,
                           softcap_val: float = 0.0):
    """Multi-query paged-attention semantics, densely: q [B, T, Hq, Dh],
    token i of slot b at absolute position lengths[b] - T + i (lengths
    count all T new tokens as written), masked softmax per token over the
    slot's gathered pages.  Returns [B, T, Hq, Dh] f32."""
    B, T, Hq, Dh = q.shape
    _, ps, kvd = k_pages.shape
    Hkv = kvd // Dh
    G = Hq // Hkv
    M = block_tables.shape[1]
    S = M * ps
    kg = k_pages[block_tables].reshape(B, S, Hkv, Dh)
    vg = v_pages[block_tables].reshape(B, S, Hkv, Dh)
    if fmt_kv is not None:
        kg = decode_ref(kg, fmt_kv)
        vg = decode_ref(vg, fmt_kv)
    scale = 1.0 / (Dh ** 0.5)
    qg = q.reshape(B, T, Hkv, G, Dh).astype(jnp.float32) * scale
    s = jnp.einsum("bthgd,bkhd->bthgk", qg, kg.astype(jnp.float32))
    if softcap_val > 0:
        s = softcap_val * jnp.tanh(s / softcap_val)
    pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]        # [1, 1, S]
    q_pos = (lengths[:, None] - T + jnp.arange(T)[None, :])[..., None]
    mask = (pos <= q_pos) & ((q_pos - pos) < window[0])        # [B, T, S]
    s = jnp.where(mask[:, :, None, None, :], s, -2.0e38)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bthgk,bkhd->bthgd", p, vg.astype(jnp.float32))
    return o.reshape(B, T, Hq, Dh)
