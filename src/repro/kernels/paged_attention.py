"""Pallas TPU kernel: paged-attention decode over posit-coded KV pages.

The serving KV cache is a pool of fixed-size pages `[n_pages, page_size,
Hkv*Dh]` stored at posit code width (int8/int16); each batch slot owns an
ordered list of page indices (its *block table*), so page j of a slot holds
the keys/values for absolute positions [j*page_size, (j+1)*page_size).

This kernel is the PDPU fused-decode idea applied to attention instead of
GEMM: per (slot, page) grid cell it

  * gathers the page by block table — `PrefetchScalarGridSpec` scalar-
    prefetches the block tables so the BlockSpec index_map DMAs exactly the
    pages the slot owns, HBM->VMEM at code width (the paged cache is never
    materialized densely, and never decoded in HBM),
  * decodes the posit codes to exact f32 on the VPU *inside* the kernel,
    right next to the q·k dot — one decode per element, total,
  * accumulates a streaming softmax (running max / normalizer / weighted
    value sum in f32 VMEM scratch) across the slot's pages — the wide-
    accumulator property held across the page dimension,
  * normalizes and writes the output once on the last page.

Masking: page p covers positions p*ps + [0, ps); entries at positions
>= lengths[b] are dead (beyond the slot's written prefix — freshly
allocated or reclaimed-page garbage) and are masked before the running max,
so page reclamation never leaks stale keys into a new request's attention.
A sliding window is applied as (q_pos - pos) < window with
q_pos = lengths[b] - 1 (the token written immediately before this call).

Shapes here follow the serving decode step (one query token per slot);
tiles are sized by the model's head layout rather than MXU tiles — on CPU
every call runs in interpret mode (like the other kernels in this package),
on TPU the (ps, Hkv*Dh) page is the natural VMEM block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import posit
from repro.core.formats import PositFormat

_NEG = -2.0e38

def _softcap(x, cap: float):
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


def _paged_attention_kernel(bt_ref, len_ref, win_ref, ok_ref, q_ref, k_ref,
                            v_ref, *refs, fmt_kv: PositFormat | None,
                            page_size: int, t_total: int, t_block: int,
                            n_heads: int, n_kv_heads: int, head_dim: int,
                            softcap_val: float, partials: bool):
    """One (slot, query tile, page) grid cell: T new tokens per slot.

    Query row i of slot b sits at absolute position lengths[b] - T + i
    (all T tokens already inserted); causality between the new tokens is
    enforced by the same position mask that guards written-prefix reads.
    Rows are independent and each runs the same one-token streaming step,
    so any t_block tiling of T is bitwise identical — t_block is the
    autotuned launch parameter.  The one-token decode is the T=1 case.
    """
    if partials:
        out_ref, m_ref, l_ref, m_scr, l_scr, o_scr = refs
    else:
        (out_ref, m_scr, l_scr, o_scr), m_ref, l_ref = refs, None, None
    b = pl.program_id(0)
    t = pl.program_id(1)
    p = pl.program_id(2)
    G = n_heads // n_kv_heads

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        o_scr[...] = jnp.zeros_like(o_scr)

    # in-kernel decode: the page travels HBM->VMEM as posit codes and turns
    # into exact f32 only here, next to the dot (fmt_kv=None = float pages)
    if fmt_kv is None:
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
    else:
        k = posit.decode(k_ref[0].astype(jnp.int32) & fmt_kv.mask, fmt_kv)
        v = posit.decode(v_ref[0].astype(jnp.int32) & fmt_kv.mask, fmt_kv)
    k = k.reshape(page_size, n_kv_heads, head_dim)
    v = v.reshape(page_size, n_kv_heads, head_dim)

    scale = 1.0 / math.sqrt(head_dim)
    pos = p * page_size + jax.lax.iota(jnp.int32, page_size)
    page_ok = ok_ref[b, p] > 0
    # one query row at a time: Mosaic cannot split the fused [T, heads]
    # axis of a multi-row einsum into (kv head, group)
    for i in range(t_block):
        q_pos = len_ref[b] - t_total + t * t_block + i
        mask = (pos <= q_pos) & ((q_pos - pos) < win_ref[0]) & page_ok
        qg = q_ref[0, i].reshape(n_kv_heads, G, head_dim) \
                        .astype(jnp.float32) * scale
        s = jnp.einsum("hgd,khd->hgk", qg, k)  # [Hkv, G, ps]
        s = _softcap(s, softcap_val)
        s = jnp.where(mask[None, None, :], s, _NEG)

        m_prev, l_prev, o_prev = m_scr[i], l_scr[i], o_scr[i]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        pr = jnp.exp(s - m_new[..., None])
        pr = jnp.where(mask[None, None, :], pr, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_scr[i] = m_new
        l_scr[i] = l_prev * corr + jnp.sum(pr, axis=-1)
        o_scr[i] = o_prev * corr[..., None] \
            + jnp.einsum("hgk,khd->hgd", pr, v)

    @pl.when(p == pl.num_programs(2) - 1)
    def _finalize():
        for i in range(t_block):
            if partials:
                # leave the streaming state unnormalized: (o, m, l) per
                # slot, to be log-sum-exp merged across kv_pages shards
                # (ops.merge_attn_partials) before the single final
                # normalization
                out_ref[0, i] = o_scr[i].reshape(n_heads, head_dim)
            else:
                o = o_scr[i] / jnp.maximum(l_scr[i], 1e-30)[..., None]
                out_ref[0, i] = o.reshape(n_heads, head_dim)
        if partials:
            m_ref[0] = m_scr[...]
            l_ref[0] = l_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("fmt_kv", "softcap_val", "interpret", "partials",
                     "t_block"),
)
def paged_attention(q, k_pages, v_pages, block_tables, lengths, window,
                    fmt_kv: PositFormat | None = None,
                    softcap_val: float = 0.0, interpret: bool = False,
                    page_ok=None, partials: bool = False,
                    t_block: int | None = None):
    """Single- or multi-token attention over block-table-paged posit KV.

    q            : [B, Hq, Dh] float query (one decode token per slot), or
                   [B, T, Hq, Dh] for the multi-query grid — one launch
                   covers T new tokens per slot (token i of slot b at
                   absolute position lengths[b] - T + i, causally masked
                   against both history and the other new tokens; the 3-D
                   form is exactly T=1).  `t_block` tiles T (autotuned;
                   rows are independent so any tiling is bitwise
                   identical); the 3-D path ignores it.
    k/v_pages    : [n_pages, page_size, Hkv*Dh] posit codes (int8/int16,
                   decoded in-kernel via fmt_kv) or float (fmt_kv=None).
    block_tables : [B, max_pages] int32 — page j holds the slot's positions
                   [j*page_size, (j+1)*page_size); unallocated entries may
                   point anywhere (they are masked by `lengths`).
    lengths      : [B] int32 valid positions per slot *including* the
                   current token(s) (written by the caller before this
                   call).
    window       : [1] int32 sliding-window size (>= max_seq = unbounded).
    page_ok      : optional [B, max_pages] mask (nonzero = contribute).
                   On a kv_pages-sharded pool each shard passes its
                   ownership mask with block tables pre-localized, so the
                   kernel only attends over the pages it physically holds.
    partials     : return the unnormalized streaming-softmax state
                   `(o [B,Hq,Dh], m [B,Hq], l [B,Hq])` instead of the
                   normalized output — the per-shard contribution merged
                   across shards by `ops.merge_attn_partials` (exactly the
                   kernel's own finalize once merged, so a slot whose pages
                   live on one shard is bitwise identical to partials=False).

    Returns [B, Hq, Dh] f32 (or [B, T, Hq, Dh] for 4-D q), or the
    corresponding (o, m, l) triple when partials=True.
    """
    if v_pages.shape != k_pages.shape:
        raise ValueError(f"k/v page pools differ: {k_pages.shape} vs "
                         f"{v_pages.shape}")
    one_token = q.ndim == 3
    if one_token:
        q = q[:, None]
        t_block = 1
    B, T, Hq, Dh = q.shape
    n_pages, page_size, kvd = k_pages.shape
    Hkv = kvd // Dh
    if Hkv * Dh != kvd or Hq % Hkv:
        raise ValueError(f"page feature dim {kvd} incompatible with "
                         f"q heads {Hq} x head_dim {Dh}")
    G = Hq // Hkv
    M = block_tables.shape[1]
    if page_ok is None:
        page_ok = jnp.ones((B, M), jnp.int32)
    if t_block is None:
        t_block = next(tb for tb in (8, 4, 2, 1) if T % tb == 0)
    if T % t_block:
        raise ValueError(f"t_block={t_block} must divide T={T}")

    def _qmap(b, t, p, bt, ln, wn, ok):
        return (b, t, 0, 0)

    def _pmap(b, t, p, bt, ln, wn, ok):
        return (bt[b, p], 0, 0)

    # every block's last two dims span the whole array dims (Mosaic's
    # (8, 128)-or-full rule): m/l come out as [B, T, Hkv, G] and are
    # flattened to [B, T, Hq] outside the kernel
    out_spec = pl.BlockSpec((1, t_block, Hq, Dh), _qmap)
    out_shape = jax.ShapeDtypeStruct((B, T, Hq, Dh), jnp.float32)
    if partials:
        ml_spec = pl.BlockSpec((1, t_block, Hkv, G), _qmap)
        ml_shape = jax.ShapeDtypeStruct((B, T, Hkv, G), jnp.float32)
        out_specs = [out_spec, ml_spec, ml_spec]
        out_shapes = [out_shape, ml_shape, ml_shape]
    else:
        out_specs, out_shapes = out_spec, out_shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, T // t_block, M),
        in_specs=[
            out_spec,
            pl.BlockSpec((1, page_size, kvd), _pmap),
            pl.BlockSpec((1, page_size, kvd), _pmap),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((t_block, Hkv, G), jnp.float32),
            pltpu.VMEM((t_block, Hkv, G), jnp.float32),
            pltpu.VMEM((t_block, Hkv, G, Dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_attention_kernel, fmt_kv=fmt_kv, page_size=page_size,
        t_total=T, t_block=t_block, n_heads=Hq, n_kv_heads=Hkv, head_dim=Dh,
        softcap_val=softcap_val, partials=partials)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      window.astype(jnp.int32), page_ok.astype(jnp.int32),
      q.astype(jnp.float32), k_pages, v_pages)
    if partials:
        o, m, l = out
        out = (o, m.reshape(B, T, Hq), l.reshape(B, T, Hq))
        return tuple(x[:, 0] for x in out) if one_token else out
    return out[:, 0] if one_token else out


# ---------------------------------------------------------------------------
# fused decode epilogue: logits-head posit GEMM + sampling in one program
# ---------------------------------------------------------------------------


# Untuned vocab tile budget: the decoded f32 weight tile, its code tile
# (double-buffered) and the in-kernel temporaries must fit the 16 MiB
# scoped VMEM of a TPU v5e — a 2048 x 2560 tile of gemma3-4b's head needs
# 20 MiB there and is refused.
_V_TILE_BYTES = 4 << 20


def default_v_block(V: int, D: int) -> int:
    """V itself when the whole [D, V] f32 tile fits `_V_TILE_BYTES`;
    otherwise the widest power-of-two tile of at least 128 lanes that fits
    (128 when none does).  A tile that does not divide V leaves a partial
    last tile, which the kernel masks."""
    if V * D * 4 <= _V_TILE_BYTES:
        return V
    vb = 128
    while 2 * vb < V and 2 * vb * D * 4 <= _V_TILE_BYTES:
        vb *= 2
    return vb


def _decode_sample_kernel(x_ref, w_ref, *refs, plan: str,
                          fmt_w: PositFormat | None, transpose: bool,
                          greedy: bool, top_k: int, softcap_val: float,
                          v_block: int, vocab: int, n_vt: int, n_phase: int):
    if greedy:
        t_ref, tok_ref, *scr = refs
        noise_ref = None
    else:
        noise_ref, t_ref, tok_ref, *scr = refs
    if n_phase == 2:
        best_scr, idx_scr, kbuf_scr = scr
    else:
        best_scr, idx_scr = scr
        kbuf_scr = None
    ph = pl.program_id(0)
    t = pl.program_id(1)

    def _logits():
        # replay logits_head's qdot plan on this vocab tile, op-for-op
        w = w_ref[...]
        if transpose:
            w = w.T  # pure relayout: commutes with the elementwise decode
        if fmt_w is not None:
            wq = posit.decode(w.astype(jnp.int32) & fmt_w.mask, fmt_w)
        else:
            wq = w
        x = x_ref[...]
        if plan == "fused":
            # ops.matmul_posit_weights: f32 activations x exact f32 decode,
            # both kept at f32 precision on the MXU
            l = jnp.dot(x.astype(jnp.float32), wq,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
        else:
            # fake_quant: unpack to x.dtype, dot in x.dtype, f32 output
            l = jnp.dot(x, wq.astype(x.dtype),
                        preferred_element_type=jnp.float32)
        return _softcap(l.astype(jnp.float32), softcap_val)

    def _scaled():
        return _logits() / jnp.maximum(t_ref[0], 1e-6)

    def _in_vocab(y):
        # the last tile may run past V: its columns there hold whatever the
        # block padding read, and must never be sampled
        if vocab % v_block == 0:
            return y
        col = t * v_block + jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
        return jnp.where(col < vocab, y, -jnp.inf)

    if n_phase == 2:
        # phase 0: stream the per-row top-k values into kbuf so the argmax
        # phase can read the exact k-th largest (== sort(l)[..., -top_k])
        @pl.when(ph == 0)
        def _topk():
            l = _in_vocab(_scaled())

            @pl.when(t == 0)
            def _init_kbuf():
                kbuf_scr[...] = jnp.full_like(kbuf_scr, -jnp.inf)

            cand = jnp.concatenate([kbuf_scr[...], l], axis=1)
            cols = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)
            tops = []
            for _ in range(top_k):
                mx = jnp.max(cand, axis=1)
                first = jnp.argmax(cand, axis=1).astype(jnp.int32)
                tops.append(mx)
                # retire one instance so repeated values keep multiset
                # semantics, exactly like a sort
                cand = jnp.where(cols == first[:, None], -jnp.inf, cand)
            kbuf_scr[...] = jnp.stack(tops, axis=1)

    @pl.when(ph == n_phase - 1)
    def _argmax():
        if greedy:
            y = _logits()  # greedy samples the raw (softcapped) logits
        else:
            l = _scaled()
            if kbuf_scr is not None:
                kth = kbuf_scr[...][:, top_k - 1]
                l = jnp.where(l >= kth[:, None], l, -1e30)
            # categorical(key, l) == argmax(gumbel_noise + l)
            y = noise_ref[...] + l
        y = _in_vocab(y)

        @pl.when(t == 0)
        def _init_best():
            best_scr[...] = jnp.full_like(best_scr, -jnp.inf)
            idx_scr[...] = jnp.zeros_like(idx_scr)

        vmax = jnp.max(y, axis=1)
        vidx = jnp.argmax(y, axis=1).astype(jnp.int32)
        # strict > keeps the first-occurrence tie-breaking of a full argmax
        upd = vmax > best_scr[0]
        best_scr[0] = jnp.where(upd, vmax, best_scr[0])
        idx_scr[0] = jnp.where(upd, t * v_block + vidx, idx_scr[0])

        @pl.when(t == n_vt - 1)
        def _emit():
            tok_ref[0] = idx_scr[0]


@functools.partial(
    jax.jit,
    static_argnames=("plan", "fmt_w", "transpose", "greedy", "top_k",
                     "softcap_val", "v_block", "interpret"),
)
def decode_sample(x, w, noise=None, temperature=None, *, plan: str = "fused",
                  fmt_w: PositFormat | None = None, transpose: bool = False,
                  greedy: bool = False, top_k: int = 0,
                  softcap_val: float = 0.0, v_block: int | None = None,
                  interpret: bool = False):
    """One-program decode epilogue: posit logits GEMM + sampling.

    Replays `common.logits_head` (the execution plan's head qdot plus the
    logit softcap) and the serving sampler (temperature / top-k /
    `jax.random.categorical`, or greedy argmax) in a single Pallas program,
    streaming the vocab dimension in `v_block` tiles so the [B, V] logits
    row never round-trips through HBM between head GEMM and sampler.

    x           : [B, D] final-norm'd hidden rows (one decode token/slot).
    w           : head weights — [D, V] (or [V, D] with transpose=True, the
                  tied-embedding layout); posit codes (integer container,
                  decoded in-kernel via fmt_w) or float (fmt_w=None).
    noise       : [B, V] f32 standard-gumbel noise, one row per slot — what
                  `jax.random.categorical` draws internally, so
                  argmax(noise + logits/T) replays it bitwise.  Ignored
                  (and may be None) when greedy.
    temperature : scalar f32 (ignored when greedy).
    plan        : "fused" (f32 activations x exact in-kernel decode,
                  matching ops.matmul_posit_weights) or "fake_quant"
                  (unpack to x.dtype, dot in x.dtype) — the two
                  dispatch.qdot decode-head plans, bit-for-bit.
    top_k       : 0 (or >= V) disables the top-k filter; otherwise a
                  streaming k-buffer phase reproduces `sort(l)[..., -k]`
                  exactly before the filtered gumbel argmax.
    v_block     : vocab tile width; None = `default_v_block`, sized so
                  the decoded f32 weight tile fits `_V_TILE_BYTES`.  A
                  tile that does not divide V leaves a partial last tile
                  whose columns past V are masked out of the sampler.
                  Tiling the vocab axis only (rows stay whole) keeps the
                  f32 dot bitwise identical to the untiled `logits_head`
                  matmul.

    Returns [B] int32 sampled tokens, bit-identical to running
    `logits_head` and the engine sampler as separate device programs.
    """
    B, D = x.shape
    V = w.shape[0] if transpose else w.shape[1]
    vb = default_v_block(V, D) if v_block is None else min(int(v_block), V)
    n_vt = pl.cdiv(V, vb)
    topk_active = (not greedy) and 0 < top_k < V
    n_phase = 2 if topk_active else 1
    if temperature is None:
        temperature = jnp.float32(1.0)
    t_arr = jnp.reshape(temperature, (1,)).astype(jnp.float32)

    w_block = (vb, D) if transpose else (D, vb)
    w_map = (lambda ph, t: (t, 0)) if transpose else (lambda ph, t: (0, t))
    in_specs = [pl.BlockSpec((B, D), lambda ph, t: (0, 0)),
                pl.BlockSpec(w_block, w_map)]
    inputs = [x, w]
    if not greedy:
        if noise is None:
            raise ValueError("non-greedy decode_sample requires noise")
        in_specs.append(pl.BlockSpec((B, vb), lambda ph, t: (0, t)))
        inputs.append(noise.astype(jnp.float32))
    in_specs.append(pl.BlockSpec((1,), lambda ph, t: (0,)))
    inputs.append(t_arr)

    scratch = [pltpu.VMEM((1, B), jnp.float32),
               pltpu.VMEM((1, B), jnp.int32)]
    if topk_active:
        scratch.append(pltpu.VMEM((B, int(top_k)), jnp.float32))

    kernel = functools.partial(
        _decode_sample_kernel, plan=plan, fmt_w=fmt_w, transpose=transpose,
        greedy=greedy, top_k=int(top_k), softcap_val=softcap_val,
        v_block=vb, vocab=V, n_vt=n_vt, n_phase=n_phase)
    tok = pl.pallas_call(
        kernel,
        grid=(n_phase, n_vt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, B), lambda ph, t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
        name="decode_sample",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(*inputs)
    return tok[0]
