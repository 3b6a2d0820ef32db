"""Pallas kernel: fused prefill-attention that writes posit KV pages.

Chunked prefill used to be three device stages per chunk (models/
transformer.py `_chunk_attn`): flash attention over [gathered history |
raw chunk], a posit `kv_encode` of the chunk's K/V, and a
`paged.insert_chunk(_batched)` scatter into the page pool.  This kernel
collapses them into ONE device program per chunk — the PDPU argument
(fuse the datapath instead of composing discrete units) applied to the
serving prefill hot path:

  * per (slot, page) grid cell the slot's page arrives HBM->VMEM at posit
    code width via the scalar-prefetched block table (no dense gather in
    HBM), is decoded in-kernel, and is staged into a VMEM ring buffer one
    flash chunk wide,
  * the same cell posit-encodes the chunk rows that land in this page and
    merge-writes them back into the pool *in place*
    (`input_output_aliases` + a block-table-driven output index_map:
    pages outside the chunk span — or not owned by this shard — redirect
    to the trash page 0, so untouched pages pass through unchanged),
  * each time the staging buffer completes a full flash chunk of history,
    one running flash-softmax step folds it into VMEM state scratch
    (m/l/o); on the slot's last page step the remaining staged rows, the
    raw chunk, and the zero pad replay flash's tail chunks and the
    attention output is written.

Bit-exactness contract
----------------------

For ANY span the kernel replays `common.flash_attention`'s chunked
streaming scan op-for-op at the caller's `flash_chunk` — same chunk
boundaries over [decoded history | raw chunk | pad], same masking, same
running-max/correction arithmetic including the `o * corr + pv` step
(dropping it flips -0.0 signs), same finalize — so the fused path is
bit-identical to the three-program decomposed path.  The only geometry
requirement is that spans beyond one flash chunk need `page_size` to
divide `flash_chunk` (pages must tile the per-chunk staging buffer);
callers gate on `paged.fused_prefill_span_ok` and fall back to the
decomposed path otherwise.

Intra-chunk attention uses the *raw* (pre-encode) k/v and only history
reads see decoded codes, exactly like `_chunk_attn`; history decode
replays the `kv_decode` dtype chain (f32 -> compute dtype -> k dtype).

Sharded pools (`hist_pool_k/v` + `hist_bt` given): history cannot be
staged from the local sub-pool (other shards hold part of it), so the
caller passes the all-gathered global pool and the kernel stages history
pages from it via the globally-addressed `hist_bt` block table —
attention is then computed identically on every shard while `page_ok`
restricts the page writes to owned pages (non-owned chunk pages redirect
to the local trash page, the `insert_chunk(shard=...)` contract).
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


def _decode_hist(x, fmt_kv, compute_dtype, out_dtype):
    """Replay common.kv_decode + the `.astype(k.dtype)` chain bit-exactly."""
    if fmt_kv is None:
        return x.astype(out_dtype)
    val = posit.decode(x.astype(jnp.int32) & fmt_kv.mask, fmt_kv)
    return val.astype(compute_dtype).astype(out_dtype)


def _fused_prefill_kernel(bt_ref, st_ref, win_ref, ok_ref, hbt_ref, q_ref,
                          k_ref, v_ref, *refs, fmt_kv: PositFormat | None,
                          compute_dtype, page_size: int, chunk: int,
                          n_pages_per_slot: int, n_heads: int,
                          n_kv_heads: int, head_dim: int, softcap_val: float,
                          flash_chunk: int, global_hist: bool):
    # Layout (set up by the wrapper, so that no in-kernel reshape splits a
    # tiled dim): q is [Hkv, C*G, Dh] with rows ordered (query, group) —
    # the row order flash_attention's GQA einsum contracts in — and the
    # chunk k/v are [Hkv, C, Dh].  History is staged per kv head into
    # [Hkv, rows, Dh] buffers; softmax state is [Hkv, C*G, 1|Dh].
    if global_hist:
        hkp_ref, hvp_ref, *refs = refs
    else:
        hkp_ref = hvp_ref = None
    kp_ref, vp_ref, attn_ref, kp_out, vp_out, hk_scr, hv_scr, *state = refs
    b = pl.program_id(0)
    p = pl.program_id(1)
    ps, C, M = page_size, chunk, n_pages_per_slot
    G = n_heads // n_kv_heads
    Dh = head_dim
    CG = C * G
    # flash_attention's chunk geometry, replayed statically: the key span
    # [history S_h | chunk C | pad] is scanned in ck-row chunks; the first
    # n_hist_full chunks are pure history and stream through the staging
    # ring, the tail (history remainder + raw chunk + pad) runs on the
    # slot's last page step.
    S_h = M * ps
    ck = min(flash_chunk, S_h + C)
    n_hist_full = S_h // ck
    h_rem = S_h - n_hist_full * ck
    n_tail = -(-(h_rem + C) // ck)
    R = min(S_h, ck) // ps  # staging ring size, in pages
    start = st_ref[b]

    if state:
        m_scr, l_scr, o_scr = state

        @pl.when(p == 0)
        def _init_state():
            m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            o_scr[...] = jnp.zeros(o_scr.shape, jnp.float32)

    # Snapshot the page before any aliased output write: history staging
    # and the read side of the merge must see pre-insert pool content
    # (exactly what paged.gather_slot would have gathered).
    old_k = kp_ref[0]
    old_v = vp_ref[0]
    src_k = hkp_ref[0] if global_hist else old_k
    src_v = hvp_ref[0] if global_hist else old_v
    stage = pl.multiple_of((p % R) * ps, ps)
    dk = _decode_hist(src_k, fmt_kv, compute_dtype, hk_scr.dtype)
    dv = _decode_hist(src_v, fmt_kv, compute_dtype, hv_scr.dtype)
    for h in range(n_kv_heads):
        hk_scr[h, pl.ds(stage, ps)] = dk[:, h * Dh:(h + 1) * Dh]
        hv_scr[h, pl.ds(stage, ps)] = dv[:, h * Dh:(h + 1) * Dh]

    # ---- in-kernel encode + page write ------------------------------------
    # rows r of page p hold absolute positions p*ps + r; the chunk occupies
    # [start, start + C).  Select each covered row's raw chunk k/v with a
    # 0/1 matmul (exact: one surviving term per row), encode, merge with the
    # old page content, write.  The output index_map redirects pages outside
    # the chunk span (or not owned by this shard) to the trash page.  Row
    # indices are built as a [ps, 1] column directly: Mosaic cannot reshape
    # a 1-D bool vector into a column.
    j = p * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0) - start
    wm = (j >= 0) & (j < C)
    sel = (j == jax.lax.broadcasted_iota(jnp.int32, (ps, C), 1))
    sel_f = sel.astype(jnp.float32)
    kc = jnp.concatenate([k_ref[0, h] for h in range(n_kv_heads)], axis=-1)
    vc = jnp.concatenate([v_ref[0, h] for h in range(n_kv_heads)], axis=-1)
    # (HIGHEST: the MXU default would round f32 rows to bf16)
    k_rows = jnp.dot(sel_f, kc.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST).astype(kc.dtype)
    v_rows = jnp.dot(sel_f, vc.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST).astype(vc.dtype)
    if fmt_kv is None:
        k_codes = k_rows.astype(compute_dtype)
        v_codes = v_rows.astype(compute_dtype)
    else:
        k_codes = posit.encode(k_rows, fmt_kv)
        v_codes = posit.encode(v_rows, fmt_kv)
    kp_out[0] = jnp.where(wm, k_codes.astype(old_k.dtype), old_k)
    vp_out[0] = jnp.where(wm, v_codes.astype(old_v.dtype), old_v)

    # ---- running flash softmax --------------------------------------------
    scale = 1.0 / math.sqrt(Dh)
    # query row r is token r // G of the chunk
    q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (CG, 1), 0) // G

    def _qg():
        return q_ref[0].astype(jnp.float32) * scale

    def _flash_step(m, l, o, kb, vb, kv_pos, qg):
        # one chunk of flash_attention's streaming scan, replayed verbatim
        # (B=1 blocks); kv_pos is a [1, ck] row
        s = jnp.einsum("hqd,hkd->hqk", qg, kb.astype(jnp.float32))
        s = _softcap(s, softcap_val)
        mask = kv_pos >= 0
        mask &= q_pos >= kv_pos
        mask &= (q_pos - kv_pos) < win_ref[0]
        s = jnp.where(mask[None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(pr, axis=-1, keepdims=True)
        pv = jnp.einsum("hqk,hkd->hqd", pr, vb.astype(jnp.float32))
        # keep the o*corr term: 0.0*corr + (-0.0) is +0.0, matching flash;
        # writing `pv` alone would flip those signs
        o_new = o * corr + pv
        return m_new, l_new, o_new

    if n_hist_full:
        # the staging ring just completed a full flash chunk of history:
        # fold it into the running state
        @pl.when(((p + 1) % R == 0) & (p + 1 <= n_hist_full * R))
        def _hist_step():
            base = (p + 1) * ps - ck
            pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, ck), 1)
            pos = jnp.where(pos < start, pos, -1)
            m_new, l_new, o_new = _flash_step(
                m_scr[...], l_scr[...], o_scr[...], hk_scr[:, :ck],
                hv_scr[:, :ck], pos, _qg())
            m_scr[...] = m_new
            l_scr[...] = l_new
            o_scr[...] = o_new

    # ---- tail chunks + finalize on the slot's last page step --------------
    @pl.when(p == M - 1)
    def _attend():
        qg = _qg()
        if state:
            m = m_scr[...]
            l = l_scr[...]
            o = o_scr[...]
        else:
            m = jnp.full((n_kv_heads, CG, 1), _NEG, jnp.float32)
            l = jnp.zeros((n_kv_heads, CG, 1), jnp.float32)
            o = jnp.zeros((n_kv_heads, CG, Dh), jnp.float32)
        # the staging ring already holds the history remainder in rows
        # [0, h_rem); append the raw chunk and zero the pad behind it
        n_rows = n_tail * ck
        hk_scr[:, h_rem:h_rem + C] = k_ref[0].astype(hk_scr.dtype)
        hv_scr[:, h_rem:h_rem + C] = v_ref[0].astype(hv_scr.dtype)
        if n_rows > h_rem + C:
            pad = (n_kv_heads, n_rows - h_rem - C, Dh)
            hk_scr[:, h_rem + C:n_rows] = jnp.zeros(pad, hk_scr.dtype)
            hv_scr[:, h_rem + C:n_rows] = jnp.zeros(pad, hv_scr.dtype)
        r = jax.lax.broadcasted_iota(jnp.int32, (1, n_rows), 1)
        hp = n_hist_full * ck + r
        pos_tail = jnp.where(
            r < h_rem, jnp.where(hp < start, hp, -1),
            jnp.where(r < h_rem + C, start + r - h_rem, -1))
        for jt in range(n_tail):
            sl = slice(jt * ck, (jt + 1) * ck)
            m, l, o = _flash_step(m, l, o, hk_scr[:, sl], hv_scr[:, sl],
                                  pos_tail[:, sl], qg)
        o = o / jnp.maximum(l, 1e-30)
        attn_ref[0] = o.astype(attn_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fmt_kv", "compute_dtype", "softcap_val", "flash_chunk",
                     "interpret", "dimension_semantics", "vmem_limit_mb"),
)
def prefill_attention_paged(q, k, v, k_pages, v_pages, block_tables, starts,
                            window, fmt_kv: PositFormat | None = None,
                            compute_dtype=jnp.float32, softcap_val: float = 0.0,
                            flash_chunk: int = 1024, interpret: bool = False,
                            hist_pool_k=None, hist_pool_v=None, hist_bt=None,
                            page_ok=None, dimension_semantics: str | None = None,
                            vmem_limit_mb: int | None = None):
    """Fused prefill: chunk attention + posit KV encode + paged insert.

    q            : [B, C, Hq, Dh] post-rope queries (chunk positions
                   starts[b] + [0, C)).
    k, v         : [B, C, Hkv, Dh] raw post-rope chunk keys/values — the
                   kernel encodes them to the pool's code width itself.
    k/v_pages    : [n_pages, page_size, Hkv*Dh] pool (the local sub-pool
                   under a kv_pages shard).
    block_tables : [B, M] page ids (pre-localized under a shard); rows of
                   inactive slots zeroed -> writes land on the trash page.
    starts       : [B] int32 chunk start position per slot.
    window       : [1] int32 sliding window (>= max_seq = unbounded).
    flash_chunk  : flash_attention key-chunk length the kernel replays
                   (spans beyond it require page_size | flash_chunk).
    hist_pool_k/v: optional [n_pages_global, page_size, Hkv*Dh] all-
                   gathered global pool (kv_pages-sharded pools) history
                   pages are staged from; `hist_bt` then carries the
                   *global* page ids.  When omitted, history is staged
                   from the local pool via `block_tables`.
    page_ok      : optional [B, M] write-ownership mask (sharded pools).
    dimension_semantics / vmem_limit_mb : TPU launch knobs (autotuned);
                   value-neutral by construction.

    Returns (attn [B, C, Hq, Dh] in q.dtype, k_pages', v_pages') with the
    pools updated in place (donated/aliased) exactly as
    `paged.insert_chunk_batched` would have written them.
    """
    B, C, Hq, Dh = q.shape
    n_pages, ps, kvd = k_pages.shape
    Hkv = kvd // Dh
    if Hkv * Dh != kvd or Hq % Hkv:
        raise ValueError(f"page feature dim {kvd} incompatible with "
                         f"q heads {Hq} x head_dim {Dh}")
    if k.shape != (B, C, Hkv, Dh) or v.shape != (B, C, Hkv, Dh):
        raise ValueError(f"chunk k/v shape {k.shape} != {(B, C, Hkv, Dh)}")
    M = block_tables.shape[1]
    global_hist = hist_pool_k is not None
    if global_hist:
        if hist_bt is None:
            raise ValueError("hist_pool_k/v require the global hist_bt")
        if hist_pool_k.shape[1:] != (ps, kvd):
            raise ValueError(f"hist pool page shape {hist_pool_k.shape[1:]} "
                             f"!= {(ps, kvd)}")
    if page_ok is None:
        page_ok = jnp.ones((B, M), jnp.int32)
    S_h = M * ps
    ck = min(int(flash_chunk), S_h + C)
    n_hist_full = S_h // ck
    if n_hist_full and ck % ps:
        raise ValueError(f"span {S_h}+{C} needs page_size {ps} to divide "
                         f"flash_chunk {ck} (see fused_prefill_span_ok)")

    def _qmap(b, p, bt, st, wn, ok, hbt):
        return (b, 0, 0, 0)

    def _pmap(b, p, bt, st, wn, ok, hbt):
        return (bt[b, p], 0, 0)

    def _hmap(b, p, bt, st, wn, ok, hbt):
        return (hbt[b, p], 0, 0)

    def _wmap(b, p, bt, st, wn, ok, hbt):
        pstart = p * ps
        w = (pstart < st[b] + C) & (pstart + ps > st[b]) & (ok[b, p] > 0)
        return (jnp.where(w, bt[b, p], 0), 0, 0)

    # kernel-side layout: heads leading, so every in-kernel op works on
    # whole [rows, Dh] tiles (pure relayouts here and on the way out)
    G = Hq // Hkv
    qt = q.reshape(B, C, Hkv, G, Dh).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Hkv, C * G, Dh)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    q_spec = pl.BlockSpec((1, Hkv, C * G, Dh), _qmap)
    chunk_spec = pl.BlockSpec((1, Hkv, C, Dh), _qmap)
    page_spec = pl.BlockSpec((1, ps, kvd), _pmap)
    in_specs = [q_spec, chunk_spec, chunk_spec]
    inputs = [qt, kt, vt]
    if global_hist:
        hist_spec = pl.BlockSpec((1, ps, kvd), _hmap)
        in_specs += [hist_spec, hist_spec]
        inputs += [hist_pool_k, hist_pool_v]
    in_specs += [page_spec, page_spec]
    inputs += [k_pages, v_pages]
    # flattened input index of k_pages/v_pages, counting the 5 scalar-
    # prefetch operands first — aliased onto pool outputs 1 and 2
    kp_idx = 5 + len(in_specs) - 2
    aliases = {kp_idx: 1, kp_idx + 1: 2}

    # staging ring (history) that also holds the tail [rem | chunk | pad]
    n_tail = -(-(S_h - n_hist_full * ck + C) // ck)
    buf_rows = max(min(S_h, ck), n_tail * ck)
    scratch = [pltpu.VMEM((Hkv, buf_rows, Dh), k.dtype),
               pltpu.VMEM((Hkv, buf_rows, Dh), v.dtype)]
    if n_hist_full:
        scratch += [pltpu.VMEM((Hkv, C * G, 1), jnp.float32),
                    pltpu.VMEM((Hkv, C * G, 1), jnp.float32),
                    pltpu.VMEM((Hkv, C * G, Dh), jnp.float32)]

    out_specs = [
        q_spec,
        pl.BlockSpec((1, ps, kvd), _wmap),
        pl.BlockSpec((1, ps, kvd), _wmap),
    ]
    out_shapes = [
        jax.ShapeDtypeStruct(qt.shape, q.dtype),
        jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
        jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, M),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _fused_prefill_kernel, fmt_kv=fmt_kv, compute_dtype=compute_dtype,
        page_size=ps, chunk=C, n_pages_per_slot=M, n_heads=Hq,
        n_kv_heads=Hkv, head_dim=Dh, softcap_val=softcap_val,
        flash_chunk=int(flash_chunk), global_hist=global_hist)
    cp_kwargs = {"dimension_semantics":
                 (dimension_semantics or "parallel", "arbitrary")}
    if vmem_limit_mb is not None:
        cp_kwargs["vmem_limit_bytes"] = int(vmem_limit_mb) << 20
    hbt = hist_bt if global_hist else block_tables
    attn, k_new, v_new = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        input_output_aliases=aliases,
        interpret=interpret,
        name="prefill_attention_paged",
        compiler_params=pltpu.CompilerParams(**cp_kwargs),
    )(block_tables.astype(jnp.int32), starts.astype(jnp.int32),
      window.astype(jnp.int32), page_ok.astype(jnp.int32),
      hbt.astype(jnp.int32), *inputs)
    attn = attn.reshape(B, Hkv, C, G, Dh).transpose(0, 2, 1, 3, 4) \
               .reshape(B, C, Hq, Dh)
    return attn, k_new, v_new
