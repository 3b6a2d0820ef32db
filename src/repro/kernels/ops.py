"""Public jit'd wrappers around the Pallas kernels.

Central switch: on non-TPU backends every kernel runs in interpret mode
(Pallas executes the kernel body with jnp on CPU), so the whole framework —
models, tests, benchmarks — exercises the identical kernel code paths that
compile to Mosaic on a real TPU.

Two layers of entry points:

  * raw kernels (`fused_matmul`, `matmul_posit_weights`, the grouped
    variants, `pdpu_matmul`): operate on posit *codes*; forward-only —
    Pallas calls have no autodiff rules and integer codes carry no tangents.
  * STE entry points (`fused_matmul_ste`, `fused_matmul_grouped_ste` and
    the `matmul_posit_weights*_ste` aliases): operate on *float masters*,
    run the identical raw kernel forward (encode -> in-kernel decode GEMM)
    and attach a `jax.custom_vjp` straight-through backward, so `jax.grad`
    flows through the real fused datapath.  This is what lets QAT train on
    the packed-kernel forward instead of the fake_quant stand-in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import PDPUConfig, PositFormat
from . import autotune
from . import posit_codec, posit_matmul, pdpu_dot
from . import paged_attention as paged_attention_mod
from . import prefill_attention as prefill_attention_mod
from . import ref  # noqa: F401  (re-exported for tests/benchmarks)


def interpret_mode() -> bool:
    """The one decision whether Pallas kernels run in interpret mode: off
    a TPU backend they do (the kernel body executes as jnp on the host)."""
    return jax.default_backend() != "tpu"


def _resolve(kernel: str, shape, fmts, kw: dict, names) -> dict:
    """Dispatch-time autotune resolution: fill launch params the caller did
    not pass explicitly from the tuned cache (kernels/autotune.py).  Shapes
    are static at trace time, so this is pure host-side lookup; a cache
    miss leaves the params absent and the kernel's module constants apply
    (the no-cache fallback)."""
    missing = [n for n in names if kw.get(n) is None]
    if not missing:
        return kw
    tuned = autotune.lookup(kernel, shape, fmts)
    if tuned:
        for n in missing:
            if tuned.get(n) is not None:
                kw[n] = tuned[n]
    return kw


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap (>= 1)."""
    for d in range(min(int(cap), int(n)), 0, -1):
        if n % d == 0:
            return d
    return 1


# Smallest tile a cached launch param may degrade to before the resolver
# gives up on it.  A cached tile that doesn't divide the live dim degrades
# to the largest divisor below it (any tiling is value-neutral), but for a
# prime live dim that collapses to 1 — a one-element-per-program launch
# grid that is strictly worse than the kernel's own untuned default.
_TILE_FLOOR = 2


def _degrade_tile(n: int, cap: int | None) -> int | None:
    """Resolve a cached tile `cap` against live dim `n`.

    Returns a divisor of n to launch with, or None to mean "drop the cached
    value and let the kernel's untuned default apply".  The cached tile is
    kept when it divides n; otherwise it degrades to the largest divisor of
    n below it, unless that divisor falls under `_TILE_FLOOR` (while n
    itself is larger) — the pathological prime-dim collapse."""
    if cap is None:
        return None
    d = _largest_divisor(n, cap)
    if d < min(_TILE_FLOOR, int(n)):
        return None
    return d


def _flat2d(shape):
    """The codec kernels collapse leading dims: lookup on the (R, C) the
    kernel actually launches."""
    if len(shape) < 2:
        return (1, int(shape[0]) if shape else 1)
    r = 1
    for d in shape[:-1]:
        r *= int(d)
    return (r, int(shape[-1]))


def decode(codes, fmt: PositFormat, **kw):
    """posit codes -> f32 (Pallas elementwise kernel)."""
    kw = _resolve("posit_codec.decode", _flat2d(codes.shape), (fmt,), kw,
                  ("block_r", "block_c"))
    return posit_codec.decode(codes, fmt, interpret=interpret_mode(), **kw)


def encode(values, fmt: PositFormat, **kw):
    """float -> posit codes in storage dtype (Pallas elementwise kernel)."""
    kw = _resolve("posit_codec.encode", _flat2d(values.shape), (fmt,), kw,
                  ("block_r", "block_c"))
    return posit_codec.encode(values, fmt, interpret=interpret_mode(), **kw)


def fused_matmul(a_codes, b_codes, fmt_a: PositFormat, fmt_b: PositFormat,
                 fmt_out: PositFormat | None = None, **kw):
    """Fused posit GEMM: in-kernel decode -> MXU f32 -> single encode."""
    kw = _resolve("posit_matmul",
                  (a_codes.shape[0], a_codes.shape[1], b_codes.shape[1]),
                  (fmt_a, fmt_b), kw, ("bm", "bn", "bk"))
    return posit_matmul.posit_matmul(
        a_codes, b_codes, fmt_a, fmt_b, fmt_out,
        interpret=interpret_mode(), **kw)


def fused_matmul_grouped(a_codes, b_codes, fmt_a: PositFormat,
                         fmt_b: PositFormat,
                         fmt_out: PositFormat | None = None, **kw):
    """Grouped fused posit GEMM: [E,M,K] x [E,K,N] codes -> [E,M,N].

    One expert per leading grid dimension; per-expert in-kernel decode,
    f32 MXU accumulate, single encode (fmt_out=None returns f32)."""
    kw = _resolve("posit_matmul_grouped",
                  (a_codes.shape[0], a_codes.shape[1], a_codes.shape[2],
                   b_codes.shape[2]),
                  (fmt_a, fmt_b), kw, ("bm", "bn", "bk"))
    return posit_matmul.posit_matmul_grouped(
        a_codes, b_codes, fmt_a, fmt_b, fmt_out,
        interpret=interpret_mode(), **kw)


def matmul_posit_weights_grouped(x, w_codes, fmt_w: PositFormat, **kw):
    """Float activations x stacked posit weights — grouped serving fast path.

    x: [E, M, K] float; w_codes: [E, K, N] posit codes.  Activations stay
    float (an encode would add a rounding); the expert weight stacks travel
    HBM->VMEM as int8/int16 codes and decode on the VPU inside the grouped
    kernel.  Returns f32.
    """
    kw = _resolve("posit_matmul_grouped",
                  (x.shape[0], x.shape[1], x.shape[2], w_codes.shape[2]),
                  (None, fmt_w), kw, ("bm", "bn", "bk"))
    return posit_matmul.posit_matmul_grouped(
        x.astype(jnp.float32), w_codes, None, fmt_w, None,
        interpret=interpret_mode(), **kw)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, window,
                    fmt_kv: PositFormat | None = None,
                    softcap_val: float = 0.0, page_ok=None,
                    partials: bool = False, t_block: int | None = None):
    """Paged-attention decode: gather KV pages by block table, decode posit
    codes in-kernel next to the q·k dot, streaming softmax across pages.
    See kernels/paged_attention.py; forward-only (decode hot path).

    q may be [B, Hq, Dh] (one token per slot) or [B, T, Hq, Dh] — the
    multi-query grid covering T new tokens per slot in one launch, with
    the query tile `t_block` resolved through the autotune cache when not
    given (any tiling is bitwise identical; T=1 takes the 3-D path).

    page_ok masks pages out of the streaming softmax (a kv_pages shard
    passes its ownership mask); partials=True returns the unnormalized
    (o, m, l) state for cross-shard merging via `merge_attn_partials`."""
    if q.ndim == 4 and t_block is None:
        kw = _resolve(
            "paged_attention",
            (q.shape[0], q.shape[1], block_tables.shape[1],
             k_pages.shape[1], k_pages.shape[2]),
            (fmt_kv,), {}, ("t_block",))
        # a cached t_block that doesn't divide this launch's T degrades to
        # the largest divisor of T below it (any tiling is value-neutral);
        # if that collapses below _TILE_FLOOR (prime T), the cached value is
        # dropped and the kernel's untuned default applies instead
        t_block = _degrade_tile(q.shape[1], kw.get("t_block"))
    return paged_attention_mod.paged_attention(
        q, k_pages, v_pages, block_tables, lengths, window,
        fmt_kv=fmt_kv, softcap_val=softcap_val, interpret=interpret_mode(),
        page_ok=page_ok, partials=partials, t_block=t_block)


def prefill_attention_paged(q, k, v, k_pages, v_pages, block_tables, starts,
                            window, fmt_kv: PositFormat | None = None,
                            compute_dtype=jnp.float32,
                            softcap_val: float = 0.0, flash_chunk: int = 1024,
                            hist_pool_k=None, hist_pool_v=None, hist_bt=None,
                            page_ok=None, **kw):
    """Fused prefill: chunk attention + posit KV encode + page insert in a
    single device program (kernels/prefill_attention.py) — bit-identical
    to the decomposed flash_attention -> kv_encode -> insert_chunk path
    for any span admitted by `paged.fused_prefill_span_ok` (history beyond
    one flash chunk streams through the kernel's running flash softmax).

    Sharded pools pass the all-gathered global pool (hist_pool_k/v), the
    global block tables as hist_bt, the localized block tables, and their
    ownership mask as page_ok."""
    kw = _resolve("prefill_attention",
                  (q.shape[0], q.shape[1], block_tables.shape[1],
                   k_pages.shape[1], k_pages.shape[2]),
                  (fmt_kv,), kw, ("dimension_semantics", "vmem_limit_mb"))
    return prefill_attention_mod.prefill_attention_paged(
        q, k, v, k_pages, v_pages, block_tables, starts, window,
        fmt_kv=fmt_kv, compute_dtype=compute_dtype, softcap_val=softcap_val,
        flash_chunk=flash_chunk, interpret=interpret_mode(),
        hist_pool_k=hist_pool_k, hist_pool_v=hist_pool_v, hist_bt=hist_bt,
        page_ok=page_ok, **kw)


def decode_sample(x, w, noise=None, temperature=None, *, plan: str = "fused",
                  fmt_w: PositFormat | None = None, transpose: bool = False,
                  greedy: bool = False, top_k: int = 0,
                  softcap_val: float = 0.0, v_block: int | None = None):
    """One-program decode epilogue: logits-head GEMM + sampling fused.

    Streams the head weights through the sampler in vocab tiles
    (kernels/paged_attention.py:decode_sample) so a decode step's logits
    never round-trip through HBM — bit-identical to running `logits_head`
    and the engine sampler as separate device programs.  `v_block` resolves
    through the autotune cache (0 = whole vocab / collapsed grid); a tile
    that doesn't divide this vocab runs with a masked partial last tile,
    and a miss takes the kernel's untuned, VMEM-budgeted default
    (`paged_attention.default_v_block`)."""
    V = w.shape[0] if transpose else w.shape[1]
    if v_block is None:
        kw = _resolve("decode_sample", (x.shape[0], x.shape[1], V),
                      (fmt_w,), {}, ("v_block",))
        vb = kw.get("v_block")
        if vb is not None:
            v_block = V if vb == 0 else vb
    return paged_attention_mod.decode_sample(
        x, w, noise, temperature, plan=plan, fmt_w=fmt_w,
        transpose=transpose, greedy=greedy, top_k=top_k,
        softcap_val=softcap_val, v_block=v_block, interpret=interpret_mode())


def merge_attn_partials(o, m, l, axis_name: str):
    """Log-sum-exp merge of per-shard paged-attention partials.

    Each kv_pages shard runs `paged_attention(..., partials=True)` over the
    pages it owns, producing unnormalized output `o` [B,Hq,Dh], running max
    `m` [B,Hq] and normalizer `l` [B,Hq].  Inside the serving shard_map this
    rescales every shard's state to the global max and psums — algebraically
    the kernel's own finalize, so when all of a slot's pages live on one
    shard the result is bitwise identical to the unsharded kernel (the other
    shards contribute w*l = 0).  Must run inside a shard_map binding
    `axis_name`."""
    m_max = jax.lax.pmax(m, axis_name)
    w = jnp.exp(m - m_max)
    l_tot = jax.lax.psum(l * w, axis_name)
    o_tot = jax.lax.psum(o * w[..., None], axis_name)
    return o_tot / jnp.maximum(l_tot, 1e-30)[..., None]


def pdpu_matmul(a_codes, b_codes, cfg: PDPUConfig, **kw):
    """Bit-exact chunked-PDPU GEMM (hardware-faithful W_m datapath)."""
    return pdpu_dot.pdpu_matmul(a_codes, b_codes, cfg,
                                interpret=interpret_mode(), **kw)


def matmul_posit_weights(x, w_codes, fmt_w: PositFormat, **kw):
    """float activations x posit-stored weights — the serving fast path.

    Activations stay float (encoding them would add a rounding); the posit
    weights decode exactly in-kernel and the dot takes both operands at f32
    precision (the MXU default would round them to bf16) and accumulates
    f32.  Returns f32.  (Used by the dispatch layer for posit-weight
    checkpoints when QuantPolicy.activations is None.)
    """
    a = x.astype(jnp.float32)
    w = posit_codec.decode(w_codes, fmt_w, interpret=interpret_mode(), **kw)
    return jnp.dot(a, w, precision=posit_matmul.F32,
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# differentiable (STE) entry points over float masters
# ---------------------------------------------------------------------------
#
# Forward runs the real fused datapath: the float masters are encoded to
# posit codes and the Pallas kernel decodes them in-kernel, accumulates f32
# on the MXU and returns f32 — exactly what serving executes.  Backward is
# straight-through w.r.t. the float operands, using the *quantized* operand
# values (the same values the kernel computed on), which is bit-for-bit the
# gradient the fake_quant STE plan produces.  Residuals are kept minimal:
# the posit codes of each quantized operand, saved once (int8/int16/int32 —
# narrower than an f32 copy), decoded exactly in the backward pass; a
# float-activation operand (fmt_a=None) is saved as-is.
#
# All STE entry points take and return float32 — the dispatch layer casts;
# custom_vjp then only ever has to produce f32 cotangents.


def _ste_primal(x, w, fmt_a, fmt_w):
    """Shared fwd: encode masters, run the raw fused kernel, return the
    f32 product plus the minimal residuals for the STE backward."""
    w_codes = encode(w, fmt_w)
    if fmt_a is None:  # float activations: the serving fast path
        return matmul_posit_weights(x, w_codes, fmt_w), (x, w_codes)
    a_codes = encode(x, fmt_a)
    out = fused_matmul(a_codes, w_codes, fmt_a, fmt_w, fmt_out=None)
    return out, (a_codes, w_codes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_matmul_ste(x, w, fmt_a: PositFormat | None, fmt_w: PositFormat):
    """Differentiable fused GEMM over float masters: x [M,K] @ w [K,N].

    fmt_a=None keeps activations float (the `matmul_posit_weights` fast
    path); otherwise both operands travel as codes through `fused_matmul`.
    Backward: dx = g @ wq^T, dw = xq^T @ g with xq/wq the decoded quantized
    operands — the straight-through gradients of the fake_quant plan.
    """
    return _ste_primal(x, w, fmt_a, fmt_w)[0]


def _fused_ste_fwd(x, w, fmt_a, fmt_w):
    return _ste_primal(x, w, fmt_a, fmt_w)


def _fused_ste_bwd(fmt_a, fmt_w, res, g):
    a_res, w_codes = res
    aq = a_res if fmt_a is None else decode(a_res, fmt_a)
    wq = decode(w_codes, fmt_w)
    g = g.astype(jnp.float32)
    dx = jnp.dot(g, wq.T, preferred_element_type=jnp.float32)
    dw = jnp.dot(aq.astype(jnp.float32).T, g,
                 preferred_element_type=jnp.float32)
    return dx, dw


fused_matmul_ste.defvjp(_fused_ste_fwd, _fused_ste_bwd)


def matmul_posit_weights_ste(x, w, fmt_w: PositFormat):
    """Differentiable serving fast path: float activations, posit weights
    encoded from float masters in the forward, STE weight gradients."""
    return fused_matmul_ste(x, w, None, fmt_w)


def _ste_grouped_primal(x, w, fmt_a, fmt_w):
    w_codes = encode(w, fmt_w)
    if fmt_a is None:
        return matmul_posit_weights_grouped(x, w_codes, fmt_w), (x, w_codes)
    a_codes = encode(x, fmt_a)
    out = fused_matmul_grouped(a_codes, w_codes, fmt_a, fmt_w, fmt_out=None)
    return out, (a_codes, w_codes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_matmul_grouped_ste(x, w, fmt_a: PositFormat | None,
                             fmt_w: PositFormat):
    """Differentiable grouped fused GEMM over float masters:
    x [E,C,K] @ w [E,K,N] -> [E,C,N], same STE semantics as
    `fused_matmul_ste` applied per expert (one batched backward einsum)."""
    return _ste_grouped_primal(x, w, fmt_a, fmt_w)[0]


def _grouped_ste_fwd(x, w, fmt_a, fmt_w):
    return _ste_grouped_primal(x, w, fmt_a, fmt_w)


def _grouped_ste_bwd(fmt_a, fmt_w, res, g):
    a_res, w_codes = res
    aq = a_res if fmt_a is None else decode(a_res, fmt_a)
    wq = decode(w_codes, fmt_w)
    g = g.astype(jnp.float32)
    dx = jnp.einsum("ecf,edf->ecd", g, wq,
                    preferred_element_type=jnp.float32)
    dw = jnp.einsum("ecd,ecf->edf", aq.astype(jnp.float32), g,
                    preferred_element_type=jnp.float32)
    return dx, dw


fused_matmul_grouped_ste.defvjp(_grouped_ste_fwd, _grouped_ste_bwd)


def matmul_posit_weights_grouped_ste(x, w, fmt_w: PositFormat):
    """Differentiable grouped serving fast path (float activations)."""
    return fused_matmul_grouped_ste(x, w, None, fmt_w)
