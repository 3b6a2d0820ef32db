"""Autotune sweep CLI: regenerate the committed kernel tile cache.

    PYTHONPATH=src python -m repro.launch.autotune [--commit] [--out PATH]
                                                   [--reps N] [--quick]
                                                   [--oracle-check]

Sweeps every tunable kernel (kernels/autotune.TUNABLES) over the
serving-representative shape set below, prunes each candidate grid with
the roofline cost oracle, wall-clock times the survivors, and prints the
per-shape winners.  `--commit` rewrites the committed cache JSON
(`kernels/autotune_cache.json`, the CI-host cache that ops.py resolves
launch params from); `--out` writes anywhere else.  `--oracle-check`
additionally lowers each winner through XLA and prints the
launch/hlo_analysis FLOP/byte accounting next to the analytic oracle, as
a sanity check that the pruning model tracks the compiler's view.

The shape set is intentionally small: shapes are *bucketed* into the
cache key (kernels/autotune.shape_bucket), so each swept point covers
its whole power-of-two band.  The committed file is regenerated on the
CI host platform — entries from other backends are keyed separately and
never collide.
"""
from __future__ import annotations

import argparse
import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core.formats import P8_2, P13_2, P16_1, P16_2
from repro.kernels import autotune, ops
from repro.kernels import paged_attention as paged_attention_mod
from repro.kernels import prefill_attention as prefill_attention_mod
from repro.kernels import posit_codec, posit_matmul


# serving-representative sweep points: (shape, fmts) per kernel.  Shapes
# bucket to powers of two, so e.g. (512, 512) covers every codec call up
# to that band.
def sweep_points(quick: bool):
    codec = [((512, 512), (P16_2,)), ((2048, 512), (P16_1,)),
             ((1024, 1024), (P16_2,)), ((1024, 1024), (P8_2,))]
    mm = [((256, 256, 256), (P16_2, P16_2)),
          ((512, 512, 512), (P16_1, P16_1))]
    # the serving demo's smoke-config buckets (decode-step rows, chunk
    # prefill, activation-coded GEMMs), so the example's tuned-config
    # hit report shows live coverage rather than all-misses
    codec += [((r, c), (P13_2,)) for r in (8, 16, 64) for c in (64, 256)]
    codec += [((64, 512), (P16_2,))]
    mm += [((r, k, n), (P13_2, P16_2)) for r in (8, 16, 64)
           for k, n in ((64, 32), (64, 64), (64, 256), (256, 64))]
    grouped = [((4, 128, 128, 128), (None, P16_2))]
    paged = [((4, 8, 8, 16, 128), (P16_1,)),
             ((8, 8, 16, 16, 128), (P8_2,)),
             ((4, 8, 8, 4, 16), (P16_1,))]
    # fused prefill (B, C, M, ps, F): serving-default paged geometry plus
    # the tiny smoke-config band
    prefill = [((2, 64, 8, 16, 128), (P16_1,)),
               ((4, 64, 4, 16, 128), (P8_2,)),
               ((2, 16, 4, 16, 8), (P8_2,)),
               ((2, 16, 4, 16, 8), (P16_1,))]
    # fused decode epilogue (B, D, V): packed-head serving bands plus the
    # tiny smoke-config vocab and a float-master (fake_quant) point
    decode = [((4, 256, 4096), (P16_2,)),
              ((2, 16, 64), (P16_2,)),
              ((2, 64, 256), (None,))]
    if quick:
        codec, mm, grouped, paged = codec[:1], mm[:1], grouped[:1], paged[:1]
        prefill, decode = prefill[:1], decode[:1]
    return {"posit_codec.decode": codec, "posit_codec.encode": codec,
            "posit_matmul": mm, "posit_matmul_grouped": grouped,
            "paged_attention": paged,
            "prefill_attention": prefill,
            "decode_sample": decode}


def _runner(kernel: str, shape, fmts, rng):
    """Build `run(params) -> thunk` for one sweep point (see
    autotune.sweep); inputs are generated once and closed over."""
    interp = ops.interpret_mode()
    if kernel in ("posit_codec.decode", "posit_codec.encode"):
        (fmt,) = fmts
        vals = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
        codes = posit.pack(vals, fmt)
        if kernel.endswith("decode"):
            return lambda p: functools.partial(
                posit_codec.decode, codes, fmt, interpret=interp, **p)
        return lambda p: functools.partial(
            posit_codec.encode, vals, fmt, interpret=interp, **p)
    if kernel in ("posit_matmul", "posit_matmul_grouped"):
        if kernel == "posit_matmul":
            M, K, N = shape
            a_shape, b_shape = (M, K), (K, N)
        else:
            E, M, K, N = shape
            a_shape, b_shape = (E, M, K), (E, K, N)
        fmt_a, fmt_b = fmts
        a = jnp.asarray(rng.normal(0, 1, a_shape), jnp.float32)
        if fmt_a is not None:
            a = posit.pack(a, fmt_a)
        b = posit.pack(jnp.asarray(rng.normal(0, 1, b_shape), jnp.float32),
                       fmt_b)
        fn = (posit_matmul.posit_matmul if kernel == "posit_matmul"
              else posit_matmul.posit_matmul_grouped)
        return lambda p: functools.partial(
            fn, a, b, fmt_a, fmt_b, None, interpret=interp, **p)
    if kernel == "paged_attention":
        B, T, M, ps, F = shape
        (fmt,) = fmts
        Dh = 64 if F % 128 == 0 else F // 2
        Hkv = F // Dh
        n_pages = 1 + B * M
        q = jnp.asarray(rng.normal(0, 1, (B, T, 4 * Hkv, Dh)), jnp.float32)
        kp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                    jnp.float32), fmt)
        vp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                    jnp.float32), fmt)
        bt = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
        lengths = jnp.full((B,), M * ps, jnp.int32)
        win = jnp.full((1,), 2 ** 30, jnp.int32)
        return lambda p: functools.partial(
            paged_attention_mod.paged_attention, q, kp, vp, bt, lengths,
            win, fmt_kv=fmt, interpret=interp, **p)
    if kernel == "prefill_attention":
        B, C, M, ps, F = shape
        (fmt,) = fmts
        Dh = 64 if F % 128 == 0 else F // 2
        Hkv = F // Dh
        n_pages = 1 + B * M
        q = jnp.asarray(rng.normal(0, 1, (B, C, 4 * Hkv, Dh)), jnp.float32)
        kc = jnp.asarray(rng.normal(0, 1, (B, C, Hkv, Dh)), jnp.float32)
        vc = jnp.asarray(rng.normal(0, 1, (B, C, Hkv, Dh)), jnp.float32)
        kp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                    jnp.float32), fmt)
        vp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                    jnp.float32), fmt)
        bt = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
        starts = jnp.full((B,), ps, jnp.int32)  # one history page
        win = jnp.full((1,), 2 ** 30, jnp.int32)
        return lambda p: functools.partial(
            prefill_attention_mod.prefill_attention_paged, q, kc, vc,
            kp, vp, bt, starts, win, fmt_kv=fmt, interpret=interp, **p)
    if kernel == "decode_sample":
        B, D, V = shape
        (fmt,) = fmts
        x = jnp.asarray(rng.normal(0, 1, (B, D)), jnp.float32)
        w = jnp.asarray(rng.normal(0, 1, (D, V)), jnp.float32)
        plan = "fused" if fmt is not None else "fake_quant"
        if fmt is not None:
            w = posit.pack(w, fmt)
        noise = jnp.asarray(rng.gumbel(size=(B, V)), jnp.float32)
        temp = jnp.float32(0.8)
        # the sweep grid's 0 sentinel = whole vocab (ops.decode_sample
        # applies the same translation at dispatch time)
        return lambda p: functools.partial(
            paged_attention_mod.decode_sample, x, w, noise, temp,
            plan=plan, fmt_w=fmt, top_k=min(8, V), interpret=interp,
            v_block=(None if p["v_block"] == 0 else p["v_block"]))
    raise KeyError(kernel)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--commit", action="store_true",
                    help="rewrite the committed cache "
                         "(kernels/autotune_cache.json)")
    ap.add_argument("--out", default=None,
                    help="write the cache JSON to this path instead")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="one sweep point per kernel (smoke)")
    ap.add_argument("--prune-factor", type=float, default=4.0)
    ap.add_argument("--oracle-check", action="store_true",
                    help="lower each winner and print hlo_analysis "
                         "accounting next to the analytic oracle")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    cache = autotune.AutotuneCache()
    print(f"backend: {cache.backend} (interpret={ops.interpret_mode()})")
    for kernel, points in sweep_points(args.quick).items():
        for shape, fmts in points:
            run = _runner(kernel, shape, fmts, rng)
            params, ms, table = autotune.sweep(
                kernel, shape, run, fmts=fmts, reps=args.reps,
                prune_factor=args.prune_factor)
            oracle_ms = autotune.oracle_cost(kernel, shape, params, fmts) * 1e3
            cache.put(kernel, shape, params, fmts=fmts, ms=ms,
                      oracle_ms=oracle_ms)
            timed = sum(1 for t in table if t["ms"] is not None)
            print(f"{kernel} @ {autotune.shape_bucket(shape)} "
                  f"{[autotune._fmt_name(f) for f in fmts]}: {params} "
                  f"({ms:.3f} ms; {timed}/{len(table)} timed)")
            if args.oracle_check:
                acct = autotune.hlo_cost(run(params))
                print(f"  hlo: flops={acct['flops']:.3g} "
                      f"hbm_bytes={acct['hbm_bytes']:.3g} "
                      f"oracle_ms={oracle_ms:.4f}")
    if args.commit or args.out:
        path = cache.save(args.out or autotune.DEFAULT_CACHE_PATH)
        print(f"wrote {len(cache.entries)} entries -> {path}")
    else:
        print(f"{len(cache.entries)} entries swept (dry run; "
              f"--commit to persist)")


if __name__ == "__main__":
    main()
