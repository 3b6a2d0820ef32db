#!/usr/bin/env python3
"""Smoke run of the serving main path on a TPU.

    python chip_smoke.py             # gemma3-4b at full width on one chip
    python chip_smoke.py --chips 4   # the kv_pages-sharded pool on 4 chips

One chip: gemma3-4b (34 layers, d_model 2560, 8 query / 4 KV heads of 256,
d_ff 10240, vocabulary 262144) is built from a seed straight into P(16,2)
weight codes and served by `ServingEngine` exactly as `launch/serve.py`
does, under the `serve_fused_p16` policy (fused posit GEMMs, P(8,2) KV
pages, one-program chunked prefill, one-program decode+sample).  Phases:

  (a) posit encode/decode kernels at a full-width weight shape are bitwise
      equal to the numpy reference codec (`core/posit_np`);
  (b) the posit GEMMs at a full-width projection agree with
      `kernels/ref.py` within the f32-accumulation bound K * 2**-24;
  (c) a queue of long prompts (half sharing a 256-token prefix) completes
      with in-range tokens and finite prefill logits, and one request
      served alone repeats its in-batch tokens exactly;
  (d) every prefill chunk and decode step ran as one fused device program;
  (e) the page pool drains.

`--chips 4` runs only the sharded-pool comparison: the same queue on a
one-chip pool, then on a pool sharded over four chips
(`launch.mesh.make_serving_mesh(4)`); token streams must be identical and
every shard's pool must drain.  The model is the same full-depth
gemma3-4b, its weights replicated on every chip.

Any failed check raises, so the exit code is non-zero.  Without a TPU the
script exits non-zero before running anything.  The last line of standard
output is one JSON object naming the device the run used.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 0
SLOTS = 4
MAX_SEQ = 2048
NEW_TOKENS = 32
PREFIX = 256
N_REQUESTS = 6
PROMPT_LENS = (300, 1200)


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    print(f"  ok: {what}")


def make_queue(rng, vocab: int, n: int, lo: int, hi: int, prefix: int):
    """n prompts with lengths in [lo, hi]; every other one opens with the
    same `prefix`-token system prompt."""
    from repro.serve import Request

    shared = rng.integers(0, vocab, prefix).astype(np.int32)
    reqs = []
    for i in range(n):
        length = int(rng.integers(lo, hi + 1))
        body = rng.integers(0, vocab, length).astype(np.int32)
        if i % 2 == 0:
            body[:prefix] = shared
        reqs.append(Request(rid=i, prompt=body, max_new_tokens=NEW_TOKENS))
    return reqs


def clone(req):
    from repro.serve import Request

    return Request(rid=req.rid, prompt=req.prompt.copy(),
                   max_new_tokens=req.max_new_tokens)


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own monitoring events —
    kept apart from the seconds spent running."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def phase_codec(shape):
    """(a) Pallas codec on the chip vs the numpy reference, bitwise."""
    import jax.numpy as jnp

    from repro.core import posit_np
    from repro.core.formats import P8_2, P16_2
    from repro.kernels import ops

    rng = np.random.default_rng(SEED)
    n = int(np.prod(shape))
    # wide dynamic range, exact zeros and both signs
    x = (rng.standard_normal(n) * np.exp2(rng.uniform(-30, 30, n)))
    x[rng.random(n) < 1e-3] = 0.0
    x = x.astype(np.float32).reshape(shape)
    for fmt in (P16_2, P8_2):
        got = np.asarray(ops.encode(jnp.asarray(x), fmt)).astype(np.int64)
        want = posit_np.encode_np(x.astype(np.float64), fmt)
        check(np.array_equal(got & fmt.mask, want),
              f"encode {fmt} {shape} bitwise == posit_np")
        codes = rng.integers(0, 1 << fmt.n, shape)
        store = {8: np.int8, 16: np.int16}[fmt.storage_bits]
        dev = np.asarray(ops.decode(jnp.asarray(codes.astype(store)), fmt))
        want = posit_np.decode_np(codes, fmt).astype(np.float32)
        # NaR decodes to a NaN whose payload is not part of the contract
        nar = np.isnan(want)
        check(np.array_equal(np.isnan(dev), nar)
              and np.array_equal(dev.view(np.int32)[~nar],
                                 want.view(np.int32)[~nar]),
              f"decode {fmt} {shape} bitwise == posit_np (NaR as NaN)")


def phase_gemm(m: int, k: int, n: int):
    """(b) posit GEMMs on the chip vs kernels/ref.py (its exact decode,
    multiplied out in float64 on the host).

    Bound: K * 2**-24 of sum_k |a_k| |b_k|, the worst case of summing K
    f32 products in any order with each product within one f32 rounding
    (unit roundoff 2**-24).  The GEMMs take their operands at f32
    precision; operands rounded to bf16 (8 significant bits of the 12 a
    P(16,2) value carries) read about 3e-4 at K=2560, above this bound."""
    import jax.numpy as jnp

    from repro.core.formats import P16_2
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED + 1)
    fmt = P16_2
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = (rng.standard_normal((k, n)) / np.sqrt(k)).astype(np.float32)
    a_codes = ops.encode(jnp.asarray(x), fmt)
    w_codes = ops.encode(jnp.asarray(w), fmt)
    # the reference's exact decode, multiplied out in float64 on the host
    xa = np.asarray(ref.decode_ref(a_codes, fmt), np.float64)
    wd = np.asarray(ref.decode_ref(w_codes, fmt), np.float64)
    x64 = x.astype(np.float64)
    cases = (
        ("fused_matmul (codes x codes)",
         ops.fused_matmul(a_codes, w_codes, fmt, fmt), xa @ wd,
         np.abs(xa) @ np.abs(wd)),
        ("matmul_posit_weights (f32 x codes, the serve path)",
         ops.matmul_posit_weights(jnp.asarray(x), w_codes, fmt), x64 @ wd,
         np.abs(x64) @ np.abs(wd)),
    )
    bound = k * 2.0**-24
    for name, got, want, mag in cases:
        err = np.abs(np.asarray(got, np.float64) - want)
        ratio = float(np.max(err / np.maximum(mag, 1e-30)))
        print(f"  {name} [{m}x{k}]x[{k}x{n}]: max |err| / sum|a||b| "
              f"= {ratio:.3e} (bound K*2**-24 = {bound:.3e})")
        check(bool(np.all(np.isfinite(np.asarray(got)))) and ratio <= bound,
              f"{name} within the f32-accumulation bound of kernels/ref.py")


def build_model(sharding=None):
    """gemma3-4b at full width, weights from the seed straight into codes
    (placed on `sharding` leaf by leaf, when given)."""
    import jax

    from repro import configs
    from repro.core.quant import policy_by_name
    from repro.models import api

    cfg = configs.get("gemma3_4b").replace(
        quant=policy_by_name("serve_fused_p16"))
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}; policy serve_fused_p16 "
          f"(weights {cfg.quant.weights}, kv {cfg.quant.kv_cache}, "
          f"execution {cfg.quant.execution})")
    t0 = time.perf_counter()
    params = api.init_packed(jax.random.key(SEED), cfg, sharding=sharding)
    print(f"weights built from seed {SEED} in "
          f"{time.perf_counter() - t0:.1f} s: "
          f"{api.weight_bytes(params)} resident bytes")
    return cfg, params


def serve(engine, reqs):
    """Submit and drain, as launch/serve.py does; returns rid -> tokens."""
    for r in reqs:
        engine.submit(r)
    done = engine.run()
    return {r.rid: list(r.out_tokens) for r in done}


def watch_prefill_logits(engine):
    """Count non-finite prefill logits of live rows as the engine samples
    first tokens (decode steps sample inside the fused program, so their
    logits never leave the device)."""
    seen = {"rows": 0, "nonfinite": 0}
    sample = engine._sample

    def checked(rows, slots, live=None):
        arr = np.asarray(rows)
        keep = np.ones(len(arr), bool) if live is None else np.asarray(live)
        seen["rows"] += int(keep.sum())
        seen["nonfinite"] += int((~np.isfinite(arr[keep])).sum())
        return sample(rows, slots, live)

    engine._sample = checked
    return seen


def phase_serving(cfg, params, clock):
    """(c)-(e) on one chip."""
    import jax

    from repro.kernels import autotune
    from repro.serve import ServingEngine

    engine = ServingEngine(cfg, params, batch_slots=SLOTS, max_seq=MAX_SEQ)
    seen = watch_prefill_logits(engine)
    reqs = make_queue(np.random.default_rng(SEED), cfg.vocab_size,
                      N_REQUESTS, *PROMPT_LENS, PREFIX)
    print(f"queue: {len(reqs)} prompts of {[len(r.prompt) for r in reqs]} "
          f"tokens ({PREFIX}-token prefix shared by every other one), "
          f"{NEW_TOKENS} new tokens each, {SLOTS} slots, max_seq {MAX_SEQ}, "
          f"page size {engine.layout.page_size}")
    c0, t0 = clock.seconds, time.perf_counter()
    out = serve(engine, [clone(r) for r in reqs])
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    n_tok = sum(len(t) for t in out.values())
    print(f"batch: {n_tok} tokens; {wall:.1f} s wall = {compile_s:.1f} s "
          f"compile + {wall - compile_s:.1f} s run")
    summ = engine.execution_summary()
    print("execution summary: " + json.dumps(
        {k: summ[k] for k in ("execution", "weights", "kv_cache",
                              "weight_bytes", "kv_bytes", "page_size",
                              "prefill_chunks", "prefill_device_programs",
                              "decode_steps", "decode_device_programs",
                              "pages_shared_mapped", "pages_in_use")}))
    print(f"prefix sharing: {engine.stats['shared_admissions']} admissions "
          f"onto {engine.stats['pages_shared']} shared pages")

    check(sorted(out) == [r.rid for r in reqs]
          and all(len(t) == NEW_TOKENS for t in out.values()),
          f"all {len(reqs)} requests completed with {NEW_TOKENS} tokens")
    check(all(0 <= t < cfg.vocab_size for ts in out.values() for t in ts),
          "every token in [0, vocab)")
    check(seen["rows"] == len(reqs) and seen["nonfinite"] == 0,
          f"prefill logits finite ({seen['rows']} rows x {cfg.vocab_size})")
    check(engine.stats["shared_admissions"] > 0,
          "the shared prefix was served from shared pages")
    check(summ["prefill_chunks"] > 0
          and summ["prefill_device_programs"] == summ["prefill_chunks"],
          f"prefill_device_programs == prefill_chunks "
          f"({summ['prefill_chunks']})")
    check(summ["decode_steps"] > 0
          and summ["decode_device_programs"] == summ["decode_steps"],
          f"decode_device_programs == decode_steps ({summ['decode_steps']})")
    check(engine.pages_in_use == 0, "page pool drained after the batch")

    # a prefix sharer served alone: no batch neighbours, no shared pages
    alone = reqs[2]
    c0, t0 = clock.seconds, time.perf_counter()
    solo = serve(engine, [clone(alone)])
    wall = time.perf_counter() - t0
    print(f"request {alone.rid} alone: {wall:.1f} s wall "
          f"({clock.seconds - c0:.1f} s compile)")
    check(solo[alone.rid] == out[alone.rid],
          f"request {alone.rid} alone == its tokens in the batch")
    check(engine.pages_in_use == 0, "page pool drained after the solo run")
    print(f"autotune hits/misses: {json.dumps(autotune.hit_report())}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} of "
          f"{stats.get('bytes_limit')}")


def phase_sharded(clock):
    """--chips 4: the same queue on a one-chip pool, then kv_pages-sharded
    over four chips; identical tokens, every shard drained.

    The weights (8.48 GiB of codes) are replicated over the four chips as
    they are built, and the one-chip pool serves from chip 0's replica:
    a second copy on chip 0 would not fit beside the first."""
    import gc

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_serving_mesh
    from repro.serve import ServingEngine

    mesh = make_serving_mesh(4)
    cfg, params = build_model(NamedSharding(mesh, PartitionSpec()))
    chip0 = jax.devices()[0]
    params_chip0 = jax.tree.map(
        lambda a: next(s.data for s in a.addressable_shards
                       if s.device == chip0), params)
    reqs = make_queue(np.random.default_rng(SEED), cfg.vocab_size, SLOTS,
                      *PROMPT_LENS, PREFIX)
    results = {}
    for chips, weights, pool_mesh in ((1, params_chip0, None),
                                      (4, params, mesh)):
        engine = ServingEngine(cfg, weights, batch_slots=SLOTS,
                               max_seq=MAX_SEQ, mesh=pool_mesh)
        c0, t0 = clock.seconds, time.perf_counter()
        results[chips] = serve(engine, [clone(r) for r in reqs])
        wall = time.perf_counter() - t0
        print(f"{chips}-chip pool ({engine.n_shards} shard(s)): "
              f"{sum(len(t) for t in results[chips].values())} tokens; "
              f"{wall:.1f} s wall ({clock.seconds - c0:.1f} s compile)")
        occ = engine.allocator.pages_in_use_by_shard
        check(engine.pages_in_use == 0 and not any(occ),
              f"{chips}-chip pool drained on every shard {occ}")
        if chips > 1:
            check(engine.n_shards == 4, "pool sharded over 4 chips")
        del engine
        gc.collect()
    check(all(len(t) == NEW_TOKENS for t in results[1].values()),
          "all requests completed")
    check(results[1] == results[4],
          "token streams identical on the 1-chip and 4-chip pools")
    for i, d in enumerate(jax.devices()[:4]):
        stats = d.memory_stats() or {}
        print(f"device {i} peak_bytes_in_use: "
              f"{stats.get('peak_bytes_in_use')}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU present (JAX found "
                 f"{devices[0].platform!r} devices); refusing to run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU chips, found {len(devices)}")
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: {ROOT} is not a checkout of this repository")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    check(not ops.interpret_mode(), "Pallas kernels compile for the TPU "
          "(not interpret mode)")
    print(f"device: {devices[0].device_kind} x {len(devices)}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(clock)
    else:
        phase_codec((2560, 10240))
        phase_gemm(SLOTS, 2560, 10240)
        cfg, params = build_model()
        phase_serving(cfg, params, clock)
    print(f"total {time.perf_counter() - t0:.1f} s "
          f"({clock.seconds:.1f} s compile)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
