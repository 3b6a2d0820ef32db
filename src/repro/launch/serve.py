"""Serving entry point (continuous batching).

    python -m repro.launch.serve --arch gemma3_4b --smoke --requests 8 \
        --quant serve_p16_kv8

Posit-native speculative decoding (draft policy proposes k tokens, one
batched multi-query verify dispatch commits the matching prefix — token
streams stay bitwise identical to plain decode):

    python -m repro.launch.serve --arch gemma3_4b --smoke --requests 8 \
        --quant serve_fused_p16 --speculate 4

Async front end — SLO classes, deadlines, preemption, per-token
streaming callbacks, TTFT/ITL histograms — lives in
`repro.serve.AsyncServingFrontend`; `examples/serve_async.py` is the
runnable walkthrough (mixed interactive/batch queue, a mid-flight
high-priority arrival preempting a batch slot, streaming dedup across
the replay, speculation on top):

    PYTHONPATH=src python examples/serve_async.py
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.quant import policy_by_name
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serve import Request, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--quant", default="none")
    ap.add_argument("--dense", action="store_true",
                    help="dense KV cache instead of the paged pool")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV page size (default: quant policy kv_page_size)")
    ap.add_argument("--sample", action="store_true",
                    help="temperature/top-k sampling instead of greedy")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=0,
                    help="shard the paged KV pool over this many devices "
                         "(0 = single-device pool)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="speculative decoding span k (>= 2; draft policy "
                         "= quant.with_draft(), bitwise-identical tokens)")
    args = ap.parse_args()
    if not args.sample and (args.temperature != 1.0 or args.top_k):
        raise SystemExit("--temperature/--top-k only take effect with "
                         "--sample (greedy decoding ignores them)")

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    cfg = cfg.replace(quant=policy_by_name(args.quant))
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only; nothing to serve")
    enable_compile_cache()
    mesh = None
    if args.mesh_model > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(args.mesh_model)
    # a policy with posit weights gets them built straight into codes, one
    # leaf at a time (replicated over the mesh as they are built): full
    # widths fit where the float tree would not
    if cfg.quant.weights is not None:
        rep = None if mesh is None else NamedSharding(mesh, P())
        params = api.init_packed(jax.random.key(0), cfg, sharding=rep)
    else:
        params = api.init(jax.random.key(0), cfg)
    engine = ServingEngine(cfg, params, batch_slots=args.slots,
                           max_seq=args.max_seq, paged=not args.dense,
                           page_size=args.page_size,
                           greedy=not args.sample,
                           temperature=args.temperature, top_k=args.top_k,
                           speculate_k=args.speculate, mesh=mesh)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        engine.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
            max_new_tokens=args.new_tokens))
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    total_new = sum(len(r.out_tokens) for r in done)
    layout = (f"paged(ps={engine.layout.page_size}, "
              f"peak={engine.allocator.peak_in_use}/"
              f"{engine.allocator.capacity} pages)"
              if engine.paged else "dense")
    print(f"[serve] {len(done)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s) kv dtype="
          f"{'posit' if cfg.quant.kv_cache else cfg.dtype} cache={layout}")
    s = engine.execution_summary()
    print(f"[serve] scheduling: {s['admissions']} admissions, queue wait "
          f"{_ms(s['queue_wait_mean_ms'])}, admit to first token "
          f"{_ms(s['admit_to_first_token_mean_ms'])} (means); prefill row "
          f"use {_pct(s['prefill_row_use'])}, decode slot use "
          f"{_pct(s['decode_slot_use'])}")
    if args.speculate:
        print(f"[serve] speculation: k={s['speculate_k']} "
              f"rounds={s['speculation_rounds']} "
              f"accept_rate={s['speculation_accept_rate']:.3f} "
              f"committed={s['speculation_committed_tokens']}")
    if engine.paged and engine.n_shards > 1:
        occ = engine.allocator.pages_in_use_by_shard
        per = engine.allocator.pages_per_shard - 1
        print("[serve] per-device page occupancy: "
              + " ".join(f"d{i}={u}/{per}" for i, u in enumerate(occ)))


def _ms(v):
    return "n/a" if v is None else f"{v:.1f} ms"


def _pct(v):
    return "n/a" if v is None else f"{100 * v:.1f}%"


if __name__ == "__main__":
    main()
