"""Kernel micro-benchmarks (CPU interpret wall time is NOT TPU performance;
the derived column is the analytic TPU roofline time for the same call:
max(bytes/HBM_bw, flops/MXU) from the kernel's own tile arithmetic).

Three sections beyond the raw kernel table:

  decode tokens/sec : the paged-attention decode step as served — one
      3-D kernel launch per new token (the T=1 hot loop) vs ONE 4-D
      multi-query launch covering all T tokens of every active slot.
      The grids compute bitwise-identical outputs (checked here), so the
      structural win is launches/token: T -> 1.
  prefill fused vs three-program : one prefill chunk through the fused
      kernel (ops.prefill_attention_paged — attention + posit KV encode
      + page scatter in ONE device program) vs the decomposed path
      (flash_attention, kv encode, insert_chunk_batched: three).  Bit
      parity of attention output and written pages is asserted.
  autotune : whether the committed tile cache resolved params for the
      shapes this benchmark launches (kernels/autotune.hit_report).

Results are written as machine-readable BENCH_kernels.json.  `checks` are
hard booleans; `gated` carries the structural ratios the CI perf gate
(benchmarks/perf_gate.py) compares against the committed baseline —
wall-clock latencies are recorded but never gated (interpret-mode noise).

    PYTHONPATH=src python benchmarks/bench_kernels.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.timing import time_ms, write_bench_json
except ImportError:  # bare-script run: benchmarks/ itself is sys.path[0]
    from timing import time_ms, write_bench_json
from repro.core import posit
from repro.core.formats import P13_2, P16_1, P16_2, P8_2, PDPUConfig
from repro.kernels import autotune, ops
from repro.launch.mesh import PEAKS, V5E
from repro.models import common, paged

HW = PEAKS[V5E]  # the roofline columns model one TPU v5e


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


def rows(rng=None):
    rng = rng or np.random.default_rng(0)
    out = []

    # codec kernels
    for fmt, shape in [(P16_2, (1024, 1024)), (P8_2, (1024, 1024))]:
        codes = jnp.asarray(rng.integers(0, 1 << fmt.n, shape), jnp.int32)
        us = _time(lambda c: ops.decode(c, fmt), codes)
        n = np.prod(shape)
        tpu_us = max(n * (fmt.storage_bits // 8 + 4) / HW["hbm_bw"],
                     n * 20 / (HW["peak_flops_bf16"] / 2)) * 1e6
        out.append((f"decode_{fmt}_{shape[0]}x{shape[1]}", us, tpu_us))
        x = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
        us = _time(lambda v: ops.encode(v, fmt), x)
        out.append((f"encode_{fmt}_{shape[0]}x{shape[1]}", us, tpu_us))

    # fused matmul kernel (posit-in, posit-out)
    M = K = N = 512
    a = jnp.asarray(rng.integers(0, 1 << 16, (M, K)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 1 << 16, (K, N)), jnp.int32)
    a = jnp.where(a == P16_2.nar_code, 0, a)
    b = jnp.where(b == P16_2.nar_code, 0, b)
    us = _time(lambda x, y: ops.fused_matmul(x, y, P16_2, P16_2, P16_2,
                                             bm=128, bn=128, bk=256), a, b)
    flops = 2 * M * K * N
    byts = (M * K + K * N) * 2 + M * N * 2
    tpu_us = max(flops / HW["peak_flops_bf16"], byts / HW["hbm_bw"]) * 1e6
    out.append((f"fused_matmul_{M}x{K}x{N}_p16", us, tpu_us))

    # bit-exact PDPU GEMM kernel (VPU-bound fidelity path)
    cfg = PDPUConfig(P13_2, P16_2, N=4, w_m=14)
    a = jnp.asarray(rng.integers(0, 1 << 13, (64, 32)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 1 << 13, (32, 64)), jnp.int32)
    us = _time(lambda x, y: ops.pdpu_matmul(x, y, cfg, bm=32, bn=32), a, b)
    # ~200 int ops per MAC on the VPU (8-wide int32 lanes x 128)
    vpu_ops = 64 * 64 * 32 * 200
    tpu_us = vpu_ops / (HW["peak_flops_bf16"] / 16) * 1e6
    out.append(("pdpu_exact_gemm_64x32x64", us, tpu_us))
    return out


def bench_decode_mq(rng):
    """Decode-step tokens/sec: T single-token 3-D launches vs one 4-D
    multi-query launch over the same pool — bitwise-identical outputs."""
    B, T, Hq, Hkv, Dh, ps, M = 4, 8, 4, 2, 8, 4, 6
    fmt = P16_1
    F = Hkv * Dh
    n_pages = 1 + B * M
    kp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                jnp.float32), fmt)
    vp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                jnp.float32), fmt)
    bt = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
    lengths = jnp.asarray(rng.integers(T, M * ps, B), jnp.int32)
    win = jnp.full((1,), 2 ** 30, jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, T, Hq, Dh)), jnp.float32)

    def single_loop(q):
        outs = [ops.paged_attention(q[:, t], kp, vp, bt,
                                    lengths - (T - 1 - t), win, fmt_kv=fmt)
                for t in range(T)]
        return jnp.stack(outs, axis=1)

    def mq(q):
        return ops.paged_attention(q, kp, vp, bt, lengths, win, fmt_kv=fmt)

    single_ms = time_ms(single_loop, q)
    mq_ms = time_ms(mq, q)
    exact = bool(jnp.all(single_loop(q) == mq(q)))
    return {
        "slots": B, "new_tokens_per_slot": T,
        "single_token_ms_per_step": single_ms,
        "single_token_tokens_per_s": B * T / (single_ms / 1e3),
        "multi_query_ms_per_step": mq_ms,
        "multi_query_tokens_per_s": B * T / (mq_ms / 1e3),
        # structural: one MQ launch replaces T per-token launches
        "launches_per_token_ratio": float(T),
        "mq_matches_single_token": exact,
    }


def bench_fused_prefill(rng):
    """One prefill chunk: fused single-program kernel vs the decomposed
    three-program path (attention, KV encode, page insert) — bit parity
    of the attention output and every written non-trash page."""
    B, C, Hq, Hkv, Dh, ps, M = 2, 8, 4, 2, 8, 4, 6
    fmt = P16_1
    F = Hkv * Dh
    n_pages = 1 + B * M
    kp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                jnp.float32), fmt)
    vp = posit.pack(jnp.asarray(rng.normal(0, 1, (n_pages, ps, F)),
                                jnp.float32), fmt)
    bt = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
    starts = jnp.asarray([4, 9], jnp.int32)
    win = jnp.full((1,), 2 ** 30, jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, C, Hq, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, C, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, C, Hkv, Dh)), jnp.float32)

    def three_program(q, k, v):
        k_codes = posit.pack(k.reshape(B, C, -1), fmt)        # program 1
        v_codes = posit.pack(v.reshape(B, C, -1), fmt)
        hist_k = paged.gather_slots(kp, bt)
        hist_v = paged.gather_slots(vp, bt)
        k_new = paged.insert_chunk_batched(kp, bt, starts, k_codes)  # 2
        v_new = paged.insert_chunk_batched(vp, bt, starts, v_codes)
        S_h = hist_k.shape[1]
        hist_pos = jnp.broadcast_to(jnp.arange(S_h, dtype=jnp.int32)[None],
                                    (B, S_h))
        hist_pos = jnp.where(hist_pos < starts[:, None], hist_pos, -1)
        pos = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        kd = posit.unpack(hist_k, fmt).reshape(B, S_h, Hkv, Dh)
        vd = posit.unpack(hist_v, fmt).reshape(B, S_h, Hkv, Dh)
        attn = common.flash_attention(                        # program 3
            q, jnp.concatenate([kd, k], axis=1),
            jnp.concatenate([vd, v], axis=1), pos,
            jnp.concatenate([hist_pos, pos], axis=1), causal=True,
            window=None)
        return attn, k_new, v_new

    def fused(q, k, v):
        return ops.prefill_attention_paged(q, k, v, kp, vp, bt, starts, win,
                                           fmt_kv=fmt)

    three_ms = time_ms(three_program, q, k, v)
    fused_ms = time_ms(fused, q, k, v)
    a0, k0, v0 = three_program(q, k, v)
    a1, k1, v1 = fused(q, k, v)
    exact = bool(jnp.all(a0 == a1) and jnp.all(k0[1:] == k1[1:])
                 and jnp.all(v0[1:] == v1[1:]))
    return {
        "slots": B, "chunk": C,
        "three_program_ms": three_ms,
        "fused_ms": fused_ms,
        # structural: 3 logical device programs collapse into 1
        "programs_per_chunk_ratio": 3.0,
        "fused_bit_identical": exact,
    }


def bench_serving_programs():
    """Serving-structural launch counts on a real engine run with a long
    prompt (> 3 flash chunks, so fused prefill streams history): device
    programs per prefill chunk and per decode step.  Both ratios are
    work-units-per-program — 1.0 when every chunk / step is a single
    fused device program (the gated target), < 1 on any fallback."""
    from repro import configs
    from repro.core.quant import QuantPolicy
    from repro.models import api
    from repro.serve.engine import Request, ServingEngine

    q = QuantPolicy(weights=P16_2, kv_cache=P8_2, execution="fused")
    cfg = configs.get_tiny_serving("command_r_35b", q)
    params = api.init(jax.random.key(0), cfg)
    orig_chunk = paged.FLASH_CHUNK
    paged.FLASH_CHUNK = 16  # page size 16 divides it: fused span gate holds
    try:
        rng = np.random.default_rng(5)
        prompt = [int(t) for t in
                  rng.integers(0, cfg.vocab_size, 3 * paged.FLASH_CHUNK + 5)]
        eng = ServingEngine(cfg, params, batch_slots=2, max_seq=64,
                            greedy=True, base_seed=7)
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
        eng.submit(Request(rid=1, prompt=prompt[:9], max_new_tokens=4))
        done = eng.run()
        summ = eng.execution_summary()
    finally:
        paged.FLASH_CHUNK = orig_chunk
    chunks = summ["prefill_chunks"]
    p_progs = summ["prefill_device_programs"]
    steps = summ["decode_steps"]
    d_progs = summ["decode_device_programs"]
    return {
        "prompt_tokens": len(prompt), "flash_chunk": 16,
        "completed": len(done),
        "prefill_chunks": chunks,
        "prefill_device_programs": p_progs,
        "decode_steps": steps,
        "decode_device_programs": d_progs,
        # structural: 1.0 = every prefill chunk / decode step is ONE program
        "prefill_chunks_per_device_program": chunks / p_progs,
        "decode_steps_per_device_program": steps / d_progs,
        "long_prefill_fully_fused": p_progs == chunks,
        "decode_single_program": d_progs == steps and summ["fused_decode"],
    }


def main():
    rng = np.random.default_rng(0)
    print("kernel,us_per_call_cpu_interpret,us_per_call_tpu_roofline")
    kernel_rows = rows(rng)
    for name, us, tpu in kernel_rows:
        print(f"{name},{us:.0f},{tpu:.2f}")

    decode = bench_decode_mq(rng)
    print(f"\ndecode: {decode['slots']} slots x "
          f"{decode['new_tokens_per_slot']} tokens — "
          f"single-token {decode['single_token_ms_per_step']:.1f} ms "
          f"({decode['single_token_tokens_per_s']:.0f} tok/s) vs "
          f"multi-query {decode['multi_query_ms_per_step']:.1f} ms "
          f"({decode['multi_query_tokens_per_s']:.0f} tok/s); "
          f"bitwise match: {decode['mq_matches_single_token']}")

    prefill = bench_fused_prefill(rng)
    print(f"prefill: three-program {prefill['three_program_ms']:.1f} ms vs "
          f"fused {prefill['fused_ms']:.1f} ms per chunk; "
          f"bit identical: {prefill['fused_bit_identical']}")

    serving = bench_serving_programs()
    print(f"serving: {serving['prompt_tokens']}-token prompt over "
          f"flash_chunk={serving['flash_chunk']} — "
          f"{serving['prefill_chunks']} prefill chunks / "
          f"{serving['prefill_device_programs']} programs, "
          f"{serving['decode_steps']} decode steps / "
          f"{serving['decode_device_programs']} programs")

    tuned = autotune.hit_report()
    n_entries = len(autotune.get_cache().entries)
    print(f"autotune: {n_entries} cache entries; hits/misses: {tuned}")

    checks = {
        "mq_matches_single_token": decode["mq_matches_single_token"],
        "fused_prefill_bit_identical": prefill["fused_bit_identical"],
        "long_prefill_fully_fused": serving["long_prefill_fully_fused"],
        "decode_single_program": serving["decode_single_program"],
        "autotune_cache_loaded": n_entries > 0,
    }
    payload = {
        "kernels": [{"name": n, "us_cpu_interpret": u, "us_tpu_roofline": t}
                    for n, u, t in kernel_rows],
        "decode": decode,
        "prefill": prefill,
        "serving": serving,
        "autotune": {"entries": n_entries, "report": tuned},
        # the CI perf gate compares these (>10% regression fails); they
        # are structural ratios, deterministic on any host
        "gated": {
            "decode_launches_per_token_ratio":
                decode["launches_per_token_ratio"],
            "prefill_programs_per_chunk_ratio":
                prefill["programs_per_chunk_ratio"],
            # 1.0 = every long-prompt prefill chunk is ONE fused program
            "prefill_chunks_per_device_program":
                serving["prefill_chunks_per_device_program"],
            # 1.0 = every decode step is ONE program (fused epilogue)
            "decode_steps_per_device_program":
                serving["decode_steps_per_device_program"],
        },
        "checks": checks,
    }
    write_bench_json("kernels", payload)
    failed = [k for k, ok in checks.items() if not ok]
    if failed:
        raise SystemExit(f"kernel benchmark checks failed: {failed}")
    print("all kernel benchmark checks passed:",
          ", ".join(sorted(checks)))


if __name__ == "__main__":
    main()
