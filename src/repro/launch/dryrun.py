"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE proof of distribution coherence without hardware: a successful
`.lower().compile()` on the production mesh means every sharding,
collective, and memory assignment is consistent; the compiled artifact's
cost/memory analysis feeds the roofline (EXPERIMENTS.md).

Usage:
    python -m repro.launch.dryrun --arch gemma3_4b --shape train_4k
    python -m repro.launch.dryrun --arch all --multi-pod
    python -m repro.launch.dryrun --arch all --shape all --both-meshes \
        --out experiments/dryrun
"""
# The host platform must present 512 virtual devices BEFORE jax initializes;
# these two lines must precede every other import (including repro.*).
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs                     # noqa: E402
from repro.launch.mesh import PEAKS, V5E, make_production_mesh  # noqa: E402
from repro.models import api                  # noqa: E402
from repro.models.config import shape_by_name, ALL_SHAPES  # noqa: E402
from repro.models.module import ParamSpec, abstract_params, param_bytes  # noqa: E402
from repro.optim import adamw, adafactor, cosine_schedule  # noqa: E402
from repro.parallel import sharding           # noqa: E402
from repro.train import step as step_lib      # noqa: E402

_IS_SPEC = lambda s: isinstance(s, ParamSpec)

# gradient-accumulation factor per train cell: microbatch 32 sequences
# divides both the 16-way and 32-way batch shardings and bounds live
# activations to one microbatch per layer under remat.
TRAIN_ACCUM = 8


def _opt_for(cfg):
    # >100B params: factored second moment keeps optimizer state in HBM
    n = param_bytes(api.param_specs(cfg)) / 4
    lr = cosine_schedule(3e-4, 2000, 100_000)
    return adafactor(lr) if n > 100e9 else adamw(lr)


def _spec_shardings(spec_tree, mesh):
    return jax.tree.map(
        lambda s: sharding.sharding_for(s.shape, s.logical_axes, mesh),
        spec_tree, is_leaf=_IS_SPEC)


def _opt_state_shardings(opt_state_abs, pspecs, mesh):
    """Build shardings for optimizer state: moments follow the parameter
    logical axes (matching trailing dims); scalars replicate."""
    flat_p, _ = jax.tree_util.tree_flatten(pspecs, is_leaf=_IS_SPEC)

    def for_array(a):
        # match a moment leaf to its parameter by shape suffix
        for ps in flat_p:
            if a.shape == ps.shape:
                return sharding.sharding_for(a.shape, ps.logical_axes, mesh)
            if len(ps.shape) >= 2 and a.shape == ps.shape[:-1]:  # adafactor vr
                return sharding.sharding_for(a.shape, ps.logical_axes[:-1], mesh)
            if len(ps.shape) >= 2 and a.shape == ps.shape[:-2] + ps.shape[-1:]:
                return sharding.sharding_for(
                    a.shape, ps.logical_axes[:-2] + ps.logical_axes[-1:], mesh)
        return sharding.sharding_for(a.shape, (None,) * len(a.shape), mesh)

    return jax.tree.map(for_array, opt_state_abs)


def _batch_shardings(batch_abs, mesh):
    return jax.tree.map(
        lambda a: sharding.sharding_for(
            a.shape, ("batch",) + (None,) * (len(a.shape) - 1), mesh),
        batch_abs)


# ---------------------------------------------------------------------------
# HLO collective-byte accounting (per-device program => per-device bytes)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s64": 8,
                "u64": 8, "c64": 8, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")


def _shape_bytes(shape_str: str) -> int:
    """'f32[16,128]' -> bytes; tuple shapes handled by caller."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    b = _DTYPE_BYTES.get(dt, 4)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in a (per-device) HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    # lines look like: '  %x = f32[8,128]{1,0} all-reduce(...)' or
    # '  ROOT %t = (f32[2,4]{...}, f32[2,4]{...}) all-gather(...)'
    pat = re.compile(
        r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+([a-z-]+)")
    for m in pat.finditer(hlo_text):
        shapes, op = m.groups()
        if op not in out:
            continue
        count[op] += 1
        if shapes.startswith("("):
            for s in re.findall(r"[a-z0-9]+\[[0-9,]*\]", shapes):
                out[op] += _shape_bytes(s)
        else:
            out[op] += _shape_bytes(shapes)
    total = sum(out.values())
    return {"per_op": out, "counts": count, "total_bytes": total}


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------


def build_lowered(cfg, shape, mesh, accum=None):
    accum = accum or TRAIN_ACCUM
    pspecs = api.param_specs(cfg)
    params_abs = abstract_params(pspecs)
    params_sh = _spec_shardings(pspecs, mesh)
    batch_abs = api.input_specs(cfg, shape)
    batch_sh = _batch_shardings(batch_abs, mesh)

    with sharding.use_mesh(mesh):
        if shape.kind == "train":
            opt = _opt_for(cfg)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            opt_sh = _opt_state_shardings(opt_abs, pspecs, mesh)
            state_abs = step_lib.TrainState(params_abs, opt_abs,
                                            jax.ShapeDtypeStruct((), jnp.int32))
            state_sh = step_lib.TrainState(
                params_sh, opt_sh,
                sharding.sharding_for((), (), mesh))
            fn = step_lib.make_train_step(cfg, opt, accum=accum)
            lowered = jax.jit(
                fn, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state_abs, batch_abs)
        elif shape.kind == "prefill":
            cspecs = api.cache_specs(cfg, shape.global_batch, shape.seq_len) \
                if not cfg.is_encoder else None
            if cfg.is_encoder:
                fn = lambda p, b: api.apply(p, b, cfg)
                out_sh = None
            else:
                fn = lambda p, b: api.prefill(p, b, cfg, max_seq=shape.seq_len)
                out_sh = (None, _spec_shardings(cspecs, mesh))
            lowered = jax.jit(
                fn, in_shardings=(params_sh, batch_sh), out_shardings=out_sh,
            ).lower(params_abs, batch_abs)
        elif shape.kind == "decode":
            cspecs = api.cache_specs(cfg, shape.global_batch, shape.seq_len)
            cache_abs = abstract_params(cspecs)
            cache_sh = _spec_shardings(cspecs, mesh)
            tokens_abs = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            tokens_sh = sharding.sharding_for(tokens_abs.shape, ("batch",), mesh)
            fn = lambda p, t, c: api.decode_step(p, t, c, cfg)
            lowered = jax.jit(
                fn, in_shardings=(params_sh, tokens_sh, cache_sh),
                out_shardings=(None, cache_sh), donate_argnums=(2,),
            ).lower(params_abs, tokens_abs, cache_abs)
        else:
            raise ValueError(shape.kind)
    return lowered


def analyze(lowered, compiled, cfg, shape, mesh, compile_s):
    from repro.launch.hlo_analysis import analyze_hlo

    n_dev = mesh.devices.size
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax: one dict per partition
        cost = cost[0] if cost else {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}
    # trip-count-aware per-device analysis (XLA's cost_analysis counts loop
    # bodies once — useless for scanned layers; see hlo_analysis.py)
    hlo = analyze_hlo(compiled.as_text())
    flops = hlo["flops"]
    bytes_acc = hlo["hbm_bytes"]
    coll = {"per_op": hlo["collective_per_op"],
            "counts": hlo["collective_counts"],
            "total_bytes": hlo["collective_bytes"]}

    # --- roofline terms (per-device program -> per-chip seconds) ----------
    hw = PEAKS[V5E]  # the production mesh is built of v5e chips
    t_compute = flops / hw["peak_flops_bf16"]
    t_memory = bytes_acc / hw["hbm_bw"]
    t_coll = coll["total_bytes"] / hw["ici_bw"]
    # model flops: 6*N*D for train, 2*N*D for a forward/prefill token batch
    from repro.models.module import param_count
    n_params = param_count(api.param_specs(cfg))
    n_active = _active_params(cfg)
    if shape.kind == "train":
        model_flops = 6.0 * n_active * shape.tokens
    elif shape.kind == "prefill":
        model_flops = 2.0 * n_active * shape.tokens
    else:
        model_flops = 2.0 * n_active * shape.global_batch
    terms = {
        "compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll,
        "dominant": max(
            [("compute", t_compute), ("memory", t_memory), ("collective", t_coll)],
            key=lambda kv: kv[1])[0],
        "model_flops_total": model_flops,
        "model_flops_per_dev": model_flops / n_dev,
        "hlo_flops_per_dev": flops,
        "useful_flops_ratio": (model_flops / n_dev) / flops if flops else None,
    }
    return {
        "arch": cfg.name, "shape": shape.name, "kind": shape.kind,
        "mesh": dict(mesh.shape), "devices": n_dev,
        "params": n_params, "active_params": n_active,
        "compile_seconds": compile_s,
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory_analysis": mem_info,
        "collectives": coll,
        "roofline": terms,
    }


def _active_params(cfg):
    """Parameters touched per token (MoE: top-k + shared only)."""
    from repro.models.module import param_count
    total = param_count(api.param_specs(cfg))
    if cfg.n_experts == 0:
        return total
    # subtract inactive routed-expert params
    expert = cfg.d_model * cfg.moe_d_ff * 3
    if cfg.family == "hybrid":
        n_moe_layers = sum(1 for i in range(cfg.n_layers) if cfg.layer_is_moe(i))
    else:
        n_moe_layers = cfg.n_layers
    inactive = n_moe_layers * (cfg.n_experts - cfg.top_k) * expert
    return total - inactive


def run_cell(arch, shape_name, multi_pod, out_dir=None, cfg_overrides=None,
             tag="", accum=None):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_tag = "multipod_2x16x16" if multi_pod else "pod_16x16"
    cfg = configs.get(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = shape_by_name(shape_name)
    t0 = time.time()
    lowered = build_lowered(cfg, shape, mesh, accum=accum)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rec = analyze(lowered, compiled, cfg, shape, mesh, compile_s=t2 - t1)
    rec["lower_seconds"] = t1 - t0
    rec["mesh_tag"] = mesh_tag
    rec["variant"] = tag or "baseline"
    print(f"[dryrun] {arch} x {shape_name} x {mesh_tag}"
          f"{' x ' + tag if tag else ''}: OK "
          f"(lower {t1-t0:.1f}s compile {t2-t1:.1f}s) "
          f"dominant={rec['roofline']['dominant']} "
          f"flops/dev={rec['roofline']['hlo_flops_per_dev']:.3g} "
          f"coll={rec['collectives']['total_bytes']:.3g}B")
    print("  memory_analysis:", rec["memory_analysis"])
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        path = os.path.join(out_dir,
                            f"{arch}__{shape_name}__{mesh_tag}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--continue-on-error", action="store_true")
    ap.add_argument("--tag", default="", help="artifact suffix for variants")
    ap.add_argument("--quant", default=None,
                    help="QuantPolicy name (none|paper_mixed|serve_p16_kv8|...)")
    ap.add_argument("--cast-params-early", action="store_true")
    ap.add_argument("--shard-expert-cap", action="store_true")
    ap.add_argument("--tp-bf16-reduce", action="store_true")
    ap.add_argument("--fsdp-gather-weights", action="store_true")
    ap.add_argument("--moe-grouped-dispatch", action="store_true")
    ap.add_argument("--accum", type=int, default=None,
                    help="gradient accumulation steps for train cells")
    args = ap.parse_args()

    overrides = {}
    if args.quant:
        from repro.core.quant import policy_by_name
        overrides["quant"] = policy_by_name(args.quant)
    if args.cast_params_early:
        overrides["cast_params_early"] = True
    if args.shard_expert_cap:
        overrides["shard_expert_cap"] = True
    if args.tp_bf16_reduce:
        overrides["tp_bf16_reduce"] = True
    if args.fsdp_gather_weights:
        overrides["fsdp_gather_weights"] = True
    if args.moe_grouped_dispatch:
        overrides["moe_grouped_dispatch"] = True

    archs = configs.ARCH_NAMES if args.arch == "all" else [args.arch]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch in archs:
        shapes = ([s.name for s in configs.runnable_shapes(arch)]
                  if args.shape == "all" else [args.shape])
        for shape_name in shapes:
            for mp in meshes:
                try:
                    run_cell(arch, shape_name, mp, args.out,
                             cfg_overrides=overrides or None, tag=args.tag,
                             accum=args.accum)
                except Exception as e:
                    failures.append((arch, shape_name, mp, repr(e)))
                    print(f"[dryrun] {arch} x {shape_name} x multipod={mp}: "
                          f"FAIL {e}")
                    traceback.print_exc()
                    if not args.continue_on_error:
                        raise
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("   ", f)
        raise SystemExit(1)
    print("[dryrun] all requested cells compiled.")


if __name__ == "__main__":
    main()
