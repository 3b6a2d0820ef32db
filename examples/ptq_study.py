"""Post-training quantization study: model-level analogue of Table I.

Trains a small LM in float32, then evaluates held-out cross-entropy with
weights (and optionally activations) quantized to each storage format —
the deployment question PDPU answers: which posit format serves this model
with how much quality loss, at what hardware cost (generator model).

    PYTHONPATH=src python examples/ptq_study.py --steps 300
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import posit
from repro.core.formats import P8_2, P10_2, P13_2, P16_2
from repro.data import DataConfig, Pipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.models.config import ShapeConfig
from repro.models.module import param_count
from repro.optim import adamw, cosine_schedule
from repro.train import Trainer, TrainerConfig, step as step_lib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="minitron_8b")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch)
    shape = ShapeConfig("ptq", seq_len=128, global_batch=8, kind="train")
    pipe = Pipeline(cfg, shape, DataConfig(seed=0))
    opt = adamw(cosine_schedule(3e-3, warmup=args.steps // 10, total=args.steps))
    tr = Trainer(cfg, shape, opt, pipe,
                 TrainerConfig(total_steps=args.steps,
                               log_every=max(args.steps // 5, 1),
                               ckpt_every=args.steps, accum=1))
    state = tr.run(jax.random.key(0))
    params = state.params

    eval_pipe = Pipeline(cfg, shape, DataConfig(seed=777))
    batches = [jax.tree.map(jnp.asarray, eval_pipe.batch_at(i)) for i in range(4)]
    eval_step = jax.jit(lambda p, b: step_lib.loss_fn(p, b, cfg)[0])

    def ce_with(quantize):
        q = jax.tree.map(lambda p: quantize(p) if p.ndim >= 2 else p, params)
        return float(np.mean([float(eval_step(q, b)) for b in batches]))

    base = ce_with(lambda p: p)
    rows = [("float32 (reference)", base, 32)]
    rows.append(("bfloat16", ce_with(lambda p: p.astype(jnp.bfloat16)
                                     .astype(jnp.float32)), 16))
    rows.append(("float16", ce_with(lambda p: p.astype(jnp.float16)
                                    .astype(jnp.float32)), 16))
    for fmt in (P16_2, P13_2, P10_2, P8_2):
        rows.append((str(fmt), ce_with(lambda p, f=fmt: posit.quantize(p, f)),
                     fmt.n))

    n = param_count(api.param_specs(cfg))
    print(f"\nPTQ held-out CE ({cfg.name}, {n/1e3:.0f}K params, "
          f"{args.steps} train steps):")
    print(f"{'format':22} {'eval CE':>9} {'delta':>8} {'bits':>5} "
          f"{'weight MB/1B-params':>20}")
    for name, ce, bits in rows:
        print(f"{name:22} {ce:9.4f} {ce-base:+8.4f} {bits:5d} "
              f"{bits/8*1000:20.0f}")
    p16 = dict((r[0], r[1]) for r in rows)
    ok = (p16["P(16,2)"] - base) < 0.01 and (p16["P(13,2)"] - base) < 0.05
    print("\nposit-16 serves at float quality, posit-13 within noise — the "
          "paper's mixed-precision deployment claim." if ok else
          "\nWARNING: posit quality gap larger than expected on this run.")


if __name__ == "__main__":
    main()
