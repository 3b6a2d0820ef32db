"""End-to-end driver: train a small LM with the paper's mixed-precision
posit quantization (P(13,2) operands, f32 wide accumulation — the PDPU
contract) and compare against an unquantized run.

`--execution` picks the QAT datapath (QuantPolicy.with_execution):

  fake_quant : STE fake-quantization on float dots (the classical recipe).
  fused      : kernel-in-the-loop QAT — every matmul forward runs the
               packed Pallas fused GEMM (encode -> in-kernel decode ->
               wide f32 MXU accumulate) and the loss/grads come from that
               datapath via the custom_vjp STE backward.  Training sees
               exactly what fused serving will execute.

    PYTHONPATH=src python examples/train_posit_lm.py --steps 200
    PYTHONPATH=src python examples/train_posit_lm.py --execution fused
"""
import argparse

import jax

from repro import configs
from repro.core.quant import TRAINABLE_PLANS, policy_by_name
from repro.data import DataConfig, Pipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ShapeConfig
from repro.optim import adamw, cosine_schedule
from repro.train import Trainer, TrainerConfig


def run(quant: str, steps: int, arch: str, execution: str = "fake_quant"):
    policy = policy_by_name(quant)
    if policy.enabled:  # 'none' has no formats: nothing to execute fused
        policy = policy.with_execution(execution).require_trainable()
    cfg = configs.get_smoke(arch).replace(quant=policy)
    shape = ShapeConfig("ex", seq_len=128, global_batch=8, kind="train")
    pipe = Pipeline(cfg, shape, DataConfig(seed=0))
    opt = adamw(cosine_schedule(3e-3, warmup=steps // 10, total=steps))
    tr = Trainer(cfg, shape, opt, pipe,
                 TrainerConfig(total_steps=steps, log_every=max(steps // 10, 1),
                               ckpt_every=steps, accum=2))
    tr.run(jax.random.key(0))
    return [h["loss"] for h in tr.history]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--arch", default="minitron_8b")
    ap.add_argument("--execution", default="fake_quant",
                    choices=list(TRAINABLE_PLANS),
                    help="QAT datapath: fake_quant (STE on float dots) or "
                         "fused (packed Pallas kernel forward, STE backward)")
    args = ap.parse_args()
    enable_compile_cache()
    base = run("none", args.steps, args.arch)
    mixed = run("paper_mixed", args.steps, args.arch, args.execution)
    n = max(args.steps // 5, 1)
    print(f"\nfinal loss (mean of last {n}):")
    print(f"  float32      : {sum(base[-n:])/n:.4f}")
    print(f"  P(13,2) mixed: {sum(mixed[-n:])/n:.4f} "
          f"(execution={args.execution})")
    print("mixed-precision posit training tracks the float baseline "
          "(paper §III-B / PositNN [26]).")


if __name__ == "__main__":
    main()
