#!/usr/bin/env python3
"""Calibration runs of one cell in one process: the knee sweep and the
readings that the correctness limits were set from.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 20 [--rates 0.5,1,2] [--engine '{"batch_slots": 16}'] \
        [--control] [--trace] [--limit 0.13] \
        --out <file.jsonl>

For every seed (and every rate, when given, overriding the mix's arrival
rate) it makes one run exactly as chipbench/run.py does, judged by the
cell's limits (or --limit), and with --control also judges the control:
the reference computed with float8 activations in the program's place,
which has to come out not correct.  One JSON line per run goes to --out.
The benchmark's own runs never call this; it needs a TPU as they do.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import check, run as harness, traffic  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--engine", default="{}")
    ap.add_argument("--mix", default="{}",
                    help="JSON merged into the mix's entries (e.g. shorter "
                         "prompts for a small recorded trace)")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--limit", type=float, default=None,
                    help="max_logit_gap limit in place of the cell's")
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("no TPU: refusing to run")
    bench = harness.load_bench()
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    limits = check.load_limits(cell["name"])
    if args.limit is not None:
        limits["max_logit_gap"] = args.limit
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        harness.CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    base = traffic.load(cell["traffic"])
    base["engine"].update(json.loads(args.engine))
    for key, val in json.loads(args.mix).items():
        if isinstance(val, dict):
            base.setdefault(key, {}).update(val)
        else:
            base[key] = val
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        for rate in rates:
            mix = copy.deepcopy(base)
            if rate is not None:
                mix["arrival"]["rate"] = rate
            t = time.perf_counter()
            res = harness.run_cell(bench, cell, seed, args.seconds,
                                   args.trace, devices[:cell["chips"]],
                                   t_start=t, mix=mix, control=args.control,
                                   keep_trace=(f"{args.keep_trace}/{seed}"
                                               if args.keep_trace else None),
                                   limits=limits)
            res.update(seed=seed, rate=rate, engine=mix["engine"],
                       wall_s=time.perf_counter() - t)
            line = json.dumps(res)
            print(line, flush=True)
            with out.open("a") as f:
                f.write(line + "\n")
            gc.collect()
    print(f"readings: {time.perf_counter() - T_START:.1f} s in all",
          file=sys.stderr)


if __name__ == "__main__":
    main()
