#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (BENCHMARK.json `workloads`) names a configuration
(`chipbench/configs/<config>.json`, its reference beside it in
`<config>.py`) and a traffic mix (`chipbench/traffic/<mix>.json`).  The
run builds the weights on the device from the seed, sizes the engine from
the mix, warms up the shapes the mix uses, then offers the mix's load to
`AsyncServingFrontend` -> `ServingEngine` for `--seconds`, every request
timed from the moment it was due.  The metrics are read by one reader
each, `chipbench/metrics/<metric>.py`: with `--trace 0` the cell's
end-to-end metrics, with `--trace 1` its per-layer ones (the profiler on
from the window's start until the last request due in it has finished).
After the window the program's state is freed and a sample of finished
requests is checked against the reference (chipbench/check.py).

The last line of standard output is one JSON object; the numbers that
decided `correct` end standard error, each beside its limit.  Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from chipbench import check, traffic, weights  # noqa: E402
from chipbench.trace import WINDOW_SPAN, SPAN_PREFIX  # noqa: E402

HERE = ROOT / "chipbench"
CACHE_DIR = ROOT / ".jax_cache"
DRAIN_LIMIT_S = 150.0   # serving goes on past the window until every
                        # request due in it has finished, at most this long
WARM_RID = 1 << 30       # warm-up request ids, apart from the traffic's


def load_bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics this cell reports in a run with or without trace."""
    pool = bench["per_layer"] if trace else bench["end_to_end"]
    out = []
    for m in pool:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif not trace:
            out.append(m)
        else:
            moves = next(e for e in bench["end_to_end"]
                         if e["name"] == m["moves"])
            if "workloads" not in moves or cell in moves["workloads"]:
                out.append(m)
    return out


def load_reader(metric: str) -> Callable:
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(conf: dict):
    from repro.core.quant import policy_by_name
    from repro.models.config import ModelConfig
    return ModelConfig(**conf["model"], quant=policy_by_name(conf["policy"]))


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    conf: dict
    mix: dict
    cell: str
    chips: int
    seconds: float
    device_kind: str
    items: List[traffic.Item]
    due: Dict[int, float]          # rid -> absolute due time
    submitted: Dict[int, float]    # rid -> absolute submit time
    times: Dict[int, List[float]]  # rid -> absolute token times
    t0: float                      # window opens
    t1: float                      # window closes
    t_end: float                   # serving stopped (drained)
    setup_s: float
    counters0: dict                # engine.stats at t0
    counters1: dict                # engine.stats at t_end
    page_size: int
    memory_peak_bytes: Optional[int]
    step_seconds: Optional[float] = None   # inside engine.step, t0..t_end
    trace: Optional[dict] = None

    def in_window(self):
        return [it for it in self.items if self.t0 <= self.due[it.rid]
                < self.t1]


class StepClock:
    """Harness spans around its calls into the engine: the profiler sees
    each as a TraceAnnotation, and time inside engine.step is summed."""

    def __init__(self, engine, clock):
        import jax
        self.seconds = 0.0
        self._ann = jax.profiler.TraceAnnotation
        self._clock = clock
        step = engine.step

        def timed_step():
            t = clock()
            with self._ann(SPAN_PREFIX + "engine.step"):
                out = step()
            self.seconds += clock() - t
            return out

        engine.step = timed_step
        for attr, label in (("_fill_slots", "engine.fill_slots"),
                            ("_admit", "engine.admit"),
                            ("_run_chunk_group", "engine.prefill_chunk"),
                            ("_sample", "engine.sample_first_token"),
                            ("_decode_sample", "engine.decode_step")):
            if hasattr(engine, attr):
                setattr(engine, attr, self._span(getattr(engine, attr),
                                                 SPAN_PREFIX + label))

    def _span(self, fn, label):
        ann = self._ann

        def wrapped(*a, **k):
            with ann(label):
                return fn(*a, **k)
        return wrapped


def warm_up(engine, vocab: int, buckets):
    """Compile every program the mix's traffic reaches: one prefill chunk
    program per bucket size, the first-token sampler, the fused decode
    step, and the copy-on-write page fork (two identical prompts share a
    partial page).  No other shape is compiled."""
    from repro.serve import Request
    n = int(sum(buckets))
    prompt = (np.arange(n) * 7919 % vocab).astype(np.int32)
    for i in range(2):
        engine.submit(Request(rid=WARM_RID + i, prompt=prompt.copy(),
                              max_new_tokens=3))
    engine.run()
    engine.done.clear()


async def _serve(frontend, items, seconds, clock):
    """Offer `items` on their schedule, then serve on until every one has
    finished (at most DRAIN_LIMIT_S past the window); returns the
    per-request record."""
    t0 = clock()
    due = {it.rid: t0 + it.due for it in items}
    submitted = {}
    times = {it.rid: [] for it in items}
    tokens = {it.rid: [] for it in items}
    work = asyncio.Event()
    state = {"all_submitted": False}

    def on_token(rid, index, token):
        times[rid].append(clock())
        tokens[rid].append(int(token))

    async def submitter():
        for it in items:
            delay = due[it.rid] - clock()
            if delay > 0:
                await asyncio.sleep(delay)
            submitted[it.rid] = clock()
            frontend.submit(it.prompt, max_new_tokens=it.max_new_tokens,
                            rid=it.rid, on_token=on_token)
            work.set()
        state["all_submitted"] = True
        work.set()

    async def driver():
        while True:
            await work.wait()
            work.clear()
            await frontend.run()
            if state["all_submitted"] and not work.is_set():
                return

    tasks = [asyncio.ensure_future(submitter()),
             asyncio.ensure_future(driver())]
    try:
        await asyncio.wait_for(asyncio.gather(*tasks),
                               seconds + DRAIN_LIMIT_S)
    except asyncio.TimeoutError:
        pass
    return t0, due, submitted, times, tokens, clock()


def _trace_file(trace_dir: str) -> str:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return str(found[-1])


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, devices, *, t_start: float,
             conf: Optional[dict] = None, mix: Optional[dict] = None,
             control: bool = False, fault: Optional[Callable] = None,
             keep_trace: Optional[str] = None, limits: Optional[dict] = None,
             log=None) -> dict:
    """One run of `cell`; returns the result object (printed by main).

    conf/mix override the cell's files (tests run a smoke-size copy);
    control=True also judges the control, the reference in float8 put in
    the program's place, by the same limits (result["control"]);
    fault(engine) may break the timed path (the tests of the check do);
    keep_trace names a directory to keep the trace file in, with a
    description of it; limits replace the cell's (the tests hold a smoke
    size to its own)."""
    import jax

    from repro.models import api
    from repro.serve import ServingEngine
    from repro.serve.frontend import AsyncServingFrontend

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))

    def memory(stage):
        st = [d.memory_stats() or {} for d in devices]
        if all("bytes_in_use" in s for s in st):
            log(f"memory after {stage}: in use "
                f"{max(s['bytes_in_use'] for s in st)}, peak "
                f"{max(s.get('peak_bytes_in_use', 0) for s in st)}")
    clock = time.perf_counter
    conf = conf or load_config(cell["config"])
    mix = mix or traffic.load(cell["traffic"])
    cfg = model_config(conf)
    chips = len(devices)
    if chips != 1:
        raise ValueError(f"a cell runs on one chip, got {chips} devices")
    t = clock()
    params = weights.build(api.packed_param_specs(cfg), conf["init"], seed)
    log(f"weights: {api.weight_bytes(params)} bytes from seed {seed} in "
        f"{clock() - t:.2f} s")
    memory("weights")
    items = traffic.generate(mix, seed, seconds, cfg.vocab_size)
    eng = mix["engine"]
    engine = ServingEngine(
        cfg, params, eng["batch_slots"], eng["max_seq"], greedy=True,
        prefill_buckets=tuple(eng["prefill_buckets"]),
        prefill_chunks_per_step=eng["prefill_chunks_per_step"])
    if fault is not None:
        fault(engine)
    memory("engine")
    t = clock()
    warm_up(engine, cfg.vocab_size, eng["prefill_buckets"])
    log(f"warm-up: {clock() - t:.2f} s")
    memory("warm-up")
    frontend = AsyncServingFrontend(engine)
    steps = StepClock(engine, clock) if trace else None
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **k: compiles.append(clock())
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    counters0 = json.loads(json.dumps(engine.stats))
    if trace:
        jax.profiler.start_trace(trace_dir)
    setup_s = clock() - t_start
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        t0, due, submitted, times, tokens, t_end = asyncio.run(
            _serve(frontend, items, seconds, clock))
    if trace:
        jax.profiler.stop_trace()
    t1 = t0 + seconds
    in_window_compiles = sum(1 for c in compiles if t0 <= c <= t_end)
    memory("serving")
    peak = None
    stats = [d.memory_stats() for d in devices]
    if all(s and "peak_bytes_in_use" in s for s in stats):
        peak = max(int(s["peak_bytes_in_use"]) for s in stats)
    run = Run(conf=conf, mix=mix, cell=cell["name"], chips=chips,
              seconds=seconds, device_kind=devices[0].device_kind,
              items=items, due=due, submitted=submitted, times=times,
              t0=t0, t1=t1, t_end=t_end, setup_s=setup_s,
              counters0=counters0,
              counters1=json.loads(json.dumps(engine.stats)),
              page_size=engine.layout.page_size if engine.paged else 0,
              memory_peak_bytes=peak,
              step_seconds=steps.seconds if steps else None)
    if trace:
        from chipbench import trace as tr
        path = _trace_file(trace_dir)
        pd = tr.load(path)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, keep_trace)
            Path(keep_trace, "describe.json").write_text(
                json.dumps(tr.describe(pd)))
        run.trace = tr.reduce(pd)
        if keep_trace:
            Path(keep_trace, "op_seconds.json").write_text(
                json.dumps(run.trace["op_seconds"]))
        del pd
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # free the program's state before the reference runs
    finished = [it for it in items if len(times[it.rid])
                == it.max_new_tokens]
    served = {it.rid: tokens[it.rid] for it in finished}
    del frontend, engine, steps
    gc.collect()
    window = run.in_window()
    unfinished = sum(1 for it in window if len(times[it.rid])
                     < it.max_new_tokens)
    bad = sum(1 for it in finished
              if len(served[it.rid]) != it.max_new_tokens
              or not all(0 <= x < cfg.vocab_size for x in served[it.rid]))
    t = clock()
    readings = check.readings(conf, params, finished, served, seed, mix,
                              control=control)
    log(f"check: {readings['sampled_requests']} requests, "
        f"{readings['sampled_tokens']} served tokens against the reference "
        f"in {clock() - t:.2f} s; compiles inside the window: "
        f"{in_window_compiles}")
    limits = limits or check.load_limits(cell["name"])
    compared = {
        "max_logit_gap": [readings["max_logit_gap"],
                          limits["max_logit_gap"]],
        "unfinished_requests": [unfinished, 0],
        "malformed_outputs": [bad, 0],
    }
    correct = judge(compared, readings)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(window),
              "failed": unfinished, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    if control:
        # the control is judged as the program is: the same positions, the
        # same limits, every request finished and in range
        ctl = dict(compared, max_logit_gap=[
            readings["control_max_logit_gap"], limits["max_logit_gap"]])
        result["control"] = {
            "correct": judge(ctl, readings),
            "check": {k: {"value": v, "limit": lim}
                      for k, (v, lim) in ctl.items()}}
    result["compiles_in_window"] = in_window_compiles
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    return result


def judge(compared: dict, readings: dict) -> bool:
    """Every number at or under its limit, and some tokens compared."""
    return all(v <= lim for v, lim in compared.values()) \
        and readings["sampled_tokens"] > 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_bench()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"unknown workload {args.workload!r} (have {sorted(cells)})")
    cell = cells[args.workload]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"no TPU: JAX found {devices[0].platform!r} devices; "
                 f"refusing to run")
    if len(devices) < cell["chips"]:
        sys.exit(f"{args.workload} needs {cell['chips']} chips, found "
                 f"{len(devices)}")
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell["chips"]], t_start=T_START)
    for k, v in result["check"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
