"""Least time of the fused decode head and sampler (chipbench/flops.py)
over the device time of the `decode_sample` kernel in the traced serving
period, in %: the head's P(16,2) codes read once a decode step (the
engine's decode_steps counter), two operations per weight per sampled
row."""
from chipbench import flops, peaks, trace


def read(run):
    if run.trace is None:
        return None
    busy = trace.kernel_seconds(run.trace, "decode_sample")
    steps = run.counters1["decode_steps"] - run.counters0["decode_steps"]
    rows = sum(max(len(run.times[it.rid]) - 1, 0) for it in run.items)
    if not busy or not steps:
        return None
    pk = peaks.peaks(run.device_kind)
    ops, nbytes = flops.head_work(run.conf, steps, rows)
    least = max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes"])
    return 100.0 * least / (busy * run.chips)
