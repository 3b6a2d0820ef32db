"""Least time of paged decode attention (chipbench/flops.py) over the
device time of the `paged_attention` kernel in the traced serving period,
in %.  The least time counts, for every decoded token of the requests due
in the window, the stored keys and values its context holds, read once:
attention at decode is bound by HBM bytes."""
from chipbench import flops, peaks, trace


def read(run):
    if run.trace is None:
        return None
    busy = trace.kernel_seconds(run.trace, "paged_attention")
    contexts = [len(it.prompt) + k for it in run.in_window()
                for k in range(1, len(run.times[it.rid]))]
    if not busy or not contexts:
        return None
    pk = peaks.peaks(run.device_kind)
    ops, nbytes = flops.decode_attention_work(run.conf, contexts)
    least = max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes"])
    return 100.0 * least / (busy * run.chips)
