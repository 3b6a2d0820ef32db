"""Least time of the fused prefill attention (chipbench/flops.py) over the
device time of the `prefill_attention_paged` kernel in the traced serving
period, in %.  Every prompt of the requests due in the window is chunked
as the engine chunks it; a prompt that opens with a system prompt is
spared the share of it that the engine's pages_shared counter says was
served from shared pages."""
from chipbench import flops, peaks, trace


def read(run):
    if run.trace is None:
        return None
    busy = trace.kernel_seconds(run.trace, "prefill_attention_paged")
    items = run.in_window()
    if not busy or not items:
        return None
    sp = run.mix.get("shared_prefix")
    with_prefix = [it for it in items if it.prefix_id >= 0]
    frac = 0.0
    if sp and with_prefix:
        pages = run.counters1["pages_shared"] - run.counters0["pages_shared"]
        frac = min(pages * run.page_size
                   / (len(with_prefix) * sp["length"]), 1.0)
    pk = peaks.peaks(run.device_kind)
    buckets = run.mix["engine"]["prefill_buckets"]
    least = sum(flops.prefill_attention_least_seconds(
        run.conf, len(it.prompt), buckets,
        frac * sp["length"] if it.prefix_id >= 0 else 0.0, pk)
        for it in items)
    return 100.0 * least / (busy * run.chips)
