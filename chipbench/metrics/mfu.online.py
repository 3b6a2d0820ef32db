"""Model FLOPs of every request due in the window (chipbench/flops.py:
its prompt past the shared prefix, then its decoded tokens) over the time
spent inside engine.step from the window's start until they finished,
times the chips, times the chip's bfloat16 peak, in %."""
from chipbench import flops, peaks


def read(run):
    if not run.step_seconds:
        return None
    items = run.in_window()
    total = sum(flops.request_flops(run.conf, len(it.prompt),
                                    it.max_new_tokens) for it in items)
    admissions = (run.counters1["shared_admissions"]
                  - run.counters0["shared_admissions"])
    pages = run.counters1["pages_shared"] - run.counters0["pages_shared"]
    if admissions:
        # what the prompt's shared pages spared: the mean shared prefix
        shared = pages * run.page_size // admissions
        spared = (flops.request_flops(run.conf, shared + 1, 1)
                  - flops.request_flops(run.conf, shared + 1, 1, shared))
        total -= admissions * spared
    peak = peaks.peaks(run.device_kind)["bf16_flops"]
    return 100.0 * total / (run.step_seconds * run.chips * peak)
