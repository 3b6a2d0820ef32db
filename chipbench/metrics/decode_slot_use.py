"""Share (%) of the rows of the run's decode steps that carry a live
request: the engine's decode_rows counter over its decode_steps counter
times the mix's batch slots.  A program without the decode_rows counter
reports nothing."""


def read(run):
    c0, c1 = run.counters0, run.counters1
    if "decode_rows" not in c1:
        return None
    steps = c1["decode_steps"] - c0["decode_steps"]
    if not steps:
        return None
    rows = c1["decode_rows"] - c0["decode_rows"]
    return 100.0 * rows / (steps * run.mix["engine"]["batch_slots"])
