"""Mean time (ms) a request waited in the engine's queue, from its
(re)queueing to its admission, over the run: the engine's queue_wait_s
counter over its admissions counter.  A program without these counters
reports nothing."""


def read(run):
    c0, c1 = run.counters0, run.counters1
    if "admissions" not in c1:
        return None
    n = c1["admissions"] - c0["admissions"]
    if not n:
        return None
    return 1e3 * (c1["queue_wait_s"] - c0["queue_wait_s"]) / n
