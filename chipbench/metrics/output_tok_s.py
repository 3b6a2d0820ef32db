"""Output tokens delivered inside the window, over the window's seconds."""


def read(run):
    n = sum(1 for ts in run.times.values() for t in ts
            if run.t0 <= t < run.t1)
    return n / run.seconds
