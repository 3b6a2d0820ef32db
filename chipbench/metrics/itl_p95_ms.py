"""95th percentile over every gap between consecutive output tokens of one
request, the later token delivered inside the window."""
from chipbench.stats import percentile


def read(run):
    gaps = [(b - a) * 1e3 for ts in run.times.values()
            for a, b in zip(ts, ts[1:]) if run.t0 <= b < run.t1]
    return percentile(gaps, 95) if gaps else None
