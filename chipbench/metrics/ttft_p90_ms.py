"""90th percentile, over every request due in the window, of its first
token's arrival less the moment it was due.  A request with no first
token ranks above every measured one."""
import math

from chipbench.stats import percentile


def read(run):
    ttft = [(run.times[it.rid][0] - run.due[it.rid]) * 1e3
            if run.times[it.rid] else math.inf for it in run.in_window()]
    if not ttft:
        return None
    return percentile(ttft, 90)
