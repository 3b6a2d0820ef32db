"""95th percentile of how late the load generator submitted a request
after it was due (requests due in the window)."""
from chipbench.stats import percentile


def read(run):
    late = [(run.submitted[it.rid] - run.due[it.rid]) * 1e3
            for it in run.in_window() if it.rid in run.submitted]
    return percentile(late, 95) if late else None
