"""95th percentile, over engine-served requests due in the window, of
(t_done - t_first) / (tokens - 1); unfinished requests count as
infinite."""
from harness.report import percentile


def read(run):
    tpot = run.latencies()[1]
    return percentile(tpot, 95) * 1e3 if tpot else None
