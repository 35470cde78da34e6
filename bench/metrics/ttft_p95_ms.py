"""95th percentile of the time from due to first token, over every request
due in the window; unfinished requests count as infinite."""
from harness.report import percentile


def read(run):
    return percentile(run.latencies()[0], 95) * 1e3
