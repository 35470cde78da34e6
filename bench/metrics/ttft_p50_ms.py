"""Median time from a request's due time to its first token (a hit's
answer, or a miss's first token after prefill), over every request due in
the window; unfinished requests count as infinite."""
from harness.report import percentile


def read(run):
    return percentile(run.latencies()[0], 50) * 1e3
