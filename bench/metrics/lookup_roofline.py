"""The lookup's share of its roofline in the traced part of the window:
the least time the work of an exact top-1 needs (bench/counts/lookup.py:
keys of every valid row, or up to the tile where the last query of an
all-hit batch first clears theta_R; rescored rows once more), at the
chip's peaks, over the device time of all ops that ran inside the lookup
spans, whatever implements them, once the device is on the spans' clock
(trace_reduce.clock_offset)."""
import trace_reduce
from counts import lookup


def read(run):
    if run.trace_window is None or run.peaks is None or run.reduced is None:
        return None
    lo, hi = run.trace_window
    dev_ns, n_spans = trace_reduce.device_time_in(
        run.trace, "bench.lookup", lo, hi, run.reduced["clock_offset_ns"])
    if not n_spans or dev_ns <= 0:
        return None
    t0, t1 = run.trace_host
    plane = lookup.plane_of(run.cfg["cache"])
    dim = run.cfg["encoder"]["hidden_size"]
    least = 0.0
    for rec in run.records:
        if not t0 <= rec.t < t1:
            continue
        valid = run.corpus_rows + rec.n_spill
        first = run.extra.get("first_clear", {}).get(id(rec))
        rows = lookup.rows_read(valid, first)
        resc = rec.rescored
        ops, nbytes = lookup.least(len(rec.queries), rows, dim, plane, resc)
        least += lookup.least_seconds(ops, nbytes, run.peaks)
    return 100.0 * least / (dev_ns * 1e-9)
