"""The decode step's share of its roofline in the traced part of the
window: for each decode step, the least time of its work at the chip's
peaks (bench/counts/moe.py: the weights outside the routed experts, the
held experts that the step's routing counter shows received a token, the
latent-cache rows of its kv lengths), summed, over the device time of all
ops that ran inside the decode spans, once the device is on the spans'
clock (trace_reduce.clock_offset). The routing counter is the program's,
noted on its ``engine.decode`` span while spans record; None where the
program notes none."""
import trace_reduce
from counts import moe
from counts.lookup import least_seconds


def read(run):
    try:
        from repro import trace
    except ImportError:
        return None
    if run.trace_window is None or run.peaks is None or run.reduced is None:
        return None
    t0, t1 = run.trace_host
    routed = [(s.t0, s.counts["routed"]) for s in trace.spans()
              if s.name == "engine.decode" and "routed" in s.counts]
    least = 0.0
    for a, b, kv in run.spans.select("decode", t0, t1):
        # the program's span of a step lies inside the benchmark's
        r = [c for t, c in routed if a <= t <= b]
        if len(r) != 1:
            return None
        least += least_seconds(*moe.decode_least(run.cfg["model"], kv, r[0]),
                               run.peaks)
    lo, hi = run.trace_window
    dev_ns, n_spans = trace_reduce.device_time_in(
        run.trace, "bench.decode", lo, hi, run.reduced["clock_offset_ns"])
    if not n_spans or dev_ns <= 0 or not least:
        return None
    return 100.0 * least / (dev_ns * 1e-9)
