"""The part of the traced window in which the first chip runs no op and no
program ``*.wait`` span is open: the device idle while the host does its
own work, not while it waits for the device.

The program's spans are on time.perf_counter; they are moved onto the
trace's host clock by the median offset between the benchmark's spans and
their annotations in the trace (matched by name and order), then onto the
device's clock by the shift the reduction found (clock_offset_ns)."""
import statistics


def clock_offset_ns(run):
    """trace ns - perf_counter ns, the median over the benchmark's spans in
    the traced part; None where no name matches one for one."""
    t0, t1 = run.window.trace_span
    lo, hi = run.trace_window
    host, ann = {}, {}
    for name, a in zip(run.spans.name, run.spans.t0):
        if t0 <= a < t1:
            host.setdefault("bench." + name, []).append(a * 1e9)
    for name, a, _ in run.trace["spans"]:
        if name != "bench.traced" and lo <= a < hi:
            ann.setdefault(name, []).append(a)
    d = []
    for name, xs in host.items():
        ys = ann.get(name, [])
        if len(ys) == len(xs):
            d += [y - x for x, y in zip(sorted(xs), sorted(ys))]
    return statistics.median(d) if d else None


def read(run):
    try:
        from repro import trace
    except ImportError:
        return None
    if run.reduced is None or run.trace_window is None:
        return None
    off = clock_offset_ns(run)
    waits = [s for s in trace.spans() if s.name.endswith(trace.WAIT)]
    if off is None or not waits:
        return None
    lo, hi = run.trace_window
    shift = run.reduced["clock_offset_ns"]
    dlo, dhi = lo - shift, hi - shift       # the window on the device clock
    devs = run.trace["devices"]
    busy = [(a, b) for _, a, b in devs[sorted(devs)[0]]]
    busy += [(s.t0 * 1e9 + off - shift, s.t1 * 1e9 + off - shift)
             for s in waits]
    covered, end = 0.0, dlo
    for a, b in sorted(busy):
        a, b = max(a, end), min(b, dhi)
        if b > a:
            covered += b - a
            end = b
    return 100.0 * (1.0 - covered / (dhi - dlo))
