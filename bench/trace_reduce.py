"""From the profiler's trace to busy time, per-op device time and idle gaps
labelled by the host span that was open during each gap.

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists; ``reduce(...)`` works on those lists only, so it can be
checked on a small recorded trace (``bench/tests/data/small_trace.json``).

Device events are the ops on each TPU's ``XLA Ops`` line, named by their
HLO instruction (``%cosine_topk.1``; the rest of the HLO text is dropped).
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s
(names starting with ``bench.``). The profiler puts both on one clock only
roughly: on a v5e the device's ops read some milliseconds early against
the host's spans, so an op is set against the spans only after
``clock_offset`` has moved the device onto the spans' clock.
"""
from __future__ import annotations

import bisect

import numpy as np

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def load(path) -> dict:
    """{"devices": {plane: [[name, start_ns, end_ns], ...]},
    "spans": [[name, start_ns, end_ns], ...]}"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append([e.name.split(" = ", 1)[0], e.start_ns,
                                e.start_ns + e.duration_ns])
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns,
                                      e.start_ns + e.duration_ns])
    return {"devices": devices, "spans": spans}


def _clip(evs, lo, hi):
    out = []
    for name, a, b in evs:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def window_of(trace: dict, name: str = SPAN_PREFIX + "traced"):
    """(start_ns, end_ns) of the span that marks the traced window."""
    for n, a, b in trace["spans"]:
        if n == name:
            return a, b
    raise ValueError(f"no {name!r} span in the trace")


def busy_intervals(evs, lo, hi):
    return _union([(a, b) for _, a, b in _clip(evs, lo, hi)])


def leaf_spans(trace: dict, lo: float, hi: float) -> list:
    """The benchmark's spans inside [lo, hi) that hold no other span (the
    calls into a layer, each of which waits for its device work), sorted
    by start, without the span of the traced window itself."""
    spans = sorted(((a, -b, n) for n, a, b in trace["spans"]
                    if n != SPAN_PREFIX + "traced" and lo <= a < hi))
    out = []
    for i, (a, nb, n) in enumerate(spans):
        nxt = spans[i + 1][0] if i + 1 < len(spans) else None
        if nxt is None or nxt >= -nb:
            out.append((n, a, -nb))
    return out


def clock_offset(trace: dict, lo: float, hi: float, reach_ns: float = 20e6,
                 bin_ns: float = 10e3) -> float:
    """The shift (ns) that puts the first chip's device times on the host
    spans' clock: of the shifts within ``reach_ns``, the one under which the
    most device-busy time falls inside the leaf spans (every such span waits
    for the device work it starts, so under the right shift that work lies
    inside it). Where a range of shifts ties, its middle. 0 when the trace
    holds no leaf span or no device op."""
    leaves = leaf_spans(trace, lo, hi)
    devs = trace["devices"]
    if not leaves or not devs:
        return 0.0
    t0 = lo - 2 * reach_ns
    n = int((hi - lo + 4 * reach_ns) // bin_ns) + 1

    def cover(intervals):
        d = np.zeros(n + 1)
        for a, b in intervals:
            i, j = int((a - t0) // bin_ns), int((b - t0) // bin_ns)
            if j > 0 and i < n:
                d[max(i, 0)] += 1
                d[min(j, n)] -= 1
        return (np.cumsum(d[:n]) > 0).astype(np.float64)

    busy = cover(busy_intervals(devs[sorted(devs)[0]], t0, t0 + n * bin_ns))
    span = cover([(a, b) for _, a, b in leaves])
    size = 1 << int(np.ceil(np.log2(2 * n)))
    # corr[k] = sum_t busy[t] * span[t + k]: busy time inside the spans
    # with the device moved k bins later
    corr = np.fft.irfft(np.fft.rfft(span, size) *
                        np.conj(np.fft.rfft(busy, size)), size)
    k_max = int(reach_ns // bin_ns)
    lags = np.arange(-k_max, k_max + 1)
    f = np.rint(corr[lags % size])
    best = np.flatnonzero(f == f.max())
    # the run of tied shifts that holds the first best one
    run = [best[0]]
    for k in best[1:]:
        if k != run[-1] + 1:
            break
        run.append(k)
    return float(lags[(run[0] + run[-1]) // 2] * bin_ns)


def reduce(trace: dict, lo: float, hi: float, top: int = 10) -> dict:
    """Busy and idle time inside the traced window [lo, hi) (host ns),
    averaged over the chips; the ops that took most device time (summed
    over chips); the idle gaps of the first chip, summed by the innermost
    host span open at each gap's middle (``host idle`` where none was),
    with the device on the spans' clock (``clock_offset``)."""
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    shift = clock_offset(trace, lo, hi)
    dlo, dhi = lo - shift, hi - shift       # the window on the device clock
    window_s = (hi - lo) * 1e-9
    busy, per_op = [], {}
    for plane in sorted(devs):
        evs = _clip(devs[plane], dlo, dhi)
        busy.append(sum(b - a for a, b in _union([(a, b)
                                                  for _, a, b in evs])))
        for name, a, b in evs:
            per_op[name] = per_op.get(name, 0) + (b - a)
    first = sorted(devs)[0]
    merged = busy_intervals(devs[first], dlo, dhi)
    gaps, cur = [], dlo
    for a, b in merged:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if dhi > cur:
        gaps.append((cur, dhi))
    spans = sorted(trace["spans"], key=lambda s: s[1])
    by_label = {}
    for a, b in gaps:
        mid = (a + b) / 2 + shift
        label, width = "host idle", float("inf")
        for n, s0, s1 in spans:
            if s0 > mid:
                break
            if s1 > mid and n != SPAN_PREFIX + "traced" and s1 - s0 < width:
                label, width = n[len(SPAN_PREFIX):], s1 - s0
        by_label[label] = by_label.get(label, 0) + (b - a)
    busy_s = sum(busy) / len(busy) * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": [[n, v * 1e-9] for n, v in ops],
            "idle_gaps": [[n, v * 1e-9] for n, v in idle],
            "n_chips": len(busy), "clock_offset_ns": shift}


def device_time_in(trace: dict, span_name: str, lo: float, hi: float,
                   shift: float = 0.0) -> tuple:
    """Device time (ns, summed over chips) of the ops that start inside a
    span named ``span_name`` that starts in [lo, hi), with device times
    moved by ``shift`` onto the spans' clock; ops nested in one another
    count once (the union of their intervals). Also the count of such
    spans."""
    spans = sorted((a, b) for n, a, b in trace["spans"]
                   if n == span_name and lo <= a < hi)
    if not spans:
        return 0.0, 0
    starts = [a for a, _ in spans]
    total = 0.0
    for evs in trace["devices"].values():
        inside = []
        for _, a, b in evs:
            i = bisect.bisect_right(starts, a + shift) - 1
            if i >= 0 and a + shift < spans[i][1]:
                inside.append((a, b))
        total += sum(b - a for a, b in _union(inside))
    return total, len(spans)
