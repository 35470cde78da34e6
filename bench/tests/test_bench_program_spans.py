"""The readers of the program's own spans and counters, on a hand-built run:
the program's spans recorded on a clock the test sets, a small trace whose
host clock sits a known offset from it and whose device reads early, and a
program without them (the parent of the change), for which every reader
finds nothing."""
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import bench_testlib  # noqa: F401  (import paths)
from harness.report import RunData, load_reader
from harness.serve import LookupRecord, Sent, Spans, Window

from repro import trace
from repro.serving.scheduler import Request

NEW = ("queue_wait_ms", "lookup_host_ms", "prefill_host_ms",
       "decode_host_ms", "lookup_scan_share", "host_idle_share")
OFF_NS = 5e9 + 123.0         # trace host clock - perf_counter, in ns
SHIFT_NS = 1.7e6             # the device reads 1.7 ms early
T0 = 100.0                   # the traced part: [T0, T0 + 10 ms)
MS = 1e-3


@pytest.fixture(autouse=True)
def empty_ring():
    yield
    trace.clear()


class _Clock:
    def __init__(self, times):
        self.times = iter(times)

    def __call__(self):
        return next(self.times)


def _record_program_spans(monkeypatch):
    """lookup [2, 3] ms with its wait [2.2, 3]; prefill [3.5, 4.5] with its
    wait [4, 4.5]; decode [5, 9] with its wait [6, 9]; and a lookup long
    before the traced part."""
    t = [T0 + x * MS for x in (2, 2.1, 2.2, 3, 3, 3, 3.5, 4, 4.5, 4.5,
                               5, 6, 9, 9)]
    monkeypatch.setattr(trace, "clock", _Clock([99.0, 99.5] + t))
    trace.clear()
    with trace.recording():
        with trace.span("lookup"):
            pass
        with trace.span("lookup"):
            with trace.span("lookup.scan"):
                with trace.span("lookup.wait"):
                    pass
        with trace.span("engine.prefill"):
            with trace.span("engine.prefill.wait"):
                pass
        with trace.span("engine.decode"):
            with trace.span("engine.decode.wait"):
                pass


def _request(rid, served_by, t_submit, t_admit=0.0):
    r = Request(rid=rid, tokens=np.zeros(4, np.int32))
    r.served_by, r.t_submit, r.t_admit = served_by, t_submit, t_admit
    return r


def _lookup_record(t, tiles):
    res = SimpleNamespace(hit=np.ones(1, bool), tiles=tiles)
    return LookupRecord(t, [0], [], np.zeros((1, 4), np.float32), 0.9, res,
                        0)


def _run():
    """Benchmark spans submit [1, 4.6], lookup [2, 3], decode [5, 9] ms on
    perf_counter; their annotations OFF_NS later on the trace's clock,
    within a few hundred ns; device ops at [2.3, 2.9] and [6.2, 8.0] ms of
    the traced part on the spans' clock, read SHIFT_NS early."""
    spans = Spans()
    for name, a, b in (("submit", 1, 4.6), ("lookup", 2, 3),
                       ("decode", 5, 9)):
        spans.add(name, T0 + a * MS, T0 + b * MS)
    ns = lambda s: s * 1e9 + OFF_NS                    # noqa: E731
    lo, hi = ns(T0), ns(T0 + 10 * MS)
    dev = lambda a, b: [lo + a * 1e6 - SHIFT_NS,            # noqa: E731
                        lo + b * 1e6 - SHIFT_NS]
    tr = {"spans": [["bench.traced", lo, hi],
                    ["bench.submit", ns(T0 + MS) - 100, ns(T0 + 4.6 * MS)],
                    ["bench.lookup", ns(T0 + 2 * MS), ns(T0 + 3 * MS)],
                    ["bench.decode", ns(T0 + 5 * MS) + 200,
                     ns(T0 + 9 * MS)]],
          "devices": {"/device:TPU:0": [["%cosine_topk.1", *dev(2.3, 2.9)],
                                        ["%while.14", *dev(6.2, 8.0)]]}}
    reqs = [_request(0, "engine", 1.0, 1.004),
            _request(1, "engine", 2.0, 2.010), _request(2, "cache", 2.0),
            None]
    sent = [Sent(i, i, 0, 0, None, 4, req=r) for i, r in enumerate(reqs)]
    win = Window(T0 - 40, T0 + 10 * MS, T0 + 1, sent, 0, 0,
                 trace_span=(T0, T0 + 10 * MS))
    records = [_lookup_record(T0 - 30, (3, 16)),
               _lookup_record(T0, (16, 16)),
               _lookup_record(T0 + 5, (1, 16))]      # after the close
    return RunData({}, {}, win, spans, records, 1000, 40.0, 1.0,
                   "TPU v5 lite", trace=tr, trace_window=(lo, hi),
                   trace_host=(T0, T0 + 10 * MS),
                   reduced={"clock_offset_ns": SHIFT_NS})


def test_program_span_readers_by_hand(monkeypatch):
    _record_program_spans(monkeypatch)
    run = _run()
    got = {n: load_reader(n)(run) for n in NEW}
    assert got["queue_wait_ms"] == pytest.approx(7.0)      # (4 + 10) / 2
    assert got["lookup_host_ms"] == pytest.approx(0.2)     # 1 - 0.8
    assert got["prefill_host_ms"] == pytest.approx(0.5)    # 1 - 0.5
    assert got["decode_host_ms"] == pytest.approx(1.0)     # 4 - 3
    assert got["lookup_scan_share"] == pytest.approx(100 * 19 / 32)
    # covered: the lookup's wait [2.2, 3] holds its op [2.3, 2.9], the
    # prefill's wait [4, 4.5] holds none, the decode's wait [6, 9] holds
    # its op [6.2, 8]: 4.3 of 10 ms
    assert got["host_idle_share"] == pytest.approx(57.0, abs=1e-3)


def test_the_clock_offset_is_the_median_over_the_benchmark_spans():
    off = load_reader("host_idle_share").__globals__["clock_offset_ns"]
    assert off(_run()) == pytest.approx(OFF_NS, abs=1.0)


def test_a_program_without_spans_or_counters_reads_nothing(monkeypatch):
    """The parent of the change: no repro.trace, no t_admit, no tile
    count. Every new reader returns None and none raises."""
    import repro
    monkeypatch.delattr(repro, "trace")
    monkeypatch.setitem(sys.modules, "repro.trace", None)
    run = _run()
    for s in run.window.sent:
        if s.req is not None:
            s.req = SimpleNamespace(served_by=s.req.served_by,
                                    t_submit=s.req.t_submit)
    for rec in run.records:
        rec.res = SimpleNamespace(hit=rec.res.hit)
    assert {n: load_reader(n)(run) for n in NEW} == dict.fromkeys(NEW)


def test_an_untraced_run_reads_no_program_span(monkeypatch):
    _record_program_spans(monkeypatch)
    run = _run()
    run.window.trace_span = None
    run.trace = run.trace_window = run.trace_host = run.reduced = None
    for n in ("lookup_host_ms", "prefill_host_ms", "decode_host_ms",
              "host_idle_share"):
        assert load_reader(n)(run) is None
