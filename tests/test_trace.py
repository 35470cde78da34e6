"""The tracer: spans nest with their parents and keys, the ring is bounded,
nothing records while recording is off, and while a profiler session
records the spans are annotations in its trace."""
import time

import numpy as np
import pytest

from repro import trace


@pytest.fixture(autouse=True)
def empty_ring():
    trace.clear()
    yield
    trace.clear()


class _Clock:
    """A clock that reads the times it is given, in order."""

    def __init__(self, times):
        self.times = iter(times)

    def __call__(self):
        return next(self.times)


def test_spans_nest_with_parents_and_share_a_key():
    with trace.recording():
        with trace.span("gateway.submit", key=7, n=2) as top:
            with trace.span("embed"):
                with trace.span("embed.wait"):
                    pass
            with trace.span("lookup"):
                pass
            with trace.span("sched.step", key=3):
                with trace.keyed(11):
                    with trace.span("engine.prefill"):
                        pass
    s = {r.name: r for r in trace.spans()}
    assert [r.name for r in trace.spans()] == [
        "gateway.submit", "embed", "embed.wait", "lookup", "sched.step",
        "engine.prefill"]
    assert s["gateway.submit"].parent == -1
    assert s["embed"].parent == s["lookup"].parent == top.seq
    assert s["embed.wait"].parent == s["embed"].seq
    assert s["sched.step"].parent == top.seq
    assert s["engine.prefill"].parent == s["sched.step"].seq
    assert {s[n].key for n in ("gateway.submit", "embed", "embed.wait",
                               "lookup")} == {7}
    assert s["sched.step"].key == 3 and s["engine.prefill"].key == 11
    assert s["gateway.submit"].counts == {"n": 2}
    for r in s.values():
        assert r.t0 <= r.t1
    assert s["embed.wait"].t0 >= s["embed"].t0
    assert s["embed.wait"].t1 <= s["embed"].t1


def test_the_ring_keeps_the_newest_records():
    with trace.recording():
        for i in range(trace.RING + 10):
            with trace.span("x", key=i):
                pass
    got = trace.spans()
    assert len(got) == trace.RING
    assert [r.key for r in got[:2]] == [10, 11]
    assert got[-1].key == trace.RING + 9


def test_nothing_records_while_off():
    assert not trace.enabled()
    with trace.span("lookup", n=1):
        with trace.span("lookup.wait"):
            pass
    with trace.keyed(5):
        with trace.span("engine.prefill"):
            pass
    assert trace.spans() == []


def test_recording_records_and_ends():
    with trace.recording():
        assert trace.enabled()
        with trace.span("a"):
            pass
    assert not trace.enabled()
    with trace.span("b"):
        pass
    assert [r.name for r in trace.spans()] == ["a"]


def test_a_clocked_span_reads_the_clock_while_off(monkeypatch):
    monkeypatch.setattr(trace, "clock", _Clock([2.0, 5.0]))
    with trace.span("lookup", clocked=True) as sp:
        pass
    assert (sp.t0, sp.t1) == (2.0, 5.0)
    assert trace.spans() == []


def test_host_times_take_the_waits_out(monkeypatch):
    # lookup [0, 10] holding lookup.scan [1, 9] holding lookup.wait [5, 8]
    # (and a wait inside that wait, counted once); a second lookup [20, 24]
    # with no wait; one starting at 30, outside [0, 30)
    monkeypatch.setattr(trace, "clock", _Clock(
        [0, 1, 5, 6, 7, 8, 9, 10, 20, 24, 30, 31]))
    with trace.recording():
        with trace.span("lookup"):
            with trace.span("lookup.scan"):
                with trace.span("lookup.wait"):
                    with trace.span("lookup.rescore.wait"):
                        pass
        with trace.span("lookup"):
            pass
        with trace.span("lookup"):
            pass
    assert trace.host_times(trace.spans(), "lookup", 0, 30) == [7, 4]
    assert trace.host_times(trace.spans(), "lookup.scan") == [5]


def test_a_profiler_session_records_the_spans_as_annotations(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    assert not trace.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.enabled()
        with trace.span("lookup", key=1):
            x = jnp.arange(8.0) * 2
            with trace.span("lookup.wait"):
                np.asarray(x)
    finally:
        jax.profiler.stop_trace()
    assert not trace.enabled()
    assert [r.name for r in trace.spans()] == ["lookup", "lookup.wait"]
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    names = {e.name for p in ProfileData.from_file(str(files[-1])).planes
             if p.name.startswith("/host:") for ln in p.lines
             for e in ln.events}
    assert {"lookup", "lookup.wait"} <= names


def test_an_off_span_costs_under_a_microsecond():
    def run(n=100_000):
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("lookup"):
                pass
        return time.perf_counter() - t0

    assert min(run() for _ in range(3)) < 0.1
    assert trace.spans() == []
