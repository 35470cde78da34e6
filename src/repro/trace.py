"""Spans on the served path, on the profiler's clock (DESIGN.md §18).

A span marks one piece of work where it happens (the gateway's submit, the
encoder call, the lookup, a prefill, a decode step) and, inside it, a
``*.wait`` span around the one call that blocks on the device's result, so
that a span's own host time is its duration less its waits.

Recording is on while a JAX profiler session records
(``jax.profiler.TraceAnnotation.is_enabled()``) or inside
``with recording():``; there is no other switch. While it is on, a span
enters a ``jax.profiler.TraceAnnotation`` of its name, so it lies in the
profiler's trace beside the device's ops, and on exit appends a ``Span`` to
a ring of ``RING`` records (the oldest overwritten). While it is off, a
span records nothing and costs one ``is_enabled()`` call. Nothing under
``jax.jit`` depends on the switch, so turning it on compiles nothing.

    with trace.span("lookup", key=rid, n=len(batch)):
        ...
        with trace.span("lookup.wait"):
            out = jax.device_get(x)

    with trace.span("engine.decode") as sp:
        ...
        sp.note(routed=counts)      # a count known only inside the span

    with trace.recording():
        serve()
    for s in trace.spans(): ...
"""
from __future__ import annotations

import itertools
import math
import threading
import time
from contextlib import contextmanager
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

RING = 1 << 16
WAIT = ".wait"
clock = time.perf_counter

_is_enabled = TraceAnnotation.is_enabled


class Span(NamedTuple):
    seq: int            # order of entry; the ring slot is seq % RING
    name: str
    t0: float           # time.perf_counter at entry
    t1: float           # ... and at exit
    parent: int         # seq of the enclosing span on this thread, or -1
    key: int            # shared by the spans of one batch or request
    counts: dict        # small payload of counts


_ring: list = [None] * RING
_seq = itertools.count()
_local = threading.local()
_forced = 0


def enabled() -> bool:
    """Whether spans record now."""
    return _forced > 0 or _is_enabled()


@contextmanager
def recording():
    """Record spans inside this block, profiler or not."""
    global _forced
    _forced += 1
    try:
        yield
    finally:
        _forced -= 1


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def span(name: str, key: Optional[int] = None, clocked: bool = False,
         **counts):
    """One span (see the module's docstring), for a ``with`` block.
    ``key`` defaults to the enclosing span's key (-1 at the top). With
    ``clocked`` the clock is read at entry and exit whether or not
    recording is on, so a caller can keep its own timer on the same pair
    of readings (the span's ``t0`` and ``t1``)."""
    if _forced > 0 or _is_enabled():
        return _Recording(name, key, counts)
    return _Clocked() if clocked else _OFF


class _Off:
    """A span while recording is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **counts):
        """Add counts to the span's payload (records nothing here)."""


_OFF = _Off()


class _Clocked:
    """A clocked span while recording is off."""
    __slots__ = ("t0", "t1")

    def __enter__(self):
        self.t0 = clock()
        return self

    def __exit__(self, *exc):
        self.t1 = clock()
        return False

    note = _Off.note


class _Recording:
    """A span while recording is on."""
    __slots__ = ("name", "key", "counts", "seq", "parent", "t0", "t1",
                 "_ann")

    def __init__(self, name: str, key: Optional[int], counts: dict):
        self.name, self.key, self.counts = name, key, counts

    def __enter__(self):
        st = _stack()
        self.parent, key = st[-1] if st else (-1, -1)
        if self.key is None:
            self.key = key
        self.seq = seq = next(_seq)
        _ring[seq % RING] = None
        st.append((seq, self.key))
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, *exc):
        self.t1 = t1 = clock()
        self._ann.__exit__(*exc)
        _stack().pop()
        _ring[self.seq % RING] = Span(self.seq, self.name, self.t0, t1,
                                      self.parent, int(self.key),
                                      self.counts)
        return False

    def note(self, **counts):
        """Add counts to the span's payload."""
        self.counts.update(counts)


@contextmanager
def keyed(key: int):
    """Give the spans opened inside this block ``key`` (a request's id
    around work the callee does not know it for), recording nothing."""
    if not enabled():
        yield
        return
    st = _stack()
    parent = st[-1][0] if st else -1
    st.append((parent, int(key)))
    try:
        yield
    finally:
        st.pop()


def spans() -> list:
    """The recorded spans still in the ring, in order of entry."""
    return sorted((s for s in list(_ring) if s is not None),
                  key=lambda s: s.seq)


def clear() -> None:
    _ring[:] = [None] * RING


def host_times(records: list, name: str, lo: float = -math.inf,
               hi: float = math.inf) -> list:
    """For each span ``name`` that starts in [lo, hi): its duration less
    the ``*.wait`` spans below it (a wait inside another wait counts once),
    in seconds. ``records`` is what ``spans()`` returned."""
    by_seq = {s.seq: s for s in records}
    own = {s.seq: s.t1 - s.t0 for s in records
           if s.name == name and lo <= s.t0 < hi}
    for w in records:
        if not w.name.endswith(WAIT):
            continue
        p = by_seq.get(w.parent)
        while p is not None and p.name != name \
                and not p.name.endswith(WAIT):
            p = by_seq.get(p.parent)
        if p is not None and p.seq in own:
            own[p.seq] -= w.t1 - w.t0
    return list(own.values())
