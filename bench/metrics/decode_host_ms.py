"""Host time per decode step in the traced part: each program span
``engine.decode`` (ModelEngine.decode_active) less the ``*.wait`` spans
below it (the wait for the step's tokens), averaged. The program records
its spans while the profiler records; None where it has none."""


def read(run):
    try:
        from repro import trace
    except ImportError:
        return None
    if run.window.trace_span is None:
        return None
    own = trace.host_times(trace.spans(), "engine.decode",
                           *run.window.trace_span)
    return sum(own) / len(own) * 1e3 if own else None
