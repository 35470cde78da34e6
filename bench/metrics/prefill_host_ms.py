"""Host time per prefill in the traced part: each program span
``engine.prefill`` (ModelEngine.prefill_into) less the ``*.wait`` spans
below it (the wait for the first token), averaged. The program records its
spans while the profiler records; None where it has none."""


def read(run):
    try:
        from repro import trace
    except ImportError:
        return None
    if run.window.trace_span is None:
        return None
    own = trace.host_times(trace.spans(), "engine.prefill",
                           *run.window.trace_span)
    return sum(own) / len(own) * 1e3 if own else None
