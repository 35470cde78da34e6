"""Host time per lookup in the traced part: each program span ``lookup``
(around handle_batch in ServingGateway.submit) less the ``*.wait`` spans
below it (the device_get of the lookup's outputs), averaged. The program
records its spans while the profiler records; None where it has none."""


def read(run):
    try:
        from repro import trace
    except ImportError:
        return None
    if run.window.trace_span is None:
        return None
    own = trace.host_times(trace.spans(), "lookup", *run.window.trace_span)
    return sum(own) / len(own) * 1e3 if own else None
