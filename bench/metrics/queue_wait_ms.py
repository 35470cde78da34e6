"""Mean wait in the engine's queue, over the engine-served requests due in
the window: Request.t_admit (stamped by the scheduler when it pops a
queued request into a slot, before its prefill) less t_submit. None where
the program stamps no t_admit."""


def read(run):
    waits = [s.req.t_admit - s.req.t_submit for s in run.window.sent
             if s.req is not None and s.req.served_by == "engine"
             and getattr(s.req, "t_admit", 0.0) > 0]
    return sum(waits) / len(waits) * 1e3 if waits else None
