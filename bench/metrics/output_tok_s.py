"""Engine tokens generated inside the window over the window's seconds."""


def read(run):
    w = run.window
    return (w.tokens_close - w.tokens_open) / (w.t_close - w.t_open)
