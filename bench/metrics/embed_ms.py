"""Host time per encoder call (embed_fn, which ends in np.asarray), over
the batches submitted in the window."""
import numpy as np


def read(run):
    s = run.in_window("embed")
    return float(np.mean([b - a for a, b, _ in s])) * 1e3 if s else None
