"""Host time per engine decode step (decode_active, which ends in
np.asarray), over the steps in the window."""
import numpy as np


def read(run):
    s = run.in_window("decode")
    return float(np.mean([b - a for a, b, _ in s])) * 1e3 if s else None
