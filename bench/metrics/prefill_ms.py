"""Host time per engine prefill call (prefill_into, which ends in
int(argmax)), over the calls in the window."""
import numpy as np


def read(run):
    s = run.in_window("prefill")
    return float(np.mean([b - a for a, b, _ in s])) * 1e3 if s else None
