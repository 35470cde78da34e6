"""The engine's share of the chip's bf16 peak over the window: the
operations of the prefill and decode tokens generated in the window (from
the configuration by bench/counts/engine.py) over window seconds x peak."""
from counts import engine


def read(run):
    if run.peaks is None:
        return None
    m = run.cfg["model"]
    ops = sum(engine.prefill_ops(m, n) for _, _, n in run.in_window("prefill"))
    ops += sum(engine.decode_ops(m, kv) for _, _, kv in run.in_window("decode"))
    if not ops:
        return None
    return 100.0 * ops / (run.seconds * run.peaks["bf16_flops_per_s"])
