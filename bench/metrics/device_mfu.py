"""The whole served path's share of the chip's bf16 peak over the window:
encoder, lookup and engine operations (bench/counts) over window seconds
x peak. It moves the same metric as the lookup kernel's roofline share and
bounds it in work done, whatever implements the lookup: a change that
takes the kernel off the path silences that roofline, not this share."""
from counts import embed, engine, lookup


def read(run):
    if run.peaks is None:
        return None
    m, e = run.cfg["model"], run.cfg["encoder"]
    ops = sum(engine.prefill_ops(m, n) for _, _, n in run.in_window("prefill"))
    ops += sum(engine.decode_ops(m, kv) for _, _, kv in run.in_window("decode"))
    calls = sum(-(-n // e["batch"]) for _, _, n in run.in_window("embed"))
    ops += calls * embed.bucket_ops(e)
    w = run.window
    dim = e["hidden_size"]
    plane = lookup.plane_of(run.cfg["cache"])
    for rec in run.records:
        if w.t_open <= rec.t < w.t_close:
            ops += lookup.least(len(rec.queries), run.corpus_rows
                                + rec.n_spill, dim, plane, rec.rescored)[0]
    if not ops:
        return None
    return 100.0 * ops / (run.seconds * run.peaks["bf16_flops_per_s"])
