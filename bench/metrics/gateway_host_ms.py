"""Host time per submitted batch spent in the gateway itself: each
submit's wall time minus the embed, lookup, prefill and decode spans that
ran inside it, summed over the window's batches, over the batches."""


def read(run):
    subs = run.in_window("submit")
    if not subs:
        return None
    kids = []
    for name in ("embed", "lookup", "prefill", "decode"):
        kids += [(a, b) for a, b, _ in run.spans.select(name)]
    kids.sort()
    import bisect
    starts = [a for a, _ in kids]
    total = 0.0
    for a, b, _ in subs:
        i = bisect.bisect_left(starts, a)
        inner = 0.0
        while i < len(kids) and kids[i][0] < b:
            inner += kids[i][1] - kids[i][0]
            i += 1
        total += (b - a) - inner
    return total / len(subs) * 1e3
