"""1 - (union of device op intervals / the traced window), from the
profiler's trace, averaged over the chips."""


def read(run):
    r = run.reduced
    return None if r is None or r["idle_share"] is None \
        else 100.0 * r["idle_share"]
