"""Cache hits over lookups in the window, from the cache's counters."""


def read(run):
    o, c = run.window.counters_open, run.window.counters_close
    hits = c["hits"] - o["hits"]
    n = hits + c["misses"] - o["misses"]
    return 100.0 * hits / n if n else None
