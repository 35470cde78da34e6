"""Process start to window open: JAX start-up, weights, corpus, warm-up
(and compiles, on a checkout's first run)."""


def read(run):
    return run.setup_s
