"""The gateway's own lookup timer (GatewayStats.lookup_s, a host clock
around handle_batch, which ends in jax.device_get): its entries added in
the window, summed, over the batches."""


def read(run):
    o, c = run.window.counters_open, run.window.counters_close
    n = c["lookup_batches"] - o["lookup_batches"]
    if n <= 0 or c["lookup_batches"] >= 4096:
        return None
    return (c["lookup_s_total"] - o["lookup_s_total"]) / n * 1e3
