"""One run of one cell: set-up, warm-up, the measured window, the drain,
the metrics, then (with the program's state freed) the comparison with the
reference that decides ``correct``.

Everything a cell needs is found by name: the workload in
``BENCHMARK.json`` names its configuration (``bench/configs/``) and its
traffic mix (``bench/traffic/``); the cell's own file (``bench/cells/``)
holds its fixed rate, drain limit, check sample sizes and limits; each
metric has a reader in ``bench/metrics/``.
"""
from __future__ import annotations

import gc
import json
import pathlib
import shutil
import time

import numpy as np

from harness import check as C
from harness import traffic as T
from harness.build import build
from harness.report import RunData, load_peaks, read_metrics
from harness.serve import EngineProxy, Spans, gateway_class, serve, warm_up

BENCH = pathlib.Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Compiles:
    """Counts backend compiles while ``on`` (the listener of
    ``chip_smoke.py``); registered once per process."""
    on = False
    names: list = []
    registered = False

    @classmethod
    def listen(cls):
        if cls.registered:
            return
        import jax

        def on_event(event, secs, **kw):
            if cls.on and event == COMPILE_EVENT:
                cls.names.append(kw.get("fun_name"))

        jax.monitoring.register_event_duration_secs_listener(on_event)
        cls.registered = True


def load_cell(name: str, bench: pathlib.Path = BENCH) -> tuple:
    """(workload entry, benchmark, configuration, mix, cell file)."""
    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in spec["workloads"]}[name]
    cfg = json.loads((bench / "configs" / f"{work['config']}.json")
                     .read_text())
    mix = T.load_mix(bench, work["traffic"])
    cell = json.loads((bench / "cells" / f"{name}.json").read_text())
    return work, spec, cfg, mix, cell


def metric_names(spec: dict, workload: str, trace: bool) -> list:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [(m["name"], m["unit"]) for m in group
            if workload in m.get("workloads", [workload])]


def first_clear(corpus, spill_vec, rec, slack: float = 1e-4) -> list:
    """For a batch whose every query hit: per query, the first row (in the
    order a scan reads them) whose f32 sim reaches theta_R - slack. The
    slack only moves the row earlier, so the bytes counted stay a lower
    bound of what any row-order scan must read."""
    q = rec.queries.astype(np.float32)
    out = np.full(len(q), -1, np.int64)
    bar = rec.theta - slack
    chunk = 65_536
    for s in range(0, len(corpus.vectors), chunk):
        sims = q @ corpus.vectors[s:s + chunk].T
        for i in np.flatnonzero(out < 0):
            j = np.flatnonzero(sims[i] >= bar)
            if len(j):
                out[i] = s + j[0]
        if (out >= 0).all():
            return out.tolist()
    ssim = q @ spill_vec[:rec.n_spill].T if rec.n_spill else None
    for i in np.flatnonzero(out < 0):
        j = np.flatnonzero(ssim[i] >= bar) if ssim is not None else []
        if not len(j):
            return None
        out[i] = len(corpus.vectors) + j[0]
    return out.tolist()


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, dev=None, cfg=None, mix=None, cell=None,
             spec=None, peaks=None, out_dir: pathlib.Path = None,
             bench: pathlib.Path = BENCH, controls: bool = False,
             probe: dict = None, log=print) -> dict:
    """One run; returns the result line's object (``checks`` last).
    ``cfg``/``mix``/``cell``/``spec`` default to the files found by name;
    tests pass a reduced configuration. ``controls`` adds the readings of
    the lower-precision controls (never in the benchmark's own runs)."""
    import jax
    work, spec0, cfg0, mix0, cell0 = load_cell(name, bench)
    cfg, mix, cell = cfg or cfg0, mix or mix0, cell or cell0
    spec = spec or spec0
    dev = dev or jax.devices()[0]
    e, m = cfg["encoder"], cfg["model"]
    vocab = min(e["vocab_size"], m["vocab_size"])
    sizes = mix["batch_sizes"]

    schedule = T.make_schedule(mix, float(cell["rate"]), seconds, seed)
    warm = T.warmup_requests(mix, seed, vocab)
    spans = Spans(annotate=trace)
    _Compiles.listen()
    sysm = build(cfg, mix, seed, schedule, warm, gateway_class(spans),
                 engine_wrap=lambda eng: EngineProxy(eng, spans))
    gw = sysm.gw
    t0 = time.perf_counter()
    rid = warm_up(gw, warm, sizes, int(cell["warm_max_new"]), 1)
    sysm.timings["warmup_s"] = time.perf_counter() - t0
    gc.collect()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sysm.timings.items()))

    # the traced part of the window: its own annotation marks it
    hooks = None
    tdir = None
    if trace:
        tdir = (out_dir or bench.parent / "bench_out") / "trace"
        shutil.rmtree(tdir, ignore_errors=True)
        # the last part of the window, so that writing the trace out
        # stalls the drain and not the window; no Python tracer, whose
        # events would flood the trace and slow every call
        length = min(float(cell["trace"]["seconds"]), seconds / 2)
        start = seconds - length
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0

        ann = []

        def t_on():
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
            # a TraceMe records only if the profiler is on when it is made
            ann.append(jax.profiler.TraceAnnotation("bench.traced"))
            ann[0].__enter__()

        def t_off():
            ann[0].__exit__(None, None, None)
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace written in {time.perf_counter() - t0:.3f} s")

        hooks = (start, length, t_on, t_off)

    _Compiles.names = []
    _Compiles.on = True
    win = serve(gw, schedule, seed, vocab, sizes, seconds,
                float(cell["drain_s"]), rid, trace=hooks)
    _Compiles.on = False
    compiles = list(_Compiles.names)
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    cache = gw.frontend.cache
    o, c = win.counters_open, win.counters_close
    failed = sum(1 for s in win.sent if s.req is None)
    log(f"window {seconds} s at {cell['rate']} req/s: {len(win.sent)} due, "
        f"{failed} unfinished after the drain, batcher late p95 "
        f"{win.late_p95_s * 1e3:.3f} ms, {len(compiles)} compiles in the "
        f"window and drain, hits {c['hits'] - o['hits']} / lookups "
        f"{c['hits'] - o['hits'] + c['misses'] - o['misses']}, "
        f"quant_fallbacks {c['quant_fallbacks'] - o['quant_fallbacks']}, "
        f"spill rows {len(cache.spill)} of {cache.spill_capacity}, mirror "
        f"rebuilds {cache.dev_rebuilds}, peak_bytes_in_use {peak_bytes}")
    log("loop turns over 0.1 s: " + ", ".join(
        f"{a} {d:.3f} s" for d, a in win.stalls) + "; longest gc pauses: "
        + ", ".join(f"gen{g} {d:.3f} s" for d, g in win.gc_pauses))

    run = RunData(cfg, cell, win, spans, gw.records, len(sysm.corpus),
                  seconds, setup_s, dev.device_kind, peaks=peaks)
    if run.peaks is None and dev.platform == "tpu":
        run.peaks = load_peaks(dev.device_kind)
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices()),
                     "memory_peak_bytes": peak_bytes}
    breakdown = None
    spill = C.spill_rows(gw, gw.records)
    if trace:
        import trace_reduce
        files = sorted(tdir.rglob("*.xplane.pb"))
        run.trace = trace_reduce.load(files[-1]) if files else \
            {"devices": {}, "spans": []}
        if run.trace["devices"]:
            lo, hi = trace_reduce.window_of(run.trace)
            run.trace_window, run.trace_host = (lo, hi), win.trace_span
            run.reduced = trace_reduce.reduce(run.trace, lo, hi)
            result_device["busy_s"] = run.reduced["busy_s"]
            result_device["window_s"] = run.reduced["window_s"]
            breakdown = {"device_ops": run.reduced["device_ops"],
                         "idle_gaps": run.reduced["idle_gaps"]}
            a, b = win.trace_span
            run.extra["first_clear"] = {
                id(r): first_clear(sysm.corpus, spill[0], r)
                for r in gw.records if a <= r.t < b and r.res.hit.all()}
        shutil.rmtree(tdir, ignore_errors=True)
    metrics = read_metrics(metric_names(spec, name, trace), run, bench,
                           log=log)
    if probe is not None:
        probe["ttft"], probe["tpot"] = run.latencies()
        probe["outstanding"] = win.outstanding
        probe["span_median_ms"] = {
            k: float(np.median([b - a for a, b, _ in run.in_window(k)]))
            * 1e3 for k in ("embed", "lookup", "prefill", "decode", "submit")
            if run.in_window(k)}

    # ---- correct: free the program's state, then the reference
    spill_wrong = C.check_spill(cache, *spill)
    records = gw.records
    reqs = [s.req for s in win.sent if s.req is not None
            and s.req.served_by == "engine"]
    corpus, params, eparams = sysm.corpus, sysm.params, sysm.eparams
    cache._dev = None
    sysm.engine.cache = None
    del gw, sysm, cache, run
    gc.collect()
    t0 = time.perf_counter()
    rng = np.random.default_rng([seed, 4])
    in_window = [r for r in records if win.t_open <= r.t <= win.t_end]
    chk = cell["check"]
    lim = cell["limits"]
    lpicks = C.sample_lookups(in_window, rng, chk["lookups"])
    look = C.check_lookups(corpus, spill, lpicks, lim["lookup_sim"])
    epicks = C.sample_lookups(in_window, rng, chk["embeds"])
    emb = C.embed_dist(cfg, eparams, epicks)
    sample = C.sample_requests(reqs, rng, chk["tokens"])
    gap = C.token_gaps(cfg, params, sample)
    log(f"reference: {look['checked']} lookups ({look['hits']} hits, "
        f"{look['early_accepts']} early accepts), {chk['embeds']} "
        f"embeddings, {len(sample)} engine requests "
        f"({sum(len(r.out) for r in sample)} served tokens) in "
        f"{time.perf_counter() - t0:.3f} s")
    checks = {
        "lookup_sim": {"value": look["lookup_sim"],
                       "limit": lim["lookup_sim"]},
        "lookup_wrong": {"value": look["lookup_wrong"], "limit": 0},
        "spill_wrong": {"value": spill_wrong, "limit": 0},
        "embed_dist": {"value": emb, "limit": lim["embed_dist"]},
        "token_gap": {"value": gap, "limit": lim["token_gap"]},
        "window_compiles": {"value": len(compiles), "limit": 0},
    }
    if compiles:
        log(f"compiled in the window: {sorted(set(map(str, compiles)))}")
    correct = bool(look["checked"] and sample and all(
        v["value"] <= v["limit"] for v in checks.values()))
    out = {"correct": correct, "attempted": len(win.sent), "failed": failed,
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if controls:
        ctl = {"lookup_sim": C.control_lookup_sim(corpus, spill, lpicks),
               "embed_dist": C.embed_dist(cfg, eparams, epicks, lowp=True),
               "token_gap": C.token_gaps(cfg, params, sample, lowp=True)}
        out["controls"] = ctl
        # the control in the program's place, judged by the same limits:
        # it has to come out as not correct
        out["control_correct"] = all(ctl.get(k, v["value"]) <= v["limit"]
                                     for k, v in checks.items())
    out["checks"] = checks
    return out
