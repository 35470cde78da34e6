"""Metric readers and the result line.

Every metric named in ``BENCHMARK.json`` has a reader of its own,
``bench/metrics/<name>.py``, with ``read(run) -> float | None``. The
harness finds it by the metric's name; a reader that finds nothing to read
returns None and the metric is left out of the line. ``run`` is a
``RunData``: the window as served, the spans, the counters, the reduced
trace (traced runs), the configuration and the peak table.
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib
from dataclasses import dataclass, field

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]


def percentile(values, p: float) -> float:
    """Nearest-rank percentile; infinite values (failed or unfinished
    requests) take part as the largest."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    k = max(int(math.ceil(p / 100.0 * len(v))) - 1, 0)
    return float(v[k])


def load_peaks(kind: str, path: pathlib.Path = BENCH / "peaks.json") -> dict:
    table = json.loads(path.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]


def load_reader(name: str, bench: pathlib.Path = BENCH):
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class RunData:
    cfg: dict
    cell: dict
    window: object              # harness.serve.Window
    spans: object               # harness.serve.Spans
    records: list               # harness.serve.LookupRecord
    corpus_rows: int
    seconds: float
    setup_s: float
    device_kind: str
    trace: dict = None          # trace_reduce.load output
    trace_window: tuple = None  # (lo, hi) ns of the traced part
    trace_host: tuple = None    # (t0, t1) host clock of the traced part
    reduced: dict = None        # trace_reduce.reduce output
    peaks: dict = None
    extra: dict = field(default_factory=dict)

    def in_window(self, name: str):
        w = self.window
        return self.spans.select(name, w.t_open, w.t_close)

    def latencies(self):
        """(ttft, tpot) samples over the requests due in the window, in
        seconds; a request that never finished counts as infinite."""
        ttft, tpot = [], []
        for s in self.window.sent:
            r = s.req
            if r is None:
                ttft.append(math.inf)
                tpot.append(math.inf)
                continue
            ttft.append(r.t_first - s.due)
            if r.served_by == "engine":
                n = len(r.out)
                tpot.append((r.t_done - r.t_first) / max(n - 1, 1))
        return ttft, tpot


def read_metrics(names_units: list, run: RunData, bench=BENCH,
                 log=print) -> dict:
    """The metrics that have a reading. An infinite reading (a tail that
    lies among unfinished requests) has no number to print: it is left
    out of the line and named on stderr."""
    out = {}
    for name, unit in names_units:
        v = load_reader(name, bench)(run)
        if v is None:
            continue
        if not math.isfinite(v):
            log(f"metric {name}: {v} (unfinished requests in its tail)")
            continue
        out[name] = {"value": float(v), "unit": unit}
    return out
