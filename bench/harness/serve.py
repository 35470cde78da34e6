"""The window: spans around the calls into each layer, the recording
gateway, the warm-up and the open-loop serving loop.

Spans are recorded from the benchmark's files only, around the calls into
each layer: ``submit`` (the gateway), ``embed`` (the encoder's embed_fn),
``lookup`` (the frontend's handle_batch), ``prefill`` and ``decode`` (a
proxy around the engine the harness built). Each span is a host-clock
interval; with tracing on it is also a ``jax.profiler.TraceAnnotation`` so
the host spans and the device trace share one clock.
"""
from __future__ import annotations

import gc
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

from harness import traffic as T

clock = time.perf_counter


class Spans:
    """Host-clock spans: parallel lists (name, start, end, payload)."""

    def __init__(self, annotate: bool = False):
        self.name, self.t0, self.t1, self.payload = [], [], [], []
        self.annotate = annotate

    def ctx(self, name):
        if self.annotate:
            import jax
            return jax.profiler.TraceAnnotation(f"bench.{name}")
        return nullcontext()

    def add(self, name, t0, t1, payload=None):
        self.name.append(name)
        self.t0.append(t0)
        self.t1.append(t1)
        self.payload.append(payload)

    def select(self, name, lo=-np.inf, hi=np.inf):
        """Spans of ``name`` that start inside [lo, hi)."""
        return [(a, b, p) for n, a, b, p in zip(self.name, self.t0, self.t1,
                                               self.payload)
                if n == name and lo <= a < hi]


class EngineProxy:
    """Delegates to the engine; times prefill_into and decode_active, and
    counts the tokens each produces (one per prefill, one per active slot
    per decode step) with the kv lengths the step attended over."""

    def __init__(self, engine, spans: Spans):
        self._engine = engine
        self._spans = spans

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def prefill_into(self, slot, tokens):
        with self._spans.ctx("prefill"):
            t0 = clock()
            out = self._engine.prefill_into(slot, tokens)
            t1 = clock()
        self._spans.add("prefill", t0, t1, len(tokens))
        return out

    def decode_active(self, tokens):
        e = self._engine
        kv = (e.pos[e.active] + 1).astype(np.int64)
        with self._spans.ctx("decode"):
            t0 = clock()
            out = e.decode_active(tokens)
            t1 = clock()
        self._spans.add("decode", t0, t1, kv)
        return out


@dataclass
class LookupRecord:
    """One submitted batch as the lookup served it."""
    t: float
    rids: list
    tokens: list
    queries: np.ndarray
    theta: float
    res: object
    n_spill: int                # spill rows held when it was looked up
    rescored: int = 0           # rows the int8 plane rescored for it


def gateway_class(spans: Spans):
    from repro.serving.gateway import ServingGateway

    class RecordingGateway(ServingGateway):
        """A ServingGateway that spans its embed, lookup and submit, and
        keeps for every batch what the check needs."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.records: list[LookupRecord] = []
            inner_embed = self.embed_fn
            self._toks = None
            self._q = None

            def embed(token_lists):
                with spans.ctx("embed"):
                    t0 = clock()
                    q = inner_embed(token_lists)
                    t1 = clock()
                spans.add("embed", t0, t1, len(token_lists))
                self._toks, self._q = list(token_lists), q
                return q

            self.embed_fn = embed
            fe = self.frontend
            inner_lookup = fe.handle_batch

            def handle_batch(vectors, **kw):
                with spans.ctx("lookup"):
                    t0 = clock()
                    res = inner_lookup(vectors, **kw)
                    t1 = clock()
                spans.add("lookup", t0, t1, len(vectors))
                return res

            fe.handle_batch = handle_batch

        def submit(self, batch, now=None):
            cache = self.frontend.cache
            n_spill, resc = len(cache.spill), cache.quant_rescored
            with spans.ctx("submit"):
                t0 = clock()
                hits = super().submit(batch, now=now)
                t1 = clock()
            spans.add("submit", t0, t1, len(batch))
            self.records.append(LookupRecord(
                t0, [r.rid for r in batch], self._toks,
                np.asarray(self._q, np.float32),
                float(self.frontend.theta_r), self.last_result, n_spill,
                cache.quant_rescored - resc))
            return hits

        def step(self):
            with spans.ctx("step"):
                t0 = clock()
                n = super().step()
                t1 = clock()
            spans.add("step", t0, t1, n)
            return n

    return RecordingGateway


@dataclass
class Sent:
    """One request of the schedule as it was served."""
    idx: int
    rid: int
    pool: int
    rank: int
    tokens: np.ndarray
    max_new: int
    due: float = 0.0            # absolute host-clock time it was due
    t_sub: float = 0.0
    req: object = None          # the scheduler's Request, found after serving


def warm_up(gw, warm: list, sizes: list, max_new: int, rid0: int) -> int:
    """Every shape the window uses: each batch size, each prompt length
    (one prefill per length), the decode step, the encoder bucket, and a
    spill insert. Returns the next free request id."""
    from repro.serving.gateway import GatewayRequest
    rid = rid0
    by_len: dict = {}
    for p, r, t in warm:
        by_len.setdefault(len(t), []).append(t)
    lens = sorted(by_len)
    for i, b in enumerate(sorted(sizes)):
        toks = by_len[lens[i % len(lens)]][:b]
        batch = [GatewayRequest(rid=rid + j, model_tokens=t, max_new=max_new)
                 for j, t in enumerate(toks)]
        rid += len(batch)
        gw.submit(batch)
        gw.drain()
    for n in lens:          # one prefill per length, whatever the sizes
        t = by_len[n][-1]
        gw.submit([GatewayRequest(rid=rid, model_tokens=t, max_new=max_new)])
        rid += 1
        gw.drain()
    return rid


@dataclass
class Window:
    t_open: float
    t_close: float
    t_end: float                 # drain finished or gave up
    sent: list
    tokens_open: int
    tokens_close: int
    trace_span: tuple = None     # (t0, t1) host clock of the traced part
    late_p95_s: float = 0.0
    counters_open: dict = field(default_factory=dict)
    counters_close: dict = field(default_factory=dict)
    outstanding: tuple = (0, 0)  # requests due but unfinished, at half
                                 # the window and at its close
    stalls: list = field(default_factory=list)   # (seconds, action) > 0.1 s
    gc_pauses: list = field(default_factory=list)  # (seconds, generation)


def counters(gw) -> dict:
    c = gw.frontend.cache
    return {"hits": c.hits, "misses": c.misses,
            "dev_row_writes": c.dev_row_writes,
            "dev_rebuilds": c.dev_rebuilds,
            "quant_rescored": c.quant_rescored,
            "quant_fallbacks": c.quant_fallbacks,
            "lookup_batches": len(gw.stats.lookup_s),
            "lookup_s_total": float(np.sum(gw.stats.lookup_s))}


def engine_tokens(gw) -> int:
    s = gw.sched
    return sum(len(r.out) for r in s.active.values()) + sum(
        len(r.out) for r in s.done if r.served_by == "engine")


def serve(gw, schedule: T.Schedule, seed: int, vocab: int, sizes: list,
          seconds: float, drain_s: float, rid0: int, trace=None) -> Window:
    """Open-loop serving of ``schedule``: on each turn submit the largest
    warmed batch size not above the number of requests due, else advance
    the engine by one step, else wait for the next arrival. Arrivals stop
    when the window closes; the requests due inside it are then drained
    for at most ``drain_s``. ``trace`` is (start offset, seconds, start,
    stop) for a traced part of the window."""
    from repro.serving.gateway import GatewayRequest
    sent = [Sent(i, rid0 + i, int(schedule.pool[i]), int(schedule.rank[i]),
                 T.prompt_tokens(seed, int(schedule.pool[i]),
                                 int(schedule.rank[i]),
                                 int(schedule.prompt_len[i]), vocab),
                 int(schedule.max_new[i])) for i in range(len(schedule))]
    sched = gw.sched
    nxt = 0
    n = len(sent)
    tr_state = 0
    tr = None
    t_open = clock()
    for s, d in zip(sent, schedule.due):
        s.due = t_open + float(d)
    tok_open, c_open = engine_tokens(gw), counters(gw)
    t_close = t_open + seconds
    closed = False
    tok_close, c_close = tok_open, c_open
    done0 = len(gw.done)
    mid = None

    def outstanding(t):
        due_n = sum(1 for s in sent if s.due <= t)
        return due_n - (len(gw.done) - done0)

    stalls, gc_pauses, gc_t0 = [], [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = clock()
        else:
            gc_pauses.append((clock() - gc_t0[0], info["generation"]))

    gc.callbacks.append(on_gc)
    deadline = t_close + drain_s
    while True:
        now = clock()
        if mid is None and now >= t_open + seconds / 2:
            mid = outstanding(now)
        if trace is not None:
            if tr_state == 0 and now >= t_open + trace[0]:
                trace[2]()
                tr, tr_state = [clock(), None], 1
            elif tr_state == 1 and now >= tr[0] + trace[1]:
                trace[3]()
                tr[1], tr_state = clock(), 2
                # writing the trace out is not the drain's time
                deadline = max(deadline, tr[1] + drain_s)
        if not closed and now >= t_close:
            closed = True
            tok_close, c_close = engine_tokens(gw), counters(gw)
            at_close = outstanding(now)
        if closed and (now >= deadline or (nxt >= n and not sched.queue
                                           and not sched.active)):
            break
        due = nxt
        while due < n and sent[due].due <= now:
            due += 1
        b = T.batch_size(due - nxt, sizes)
        if b:
            t_sub = clock()
            batch = []
            for s in sent[nxt:nxt + b]:
                s.t_sub = t_sub
                batch.append(GatewayRequest(rid=s.rid, model_tokens=s.tokens,
                                            max_new=s.max_new))
            nxt += b
            gw.submit(batch)
            action = f"submit {b}"
        elif sched.queue or sched.active:
            gw.step()
            action = "step"
        else:
            action = "wait"
            wait = (sent[nxt].due if nxt < n else t_close) - clock()
            if wait > 0:
                time.sleep(min(wait, 0.002))
        dt = clock() - now
        if dt > 0.1:
            stalls.append((dt, action))
    gc.callbacks.remove(on_gc)
    if tr_state == 1:
        trace[3]()
        tr[1] = clock()
    t_end = clock()
    by_rid = {r.rid: r for r in gw.done}
    for s in sent:
        s.req = by_rid.get(s.rid)
    late = [s.t_sub - s.due for s in sent if s.t_sub > 0]
    return Window(t_open, t_close, t_end, sent, tok_open, tok_close,
                  tuple(tr) if tr else None,
                  float(np.percentile(late, 95)) if late else 0.0,
                  c_open, c_close, (mid or 0, at_close),
                  sorted(stalls, reverse=True)[:5],
                  sorted(gc_pauses, reverse=True)[:5])
