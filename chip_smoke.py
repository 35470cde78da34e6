#!/usr/bin/env python3
"""Chip smoke test: the SISO serving path on a TPU, at published widths.

    python chip_smoke.py              # one chip: encoder + cache + engine + HTTP
    python chip_smoke.py --chips 4    # the sharded cache plane over four chips

One process holds the chip(s) for the whole run. Phases:

1. device  - JAX's default backend must be a TPU; there is no CPU fallback.
2. build   - the ``siso-embedder`` encoder (published widths, ``encode``
             jitted at one sequence bucket), a ``ModelEngine`` over
             ``minicpm3-4b`` (published widths, bf16, 4 slots x 256,
             weights drawn from ``--seed`` under jit) and a
             ``ServingGateway`` whose cache runs the ``pallas`` lookup kernel.
3. corpus  - 500,000 unit vectors of dim 768 made from ``--seed``, loaded
             through the cache's own shadow commit (begin_shadow /
             shadow_write / commit_shadow), as a refresh would.
4. serve   - after a warm-up, 4 batches of 8 requests through
             ``ServingGateway.submit`` then ``drain()`` (half near-duplicates
             of loaded rows, half fresh), then POSTs to an in-thread
             ``CacheHTTPServer`` over the same gateway, among them a repeat
             of an earlier miss, which must come back ``X-Cache: HIT``. No
             program may compile after the warm-up.
5. check   - every lookup's decision, answer id, answer and similarity
             against a float64 numpy top-1 over the same rows; every miss's
             first token against the greedy argmax of ``lm.forward``.

With ``--chips 4`` only the sharded plane runs: a ``ShardedCacheConfig(4)``
cache (shard-local kernel + ``cross_shard_top1``) serves lookups and spill
inserts over the same kind of corpus, compared with the float64 reference
and with a single-device cache holding the same rows.

Any failed phase raises, and the script exits non-zero without a result.
The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import threading
import time
import urllib.request
from dataclasses import dataclass

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

DIM = 768                 # siso-embedder output width = cache key width
ROWS = 500_000            # corpus rows: under the 2^19 mirror pad, so the
                          # run's spill inserts never regrow the mirror
SEQ = 16                  # prompt length = encoder sequence bucket
BATCH = 8                 # requests per submitted batch = encoder batch
N_BATCHES = 4
MAX_NEW = 8
N_SLOTS, MAX_LEN = 4, 256
ID_BASE = 10_000_000      # corpus answer ids; engine answers use request ids
NEAR_DUP_EPS = 0.2        # ||noise|| of a near-duplicate row: cos ~ 0.98
# The encoder's weights are random: its embeddings of unrelated prompts sit
# at cosine 0.3-0.86 (mean 0.61 over 200 prompts), where a trained
# paraphrase encoder run at the paper's 0.86 spreads them far lower. 0.95
# keeps fresh prompts misses and near-duplicates (cosine ~0.98) hits.
THETA_R = 0.95
LOAD_CHUNK = 65_536       # rows per shadow_write
# Lookup checks against the float64 reference. Every f32 lookup contraction
# runs at Precision.HIGHEST, which keeps a served sim within ~2e-7 of the
# exact value on a v5e (one bf16 pass, the default, is off by up to 4e-4);
# a decision may differ from the reference only when the reference sim lies
# within THETA_BAND of theta_R.
SIM_TOL = 1e-5
THETA_BAND = 1e-5


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def unit_rows(rng: np.random.Generator, n: int, d: int) -> np.ndarray:
    x = rng.standard_normal((n, d), dtype=np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak} ({peak / 2**30:.2f} GiB)"


def build_corpus(rng: np.random.Generator, rows: int, dim: int,
                 near: np.ndarray):
    """CentroidStore of ``rows`` random unit rows, with the rows of ``near``
    placed at random positions. Answers are the rows rolled by one lane, so
    an answer gather that read the key matrix would be caught."""
    from repro.core.store import CentroidStore
    vecs = unit_rows(rng, rows, dim)
    slots = rng.choice(rows, len(near), replace=False)
    vecs[slots] = near
    store = CentroidStore(dim, dim)
    store.add(vecs, np.roll(vecs, 1, axis=1), 1.0,
              answer_id=ID_BASE + np.arange(rows))
    return store


def load_corpus(cache, store) -> None:
    """Load ``store`` as the centroid region through the shadow commit a
    refresh uses: staged chunk by chunk, uploaded once, swapped in."""
    cache.begin_shadow(len(store))
    for s in range(0, len(store), LOAD_CHUNK):
        e = min(s + LOAD_CHUNK, len(store))
        cache.shadow_write(store.vectors[s:e], store.answers[s:e],
                           store.answer_id[s:e])
    cache.commit_shadow(store)


def near_duplicates(rng: np.random.Generator, emb: np.ndarray) -> np.ndarray:
    noise = rng.standard_normal(emb.shape, dtype=np.float32)
    noise *= NEAR_DUP_EPS / math.sqrt(emb.shape[1])
    out = emb + noise
    return out / np.linalg.norm(out, axis=1, keepdims=True)


@dataclass
class LookupRecord:
    """One batched lookup as served, with the spill rows it was served
    from (the corpus rows never change during a run)."""
    queries: np.ndarray
    theta: float
    res: object                 # the LookupResult
    spill_vecs: np.ndarray
    spill_answers: np.ndarray
    spill_ids: np.ndarray


def reference_top1(store, queries: np.ndarray, chunk: int = 65_536):
    """float64 top-1 of every query over the centroid rows (first max)."""
    q = queries.astype(np.float64)
    best = np.full(len(q), -np.inf)
    row = np.zeros(len(q), np.int64)
    for s in range(0, len(store), chunk):
        sims = q @ store.vectors[s:s + chunk].astype(np.float64).T
        i = np.argmax(sims, axis=1)
        v = sims[np.arange(len(q)), i]
        better = v > best
        best[better], row[better] = v[better], s + i[better]
    return best, row


def check_lookups(store, records: list, theta_band: float = THETA_BAND,
                  sim_tol: float = SIM_TOL) -> dict:
    """Hold every served lookup to a float64 numpy top-1 over the rows the
    device held at that moment (the corpus plus the spill rows inserted so
    far). A device hit must name a row whose exact sim clears theta_R (the
    kernel's early accept may stop at the first such row) and return that
    row's answer; a device miss must have no row above theta_R. Either way
    a disagreement is allowed only within ``theta_band`` of theta_R, and
    every reported sim must match the exact sim of its row."""
    queries = np.concatenate([r.queries for r in records])
    cent_best, cent_row = reference_top1(store, queries)
    out = {"queries": len(queries), "hits": 0, "max_dsim": 0.0,
           "flips": 0, "early_accepts": 0}
    k = 0
    for rec in records:
        for b, q in enumerate(rec.queries.astype(np.float64)):
            spill = rec.spill_vecs.astype(np.float64) @ q
            best, where = cent_best[k], ("c", int(cent_row[k]))
            if len(spill) and spill.max() > best:
                best, where = float(spill.max()), ("s", int(spill.argmax()))
            k += 1
            res = rec.res
            hit = bool(res.hit[b])
            if hit:
                out["hits"] += 1
                aid = int(res.answer_id[b])
                if aid >= ID_BASE:
                    r = aid - ID_BASE
                    exact = float(store.vectors[r].astype(np.float64) @ q)
                    want = store.answers[r]
                    mine = ("c", r)
                else:
                    match = np.flatnonzero(rec.spill_ids == aid)
                    check(len(match) == 1,
                          f"hit answer id {aid} names no spill row")
                    exact = float(spill[match[0]])
                    want = rec.spill_answers[match[0]]
                    mine = ("s", int(match[0]))
                check(np.array_equal(res.answer[b], want),
                      f"hit answer for id {aid} is not the stored answer")
                if mine != where:
                    # the kernel stops at the first row over theta_R only
                    # once every query of the batch has cleared it; else
                    # the row must tie the exact best
                    check(bool(res.hit.all())
                          or abs(exact - best) <= sim_tol,
                          f"hit on row {mine}, exact best is {where}")
                    out["early_accepts"] += 1
            else:
                exact = best
            dsim = abs(float(res.sim[b]) - exact)
            out["max_dsim"] = max(out["max_dsim"], dsim)
            check(dsim <= sim_tol,
                  f"sim {float(res.sim[b])!r} vs exact {exact!r}: |d| "
                  f"{dsim:.3g} > {sim_tol}")
            decided = exact if hit else best
            if hit != (decided >= rec.theta):
                out["flips"] += 1
                check(abs(decided - rec.theta) <= theta_band,
                      f"decision flip outside the band: hit={hit}, exact "
                      f"sim {decided!r}, theta {rec.theta!r}")
    return out


def check_first_tokens(params, cfg, requests: list) -> dict:
    """Each engine-served request's first token against the greedy argmax
    of a plain ``lm.forward`` over the same prompt and weights. bf16 logits
    tie or nearly tie often over a 73k vocabulary, so a token whose
    reference logit is within two bf16 ulps of the maximum also passes."""
    import jax
    from repro.models import lm
    toks = np.stack([np.asarray(r.tokens, np.int32) for r in requests])
    fwd = jax.jit(lambda p, t: lm.forward(p, cfg, {"tokens": t})[0][:, -1])
    logits = np.asarray(fwd(params, toks), np.float32)
    exact = near = 0
    for r, lg in zip(requests, logits):
        ref = int(np.argmax(lg))
        got = int(r.out[0])
        top = float(lg[ref])
        tol = 2.0 * 2.0 ** (math.floor(math.log2(max(abs(top), 1e-30))) - 7)
        if got == ref:
            exact += 1
        else:
            check(0 <= got < lg.shape[0] and lg[got] >= top - tol,
                  f"request {r.rid}: first token {got} (logit "
                  f"{lg[got] if 0 <= got < lg.shape[0] else 'n/a'}) vs "
                  f"reference argmax {ref} (logit {top})")
            near += 1
    return {"misses": len(requests), "exact": exact, "near_ties": near}


def _gateway_class():
    from repro.serving.gateway import ServingGateway

    class RecordingGateway(ServingGateway):
        """A ServingGateway that keeps, for every submitted batch, what the
        correctness check needs: the query vectors, theta_R, the result
        and the spill rows the lookup saw."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.records: list[LookupRecord] = []
            self._embed = self.embed_fn
            self.embed_fn = self._embed_and_keep

        def _embed_and_keep(self, token_lists):
            self._last_queries = np.asarray(self._embed(token_lists),
                                            np.float32)
            return self._last_queries

        def submit(self, batch, now=None):
            spill = self.frontend.cache.spill
            snap = (spill.vectors.copy(), spill.answers.copy(),
                    spill.answer_id.copy())
            hits = super().submit(batch, now=now)
            if len(batch):
                self.records.append(LookupRecord(
                    self._last_queries, self.stats.theta_trace[-1][1],
                    self.last_result, *snap))
            return hits

    return RecordingGateway


def _post(url: str, tokens, max_new: int) -> tuple[dict, dict]:
    req = urllib.request.Request(
        f"{url}/v1/query",
        data=json.dumps({"tokens": [int(t) for t in tokens],
                         "max_new": max_new}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as r:
        return dict(r.headers), json.loads(r.read())


def run_one_chip(mcfg, ecfg, rows: int, seed: int, dev) -> dict:
    """Phases 2-5 on the default device. ``mcfg``/``ecfg`` are the engine
    and encoder configs: published widths on the chip; the tests run this
    path on the CPU with a reduced engine and a small corpus."""
    import jax
    from functools import partial
    from repro.launch.serve import CacheHTTPServer, hash_embed_fn, \
        init_weights
    from repro.models import embedder
    from repro.serving.config import CacheConfig, RefreshConfig, \
        ServingConfig
    from repro.serving.engine import ModelEngine
    from repro.serving.gateway import GatewayRequest

    dim = ecfg.d_model
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    # ---- build
    eparams = jax.jit(partial(embedder.init_params, cfg=ecfg))(
        jax.random.PRNGKey(seed + 1))
    embed_fn = embedder.make_embed_fn(eparams, ecfg, SEQ, BATCH)
    params = init_weights(mcfg, seed)
    engine = ModelEngine(params, mcfg, n_slots=N_SLOTS, max_len=MAX_LEN)
    # the corpus stands for the bootstrapped history, so the first refresh
    # is due after refresh.frac (10%) of it in new misses: none in this run
    cfg = ServingConfig(
        cache=CacheConfig(dim=dim, answer_dim=dim, capacity=rows + 1024,
                          backend="pallas", theta_r=THETA_R),
        refresh=RefreshConfig(min=max(rows // 10, 1)))
    answer_of = hash_embed_fn(dim)
    gw = _gateway_class().from_config(
        cfg, engine=engine, embed_fn=embed_fn,
        answer_fn=lambda toks: answer_of([toks])[0])
    cache = gw.frontend.cache
    jax.block_until_ready((eparams, params))
    print(f"build: encoder {ecfg.name} d={ecfg.d_model} x{ecfg.n_layers}, "
          f"engine {mcfg.name} d={mcfg.d_model} x{mcfg.n_layers} "
          f"vocab={mcfg.vocab_size} {mcfg.dtype}, slots {N_SLOTS}x{MAX_LEN}, "
          f"cache backend {cache.backend}: {time.perf_counter() - t0:.1f} s")

    # ---- prompts: near-duplicate (hit) and fresh (miss) halves
    vocab = min(ecfg.vocab_size, mcfg.vocab_size)
    half = BATCH // 2
    n_hit = half * (N_BATCHES + 1) + 2      # batches + warm-up + 2 HTTP
    n_miss = half * (N_BATCHES + 1) + 2
    hit_p = rng.integers(1, vocab, (n_hit, SEQ)).astype(np.int32)
    miss_p = rng.integers(1, vocab, (n_miss, SEQ)).astype(np.int32)

    # ---- corpus
    t0 = time.perf_counter()
    near = near_duplicates(rng, embed_fn(list(hit_p)))
    store = build_corpus(rng, rows, dim, near)
    load_corpus(cache, store)
    mem = cache.memory_bytes()
    print(f"corpus: {rows} rows x {dim} via shadow commit in "
          f"{time.perf_counter() - t0:.1f} s; mirror "
          f"{int(cache.layout_dict()['pad'])} rows, "
          f"{mem['device_total_bytes'] / 2**30:.2f} GiB on device; "
          f"peak_bytes_in_use {peak_bytes(dev)}")

    rid = iter(range(100_000, 200_000))     # the HTTP server counts from 0

    def batch(hits, misses):
        return [GatewayRequest(rid=next(rid), model_tokens=t,
                               max_new=MAX_NEW)
                for t in list(hits) + list(misses)]

    server = CacheHTTPServer(("127.0.0.1", 0), [gw], ["r0"])
    thread = threading.Thread(target=server.serve_forever,
                              kwargs={"poll_interval": 0.05}, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    compiles, counting = [], [False]

    def on_event(event, secs, **kw):
        if counting[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        # ---- warm-up: every program the window runs, at its shapes
        t0 = time.perf_counter()
        gw.submit(batch(hit_p[:half], miss_p[:half]))
        gw.drain()
        _post(url, miss_p[half], MAX_NEW)         # B=1 lookup + engine
        _post(url, hit_p[half], MAX_NEW)          # B=1 early-accept hit
        print(f"warm-up: {time.perf_counter() - t0:.1f} s")

        counting[0] = True

        # ---- serve: 4 batches through submit, then drain
        t0 = time.perf_counter()
        lo = half + 1
        hits = []
        for i in range(N_BATCHES):
            s = lo + i * half
            hits.append(gw.submit(batch(hit_p[s:s + half],
                                        miss_p[s:s + half])))
        gw.drain()
        hits = np.concatenate(hits)
        print(f"serve: {len(hits)} requests in {N_BATCHES} batches, "
              f"{int(hits.sum())} hits, {int((~hits).sum())} misses: "
              f"{time.perf_counter() - t0:.1f} s")
        check(hits.any() and (~hits).any(),
              "the batches must serve both hits and misses")

        # ---- HTTP: a fresh miss, repeats of earlier misses, a hit
        earlier = miss_p[lo]                        # a batch-phase miss
        fresh = miss_p[lo + N_BATCHES * half]
        posts = [("fresh", fresh, "MISS"), ("repeat", earlier, "HIT"),
                 ("repeat", fresh, "HIT"),
                 ("near-dup", hit_p[lo + N_BATCHES * half], None)]
        for name, toks, want in posts:
            hdr, body = _post(url, toks, MAX_NEW)
            print(f"http {name}: X-Cache {hdr['X-Cache']} region "
                  f"{hdr['X-Cache-Region']} sim {body['sim']:.6f}")
            if want is not None:
                check(hdr["X-Cache"] == want,
                      f"http {name}: X-Cache {hdr['X-Cache']}, want {want}")
        counting[0] = False
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=10)
    print(f"compiles after warm-up: {len(compiles)} {sorted(set(compiles))}")
    check(not compiles, f"programs compiled after warm-up: {compiles}")
    check(not thread.is_alive(), "HTTP server thread did not stop")
    print(f"device mirror: {cache.dev_row_writes} in-place row writes, "
          f"{cache.dev_rebuilds} rebuilds, {cache.dev_swaps} swaps; "
          f"peak_bytes_in_use {peak_bytes(dev)}")
    check(cache.dev_row_writes > 0 and cache.dev_rebuilds == 0,
          "spill inserts must patch the mirror in place, not rebuild it")

    # ---- check
    t0 = time.perf_counter()
    look = check_lookups(store, gw.records)
    print(f"lookups: {look['queries']} queries, {look['hits']} hits, "
          f"max |dsim| {look['max_dsim']:.3g}, decision flips "
          f"{look['flips']} (all within +-{THETA_BAND} of theta_R), "
          f"early accepts {look['early_accepts']}")
    served = [r for r in gw.done if r.served_by == "engine"]
    toks = check_first_tokens(params, mcfg, served)
    print(f"engine: {toks['misses']} misses, first token = lm.forward "
          f"argmax for {toks['exact']}, within a bf16 near-tie for "
          f"{toks['near_ties']}; checks {time.perf_counter() - t0:.1f} s")
    return {"lookups": look, "first_tokens": toks}


def run_sharded(rows: int, seed: int, n_shards: int = 4) -> dict:
    """The sharded cache plane over ``n_shards`` devices against the
    float64 reference and a single-device cache over the same rows."""
    from repro.core.semantic_cache import SemanticCache
    from repro.distributed.cache_plane import ShardedCacheConfig
    from repro.launch.mesh import make_cache_mesh

    dim = DIM
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    n_near = 4 * BATCH
    near_src = unit_rows(rng, n_near, dim)
    store = build_corpus(rng, rows, dim, near_duplicates(rng, near_src))
    shard = ShardedCacheConfig(n_shards=n_shards,
                               mesh=make_cache_mesh(n_shards))
    sharded = SemanticCache(dim, dim, rows + 1024, backend="pallas",
                            shard=shard)
    single = SemanticCache(dim, dim, rows + 1024, backend="pallas")
    for c in (sharded, single):
        load_corpus(c, store)
    print(f"corpus: {rows} rows x {dim} over {n_shards} shards "
          f"({int(sharded.layout_dict()['pad'])} rows each) and on one "
          f"device: "
          f"{time.perf_counter() - t0:.1f} s")

    theta = THETA_R
    half = BATCH // 2
    records, agree = [], 0
    spill_v, spill_a, spill_i = (np.zeros((0, dim), np.float32),
                                 np.zeros((0, dim), np.float32),
                                 np.zeros((0,), np.int64))

    def lookup(q):
        nonlocal agree
        snap = (spill_v.copy(), spill_a.copy(), spill_i.copy())
        a = sharded.lookup(q, theta)
        b = single.lookup(q, theta)
        records.append(LookupRecord(q, theta, a, *snap))
        check(np.array_equal(a.hit, b.hit)
              and np.array_equal(a.answer_id, b.answer_id)
              and np.array_equal(a.entry, b.entry),
              "sharded and single-device lookups disagree")
        check(np.abs(a.sim - b.sim).max() <= SIM_TOL,
              "sharded and single-device sims differ")
        agree += len(q)
        return a

    fresh = unit_rows(rng, 4 * half, dim)
    for i in range(4):
        q = np.concatenate([near_src[i * BATCH:i * BATCH + half],
                            fresh[i * half:(i + 1) * half]])
        res = lookup(q)
        # record the misses into the spill region: owner-shard routed
        # donated row writes on the sharded plane
        for j in np.flatnonzero(~res.hit):
            aid = 1000 * (i + 1) + int(j)
            ans = np.roll(q[j], 1)
            for c in (sharded, single):
                c.insert_spill(q[j], ans, answer_id=aid)
            spill_v = np.concatenate([spill_v, q[j:j + 1]])
            spill_a = np.concatenate([spill_a, ans[None]])
            spill_i = np.append(spill_i, aid)
    res = lookup(np.concatenate([fresh[:half], near_src[-half:]]))
    check(res.hit[:half].all(), "recorded misses must hit on repeat")
    check(sharded.dev_row_writes > 0 and sharded.dev_rebuilds == 0,
          "sharded spill inserts must patch the plane in place")
    look = check_lookups(store, records)
    print(f"sharded lookups: {look['queries']} queries, {look['hits']} "
          f"hits, max |dsim| {look['max_dsim']:.3g} vs float64, decision "
          f"flips {look['flips']}, early accepts {look['early_accepts']}; "
          f"{agree} decisions identical to the single-device cache; "
          f"{sharded.dev_row_writes} in-place shard row writes")
    return {"lookups": look, "agree_single": agree}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded cache plane")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's default device is {dev.platform!r}, not "
              f"a TPU; this check runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s) visible", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.serve import enable_compile_cache
    print(f"device: {dev.device_kind}, {len(devices)} device(s), "
          f"jax {jax.__version__}, compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(ROWS, args.seed)
    else:
        from repro.configs.base import get_config
        run_one_chip(get_config("minicpm3-4b").replace(remat=False),
                     get_config("siso-embedder"), ROWS, args.seed, dev)
    print(f"total {time.perf_counter() - t0:.1f} s; peak_bytes_in_use "
          f"{peak_bytes(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
