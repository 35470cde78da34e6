"""ServingGateway end-to-end + device-resident cache + backend parity."""
import numpy as np
import pytest

from repro.core.semantic_cache import SemanticCache
from repro.core.siso import SISO, SISOConfig
from repro.core.store import CentroidStore


def _unit(rng, n, d=16):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _store(vectors, sizes, d):
    st = CentroidStore(d, d)
    st.add(vectors, vectors, sizes, answer_id=np.arange(len(vectors)))
    return st


# ---------------------------------------------------------------------------
# backend parity: dense / pallas / hnsw agree on hit masks
# ---------------------------------------------------------------------------


def test_backend_hit_mask_parity(rng):
    d = 32
    base = _unit(rng, 40, d)
    store = _store(base, np.arange(40, 0, -1).astype(np.float64), d)
    # hits: tight paraphrases (sim ~0.99); misses: fresh directions
    # (max sim over 40 random 32-d centroids stays far below theta=0.8)
    hits = base[:10] + 0.02 * rng.normal(size=(10, d)).astype(np.float32)
    hits /= np.linalg.norm(hits, axis=1, keepdims=True)
    misses = _unit(rng, 10, d)
    queries = np.concatenate([hits, misses])
    theta = 0.8
    results = {}
    for backend in ("dense", "pallas", "hnsw"):
        cache = SemanticCache(d, d, capacity=64, backend=backend)
        cache.set_centroids(store)
        results[backend] = cache.lookup(queries, theta_r=theta,
                                        update_counts=False)
    ref = results["dense"]
    assert ref.hit[:10].all() and not ref.hit[10:].any()
    for backend in ("pallas", "hnsw"):
        res = results[backend]
        np.testing.assert_array_equal(res.hit, ref.hit, err_msg=backend)
        np.testing.assert_array_equal(res.answer_id, ref.answer_id,
                                      err_msg=backend)
        np.testing.assert_allclose(res.answer, ref.answer, atol=1e-5,
                                   err_msg=backend)
    # dense vs pallas are both exact top-1: sims must agree tightly
    np.testing.assert_allclose(results["pallas"].sim, ref.sim, atol=3e-6)


@pytest.mark.parametrize("backend", ["dense", "pallas", "hnsw"])
def test_empty_query_batch(rng, backend):
    d = 16
    cache = SemanticCache(d, d, capacity=64, backend=backend)
    cache.set_centroids(_store(_unit(rng, 8, d), np.ones(8), d))
    res = cache.lookup(np.zeros((0, d), np.float32), theta_r=0.9)
    assert res.hit.shape == (0,) and res.answer.shape == (0, d)
    assert cache.hits == 0 and cache.misses == 0


def test_pallas_probe_lookup_exact_past_first_tile(rng):
    """T2H probes (theta_r=-1) must see true top-1 sims: the early-accept
    must not fire at theta<=0 and hide matches beyond the first kernel
    tile (block_n=512)."""
    d = 16
    base = _unit(rng, 700, d)
    store = _store(base, np.ones(700), d)
    cache = SemanticCache(d, d, capacity=1024, backend="pallas")
    cache.set_centroids(store)
    # exact copies of entries that live in the second tile
    probes = cache.centroids.vectors[600:605].copy()
    res = cache.lookup(probes, theta_r=-1.0, update_counts=False)
    np.testing.assert_allclose(res.sim, 1.0, atol=1e-5)


def test_pallas_hit_mask_comes_from_kernel():
    """The kernel's theta early-accept mask equals a host re-compare."""
    import jax.numpy as jnp
    from repro.kernels.cosine_topk.ops import cosine_topk
    rng = np.random.default_rng(3)
    q = _unit(rng, 8, 64)
    c = _unit(rng, 300, 64)
    v, i, h = cosine_topk(jnp.asarray(q), jnp.asarray(c), k=1, theta=0.5,
                          return_hit=True)
    np.testing.assert_array_equal(np.asarray(h),
                                  np.asarray(v)[:, 0] >= 0.5)


# ---------------------------------------------------------------------------
# device-resident hot path: in-place patches instead of rebuilds
# ---------------------------------------------------------------------------


def test_insert_spill_patches_device_mirror(rng):
    d = 16
    cache = SemanticCache(d, d, capacity=128, backend="dense")
    base = _unit(rng, 20, d)
    cache.set_centroids(_store(base, np.ones(20), d))
    cache.lookup(base[:1], theta_r=0.9)            # builds the mirror
    assert cache.dev_rebuilds == 1
    fresh = _unit(rng, 30, d)
    for k, v in enumerate(fresh):
        cache.insert_spill(v, v, answer_id=100 + k)
        res = cache.lookup(v[None], theta_r=0.99)
        assert res.hit[0] and res.answer_id[0] == 100 + k
        np.testing.assert_allclose(res.answer[0], v, atol=1e-6)
    # every insert was an in-place row write — the mirror never rebuilt
    assert cache.dev_rebuilds == 1
    assert cache.dev_row_writes == 30


def test_spill_lru_replacement_patches_in_place(rng):
    d = 16
    cache = SemanticCache(d, d, capacity=2, spill_lru=True)
    v = _unit(rng, 3, d)
    cache.insert_spill(v[0], v[0], answer_id=0)
    cache.insert_spill(v[1], v[1], answer_id=1)
    cache.lookup(v[0][None], theta_r=0.99)          # touch v0 -> v1 is LRU
    builds = cache.dev_rebuilds
    cache.insert_spill(v[2], v[2], answer_id=2)     # evicts v1 in place
    res = cache.lookup(v, theta_r=0.99)
    assert res.hit[0] and res.hit[2] and not res.hit[1]
    assert cache.dev_rebuilds == builds             # patched, not rebuilt


def test_device_mirror_grows_by_rebuild(rng):
    d = 16
    cache = SemanticCache(d, d, capacity=4096, backend="dense")
    base = _unit(rng, 120, d)
    cache.set_centroids(_store(base, np.ones(120), d))
    cache.lookup(base[:1], theta_r=0.9)
    assert cache._dev.pad == 128
    for v in _unit(rng, 20, d):                     # 120 + 20 > 128
        cache.insert_spill(v, v)
    res = cache.lookup(_unit(rng, 4, d), theta_r=0.99)
    assert cache._dev.pad == 256                    # pow2 growth
    assert cache.dev_rebuilds == 2


def test_batched_bookkeeping_matches_sequential(rng):
    """Vectorized access-count/LRU updates == the seed's per-hit loop."""
    d = 16
    base = _unit(rng, 8, d)
    cache = SemanticCache(d, d, capacity=16)
    cache.set_centroids(_store(base, np.arange(8, 0, -1).astype(float), d))
    order = cache.centroids.vectors
    batch = np.concatenate([order[:4], order[:2]])   # dup hits in one batch
    cache.lookup(batch, theta_r=0.99)
    counts = cache.centroids.access_count
    assert counts[:2].tolist() == [2.0, 2.0]
    assert counts[2:4].tolist() == [1.0, 1.0]
    assert cache.hits == 6 and cache.misses == 0


# ---------------------------------------------------------------------------
# gateway end-to-end over a real reduced model
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine():
    import jax
    from repro.configs.base import get_config
    from repro.models import lm
    from repro.serving.engine import ModelEngine
    cfg = get_config("qwen3-14b").reduced().replace(remat=False)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return ModelEngine(params, cfg, n_slots=2, max_len=48), cfg


def _make_gateway(rng, engine, cfg, d=16, answer_fn="embed"):
    from repro.serving.gateway import ServingGateway
    siso = SISO(SISOConfig(dim=d, answer_dim=d, capacity=64,
                           dynamic_threshold=False, theta_r=0.9))
    hist = _unit(rng, 40, d)
    siso.bootstrap(hist, hist, answer_ids=np.arange(40))
    fn = None
    if answer_fn == "embed":
        fn = lambda toks: _unit(np.random.default_rng(int(toks[0]) + 1),
                                1, d)[0]
    gw = ServingGateway(siso, engine, embed_fn=lambda vs: np.stack(vs),
                        answer_fn=fn)
    return gw, siso


def test_gateway_hits_bypass_engine(rng, tiny_engine):
    from repro.serving.gateway import GatewayRequest
    engine, cfg = tiny_engine
    gw, siso = _make_gateway(rng, engine, cfg)
    hot = siso.cache.centroids.vectors[:3].copy()
    reqs = [GatewayRequest(rid=i, model_tokens=np.asarray([1, 2, 3], np.int32),
                           embed_tokens=hot[i], max_new=4)
            for i in range(3)]
    hit = gw.submit(reqs)
    assert hit.all()
    assert not gw.sched.queue and not gw.sched.active   # engine untouched
    assert not engine.active.any()
    done = gw.drain()
    assert len(done) == 3
    assert all(r.served_by == "cache" for r in done)
    assert all(r.answer is not None for r in done)


def test_gateway_misses_flow_through_engine_and_refresh(rng, tiny_engine):
    from repro.serving.gateway import GatewayRequest
    engine, cfg = tiny_engine
    gw, siso = _make_gateway(rng, engine, cfg)
    fresh = _unit(rng, 6, 16)
    reqs = [GatewayRequest(rid=i,
                           model_tokens=rng.integers(
                               0, cfg.vocab_size, size=5).astype(np.int32),
                           embed_tokens=fresh[i], max_new=4)
            for i in range(6)]
    hit = gw.submit(reqs)
    assert not hit.any()
    done = gw.drain()
    assert len(done) == 6
    assert all(r.served_by == "engine" for r in done)
    assert all(1 <= len(r.out) <= 4 for r in done)
    # completions were recorded and (40 * 10% = 4 <= 6) triggered a refresh
    assert gw.stats.refreshes >= 1
    assert len(siso._log_vecs) == 0                  # log consumed by refresh
    # the recorded answers are now servable paraphrase hits
    res = siso.cache.lookup(fresh, theta_r=0.99, update_counts=False)
    assert res.hit.sum() >= 5            # recorded (centroid or spill) hits


def test_gateway_rejects_mixed_embed_batches(rng, tiny_engine):
    from repro.serving.gateway import GatewayRequest
    engine, cfg = tiny_engine
    gw, siso = _make_gateway(rng, engine, cfg, answer_fn=None)
    v = _unit(rng, 1, 16)[0]
    toks = np.asarray([1, 2, 3], np.int32)
    with pytest.raises(ValueError, match="mixed batch"):
        gw.submit([GatewayRequest(rid=0, model_tokens=toks, embed_tokens=v),
                   GatewayRequest(rid=1, model_tokens=toks)])


def test_cold_start_refresh_floor(rng):
    """An un-bootstrapped SISO must not re-cluster on every recorded miss."""
    siso = SISO(SISOConfig(dim=16, answer_dim=16, capacity=64,
                           dynamic_threshold=False, refresh_min=8))
    vecs = _unit(rng, 8, 16)
    for v in vecs[:7]:
        siso.record_llm_answer(v, v)
        assert not siso.needs_refresh()
    siso.record_llm_answer(vecs[7], vecs[7])
    assert siso.needs_refresh()


class _VClock:
    """Virtual clock the gateway/scheduler read; tests own .t."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_gateway_closed_loop_theta_adapts_and_recovers(rng, tiny_engine):
    """The live control loop (DESIGN.md §7.1), end to end: observed waits
    from real ContinuousBatchScheduler completions must (1) feed
    DynamicThreshold.feedback() and lower theta_R under sustained
    overload, (2) EMA-calibrate llm_latency off the bogus constructor
    guess, and (3) let theta_R recover once load drops."""
    from repro.serving.gateway import GatewayRequest, ServingGateway
    engine, cfg = tiny_engine
    d = 16
    # llm_latency deliberately ~20x too small: the EMA must fix it
    siso = SISO(SISOConfig(dim=d, answer_dim=d, capacity=64,
                           dynamic_threshold=True, theta_r=0.9),
                slo_latency=0.3, llm_latency=0.01)
    base = _unit(rng, 12, d)
    hist = np.repeat(base, 8, axis=0) \
        + 0.1 * rng.normal(size=(96, d)).astype(np.float32)
    hist /= np.linalg.norm(hist, axis=1, keepdims=True)
    siso.bootstrap(hist, hist, answer_ids=np.arange(96))
    siso.threshold.lambda_window = 1.0
    theta0 = siso.threshold.theta
    clock = _VClock()
    gw = ServingGateway(siso, engine, embed_fn=lambda vs: np.stack(vs),
                        answer_fn=None, clock=clock, auto_refresh=False)
    TICK = 0.05
    toks = np.asarray([1, 2, 3], np.int32)

    # -- overload: 48 cache-missing requests in 0.6 virtual seconds ------
    fresh = _unit(rng, 48, d)
    rid = 0
    for k in range(0, 48, 4):
        reqs = [GatewayRequest(rid=rid + j, model_tokens=toks,
                               embed_tokens=fresh[k + j], max_new=4)
                for j in range(4)]
        rid += 4
        gw.submit(reqs, now=clock.t)
        clock.t += TICK
    while gw.sched.queue or gw.sched.active:   # drain, time advancing
        gw.step()
        clock.t += TICK
    thr = siso.threshold
    assert thr.n_feedback > 0                  # scheduler fed the loop
    assert thr._bias > 0                       # waits exceeded the model
    theta_over = thr.theta
    assert theta_over < theta0                 # overload lowered theta_R
    assert 0.05 < thr.llm_latency < 1.0        # EMA left the 0.01 guess
    rep = gw.report()
    assert rep["slo_attainment"] < 1.0
    assert rep["n_feedback"] == thr.n_feedback
    assert len(rep["theta_trace"]) > 0

    # -- recovery: light cache-friendly load -> bias decays, theta rises -
    hot = siso.cache.centroids.vectors
    for k in range(30):
        clock.t += 0.5
        gw.submit([GatewayRequest(rid=rid, model_tokens=toks,
                                  embed_tokens=hot[k % len(hot)].copy(),
                                  max_new=4)], now=clock.t)
        rid += 1
        while gw.sched.queue or gw.sched.active:
            gw.step()
            clock.t += TICK
    assert siso.threshold.theta > theta_over   # operating point recovered
    assert siso.threshold._bias == 0


def test_gateway_baseline_frontends_run_the_same_path(rng, tiny_engine):
    """NoCache / VectorCache drive the identical live pipeline (the
    bench_slo comparison relies on this): misses flow through engine
    slots, completions are recorded via insert(), report() works."""
    from repro.serving.baselines import VectorCache
    from repro.serving.gateway import GatewayRequest, ServingGateway
    engine, cfg = tiny_engine
    d = 16
    vc = VectorCache(d, d, capacity=32, policy="lru", theta_r=0.9)
    clock = _VClock()
    gw = ServingGateway(vc, engine, embed_fn=lambda vs: np.stack(vs),
                        clock=clock, slo_latency=10.0)
    vecs = _unit(rng, 4, d)
    reqs = [GatewayRequest(rid=i, model_tokens=np.asarray([1, 2, 3],
                                                          np.int32),
                           embed_tokens=vecs[i], max_new=4,
                           answer_vec=vecs[i])
            for i in range(4)]
    hit = gw.submit(reqs, now=0.0)
    assert not hit.any()
    while gw.sched.queue or gw.sched.active:
        gw.step()
        clock.t += 0.05
    # completions recorded into the vector cache -> exact re-asks hit
    hit2 = gw.submit([GatewayRequest(rid=10 + i, model_tokens=np.asarray(
        [1, 2, 3], np.int32), embed_tokens=vecs[i], max_new=4)
        for i in range(4)], now=clock.t)
    assert hit2.all()
    rep = gw.report()
    assert rep["completed"] == 8
    assert rep["served_cache"] == 4 and rep["served_engine"] == 4
    assert rep["slo_attainment"] == 1.0
    assert rep["hit_ratio"] == pytest.approx(0.5)


def test_gateway_repeat_escape(rng, tiny_engine):
    from repro.serving.gateway import GatewayRequest
    engine, cfg = tiny_engine
    gw, siso = _make_gateway(rng, engine, cfg, answer_fn=None)
    hot = siso.cache.centroids.vectors[0].copy()
    toks = np.asarray([1, 2, 3], np.int32)
    h1 = gw.submit([GatewayRequest(rid=0, model_tokens=toks,
                                   embed_tokens=hot, user_id=7, max_new=4)])
    h2 = gw.submit([GatewayRequest(rid=1, model_tokens=toks,
                                   embed_tokens=hot, user_id=7, max_new=4)])
    assert h1[0] and not h2[0]           # same user repeat -> forced miss


def test_gateway_tenant_report_and_counter_persistence(rng, tiny_engine):
    """Per-tenant serving breakdown (DESIGN.md §14): report()["tenants"]
    merges the frontend's cache-side view (hit ratio, occupancy) with
    the gateway's served split and SLO attainment, the tallies survive a
    state_dict round trip, and anonymous requests stay out."""
    from repro.core.tenancy import TenancyConfig
    from repro.serving.gateway import GatewayRequest, ServingGateway
    engine, cfg = tiny_engine
    d = 16
    siso = SISO(SISOConfig(dim=d, answer_dim=d, capacity=64,
                           dynamic_threshold=False, theta_r=0.9,
                           tenancy=TenancyConfig()))
    hist = _unit(rng, 40, d)
    siso.bootstrap(hist, hist, answer_ids=np.arange(40))
    clock = _VClock()
    gw = ServingGateway(siso, engine, embed_fn=lambda vs: np.stack(vs),
                        clock=clock, slo_latency=10.0)
    hot = siso.cache.centroids.vectors[:2].copy()
    fresh = _unit(rng, 2, d)
    toks = np.asarray([1, 2, 3], np.int32)
    reqs = [
        GatewayRequest(rid=0, model_tokens=toks, embed_tokens=hot[0],
                       tenant=1, max_new=4, answer_vec=hot[0]),
        GatewayRequest(rid=1, model_tokens=toks, embed_tokens=fresh[0],
                       tenant=1, max_new=4, answer_vec=fresh[0]),
        GatewayRequest(rid=2, model_tokens=toks, embed_tokens=hot[1],
                       tenant=2, max_new=4, answer_vec=hot[1]),
        GatewayRequest(rid=3, model_tokens=toks, embed_tokens=fresh[1],
                       max_new=4, answer_vec=fresh[1]),    # anonymous
    ]
    gw.submit(reqs, now=0.0)
    while gw.sched.queue or gw.sched.active:
        gw.step()
        clock.t += 0.05
    rep = gw.report()
    tn = rep["tenants"]
    assert set(tn) == {1, 2}                    # anonymous stays out
    assert tn[1]["served_cache"] == 1 and tn[1]["served_engine"] == 1
    assert tn[2]["served_cache"] == 1 and tn[2]["served_engine"] == 0
    assert tn[1]["slo_attainment"] == 1.0
    # cache-side view rode along from the frontend
    assert tn[1]["hits"] == 1 and tn[1]["misses"] == 1
    assert tn[1]["hit_ratio"] == pytest.approx(0.5)
    assert "occupancy_share" in tn[1]
    # tallies survive a gateway state round trip (and pre-tenancy
    # snapshots without the keys load clean)
    st = gw.state_dict()
    gw2 = ServingGateway(siso, engine, embed_fn=lambda vs: np.stack(vs),
                         clock=clock, slo_latency=10.0)
    gw2.load_state(st)
    assert gw2._tenant_counts == gw._tenant_counts
    for k in ("tenant_ids", "tenant_counts"):
        del st[k]
    gw3 = ServingGateway(siso, engine, embed_fn=lambda vs: np.stack(vs),
                         clock=clock, slo_latency=10.0)
    gw3.load_state(st)
    assert gw3._tenant_counts == {}


# ---------------------------------------------------------------------------
# spans and counters on the served path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_gateway_records_its_spans_and_counters(rng, tiny_engine, backend):
    """With recording on, one submit and its drain leave the served path's
    spans, keyed by the batch's first rid, the scheduler tick and the
    prefill's rid; the gateway's lookup timer reads the lookup span's own
    clock pair; each engine request is stamped submitted <= admitted <=
    first token; the f32 kernel reports its tiles, the dense path none."""
    from repro import trace
    from repro.serving.gateway import GatewayRequest, ServingGateway
    engine, cfg = tiny_engine
    d = 16
    siso = SISO(SISOConfig(dim=d, answer_dim=d, capacity=64,
                           dynamic_threshold=False, theta_r=0.9,
                           backend=backend))
    hist = _unit(rng, 40, d)
    siso.bootstrap(hist, hist, answer_ids=np.arange(40))
    gw = ServingGateway(siso, engine, embed_fn=lambda vs: np.stack(vs))
    vecs = np.concatenate([siso.cache.centroids.vectors[:1], _unit(rng, 3)])
    reqs = [GatewayRequest(rid=10 + i, model_tokens=np.asarray(
        [1, 2, 3 + i], np.int32), embed_tokens=vecs[i], max_new=3,
        answer_vec=vecs[i]) for i in range(4)]
    trace.clear()
    try:
        with trace.recording():
            hit = gw.submit(reqs)
            gw.drain()
        rec = trace.spans()
    finally:
        trace.clear()
    assert hit.tolist() == [True, False, False, False]
    names = {s.name for s in rec}
    assert {"gateway.submit", "lookup", "lookup.scan", "lookup.wait",
            "sched.step", "engine.prefill", "engine.prefill.wait",
            "engine.decode", "engine.decode.wait", "sched.retire"} <= names
    assert not any(s.name.startswith("bench.") for s in rec)
    by = {}
    for s in rec:
        by.setdefault(s.name, []).append(s)
    (sub,) = by["gateway.submit"]
    (look,) = by["lookup"]
    assert sub.key == look.key == by["lookup.wait"][0].key == 10
    assert look.parent == sub.seq
    assert gw.stats.lookup_s[-1] == look.t1 - look.t0
    assert sorted(s.key for s in by["engine.prefill"]) == [11, 12, 13]
    steps = {s.seq: s.key for s in by["sched.step"]}
    assert all(steps[s.parent] == s.key for s in by["engine.decode"])
    for r in gw.done:
        if r.served_by == "engine":
            assert r.t_submit <= r.t_admit <= r.t_first
        else:
            assert r.t_admit == 0.0
    tiles = gw.last_result.tiles
    if backend == "pallas":
        assert tiles is not None and 1 <= tiles[0] <= tiles[1]
    else:
        assert tiles is None


def test_gateway_snapshot_that_carries_batch_sizes_loads(rng, tiny_engine,
                                                         tmp_path):
    """Gateway snapshots written while GatewayStats kept batch_sizes hold
    that entry; they load, and it is ignored."""
    from repro.checkpoint import CheckpointManager
    from repro.serving.gateway import GatewayRequest, ServingGateway
    engine, cfg = tiny_engine
    gw, siso = _make_gateway(rng, engine, cfg)
    hot = siso.cache.centroids.vectors[:2].copy()
    gw.submit([GatewayRequest(rid=i, model_tokens=np.asarray([1, 2], np.int32),
                              embed_tokens=hot[i], max_new=2)
               for i in range(2)])
    gw.drain()
    st = gw.state_dict()
    assert "batch_sizes" not in st
    st["batch_sizes"] = np.asarray([2], np.int64)
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(1, {"gateway": st})
    gw2 = ServingGateway(siso, engine, embed_fn=lambda vs: np.stack(vs))
    gw2.load_state(ckpt.restore(1)["gateway"])
    assert gw2.stats.submitted == 2
    assert list(gw2.stats.lookup_s) == list(gw.stats.lookup_s)
    assert not hasattr(gw2.stats, "batch_sizes")
