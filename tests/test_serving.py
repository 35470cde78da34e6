"""Serving layer: baselines, simulator orderings, continuous batching."""
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.data.synth import SyntheticWorkload
from repro.serving.baselines import NoCache, VectorCache
from repro.serving.engine import AnalyticEngine, EngineModel
from repro.serving.simulator import (ServingSimulator, bootstrap_frontend,
                                     build_system)


def _unit(rng, n, d=16):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# vector-cache policies (§5.2.6)
# ---------------------------------------------------------------------------


def test_vector_cache_capacity_bound(rng):
    vc = VectorCache(16, 16, capacity=8, policy="lru")
    for v in _unit(rng, 40):
        vc.insert(v, v)
    assert len(vc) == 8


def test_lru_evicts_least_recent(rng):
    vc = VectorCache(16, 16, capacity=2, policy="lru", theta_r=0.99)
    v = _unit(rng, 3)
    vc.insert(v[0], v[0], 0)
    vc.insert(v[1], v[1], 1)
    vc.lookup(v[0][None])              # touch 0 -> 1 is LRU
    vc.insert(v[2], v[2], 2)           # evicts 1
    res = vc.lookup(v)
    assert res.hit[0] and res.hit[2] and not res.hit[1]


def test_lfu_keeps_frequent(rng):
    vc = VectorCache(16, 16, capacity=2, policy="lfu", theta_r=0.99)
    v = _unit(rng, 3)
    vc.insert(v[0], v[0], 0)
    vc.insert(v[1], v[1], 1)
    for _ in range(5):
        vc.lookup(v[0][None])
    vc.insert(v[2], v[2], 2)           # evicts 1 (freq 1 < 6)
    assert vc.lookup(v[:1]).hit[0]
    assert not vc.lookup(v[1:2]).hit[0]


def test_fifo_ignores_touches(rng):
    vc = VectorCache(16, 16, capacity=2, policy="fifo", theta_r=0.99)
    v = _unit(rng, 3)
    vc.insert(v[0], v[0], 0)
    vc.insert(v[1], v[1], 1)
    for _ in range(5):
        vc.lookup(v[0][None])          # touches do not matter for FIFO
    vc.insert(v[2], v[2], 2)           # evicts 0 (first in)
    assert not vc.lookup(v[:1]).hit[0]
    assert vc.lookup(v[1:2]).hit[0]


def test_optimal_never_evicts(rng):
    vc = VectorCache(16, 16, capacity=4, policy="optimal")
    for v in _unit(rng, 50):
        vc.insert(v, v)
    assert len(vc) == 50


# ---------------------------------------------------------------------------
# analytic engine
# ---------------------------------------------------------------------------


def _model():
    return EngineModel.from_config(get_config("qwen3-14b"), n_chips=8)


def test_engine_latency_monotone_in_tokens():
    m = _model()
    assert m.e2e(10, 50) < m.e2e(10, 500) < m.e2e(10, 5000)
    assert m.ttft(10) < m.ttft(1000)


def test_engine_fifo_queueing():
    eng = AnalyticEngine(_model(), concurrency=1)
    s1, d1 = eng.submit(0.0, 10, 100)
    s2, d2 = eng.submit(0.0, 10, 100)
    assert s1 == 0.0 and s2 == pytest.approx(d1)   # second waits


def test_engine_concurrency_reduces_wait():
    e1 = AnalyticEngine(_model(), concurrency=1)
    e4 = AnalyticEngine(_model(), concurrency=4)
    waits1 = [e1.submit(0.0, 10, 100)[0] for _ in range(4)]
    waits4 = [e4.submit(0.0, 10, 100)[0] for _ in range(4)]
    assert sum(waits4) < sum(waits1)


# ---------------------------------------------------------------------------
# simulator: the paper's system ordering (Figs. 9/15 qualitative)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sim_results():
    wl = SyntheticWorkload("quora", dim=32, n_clusters=300, seed=0)
    train = wl.sample(3000, rps=50)
    test = wl.sample(500, rps=12, cv=0.1)
    model = EngineModel.from_config(get_config("qwen3-14b"), n_chips=8)
    L = model.e2e(12, 180)
    out = {}
    for kind in ["vllm", "gptcache", "siso-nodta", "siso"]:
        fe = build_system(kind, dim=32, capacity=200, slo_latency=1.3 * L,
                          llm_latency=L)
        bootstrap_frontend(fe, train)
        sim = ServingSimulator(AnalyticEngine(model, concurrency=4), fe)
        out[kind] = sim.run(test, name=kind)
    return out


def test_siso_highest_hit_ratio(sim_results):
    r = sim_results
    assert r["siso"].hit_ratio >= r["siso-nodta"].hit_ratio \
        >= r["gptcache"].hit_ratio > r["vllm"].hit_ratio == 0.0


def test_siso_highest_slo_attainment(sim_results):
    r = sim_results
    assert r["siso"].slo_attainment >= r["gptcache"].slo_attainment
    assert r["siso"].slo_attainment > r["vllm"].slo_attainment


def test_caching_reduces_latency(sim_results):
    r = sim_results
    assert r["siso"].mean_e2e < r["vllm"].mean_e2e


def test_slo_weighted_quality_ordering(sim_results):
    """Fig. 15: under load, SISO's F1-style score beats vLLM (whose
    violations score 0) despite approximate answers."""
    r = sim_results
    assert r["siso"].slo_weighted_quality > r["vllm"].slo_weighted_quality


def test_vllm_quality_is_exact(sim_results):
    assert sim_results["vllm"].mean_quality == pytest.approx(1.0)


def test_straggler_hedging_reduces_tail():
    wl = SyntheticWorkload("quora", dim=16, n_clusters=100, seed=1)
    test = wl.sample(300, rps=2.0)
    model = EngineModel.from_config(get_config("qwen3-14b"), n_chips=8)
    base = ServingSimulator(AnalyticEngine(model, concurrency=4), NoCache(),
                            jitter_cv=1.0, seed=3)
    hedged = ServingSimulator(AnalyticEngine(model, concurrency=4), NoCache(),
                              jitter_cv=1.0, hedge_threshold=1.5, seed=3)
    rb = base.run(test, "base")
    rh = hedged.run(test, "hedged")
    assert rh.extras["hedged"] > 0
    assert rh.p99_e2e <= rb.p99_e2e * 1.05


# ---------------------------------------------------------------------------
# continuous batching over a real (reduced) model
# ---------------------------------------------------------------------------


def test_scheduler_serves_all_requests(rng):
    import jax
    from repro.models import lm
    from repro.serving.engine import ModelEngine
    from repro.serving.scheduler import ContinuousBatchScheduler, Request
    cfg = get_config("qwen3-14b").reduced().replace(remat=False)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ModelEngine(params, cfg, n_slots=2, max_len=48)
    sched = ContinuousBatchScheduler(eng)
    for i in range(5):
        toks = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
        sched.submit(Request(rid=i, tokens=toks, max_new=4))
    done = sched.drain()
    assert len(done) == 5
    assert sorted(r.rid for r in done) == list(range(5))
    for r in done:
        assert 1 <= len(r.out) <= 4


def test_scheduler_stamps_admission(rng):
    """t_admit is stamped when a queued request is popped into a slot,
    before its prefill: a request that waits for a free slot is admitted
    later than it was queued."""
    import jax
    from repro.models import lm
    from repro.serving.engine import ModelEngine
    from repro.serving.scheduler import ContinuousBatchScheduler, Request
    cfg = get_config("qwen3-14b").reduced().replace(remat=False)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    sched = ContinuousBatchScheduler(ModelEngine(params, cfg, n_slots=2,
                                                 max_len=48))
    for i in range(3):      # the scheduler's clock is its tick
        toks = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
        sched.enqueue(Request(rid=i, tokens=toks, max_new=3))
    done = {r.rid: r for r in sched.drain()}
    for r in done.values():
        assert r.t_submit <= r.t_admit <= r.t_first
    assert done[0].t_admit == done[1].t_admit == 1.0
    assert done[2].t_admit > done[0].t_admit    # waited for a slot


def test_scheduler_continuous_batching_matches_sequential(rng):
    """Staggered continuous batching must produce the same tokens as
    serving each request alone (per-slot positions are independent).

    An untrained model's near-tied logits can argmax differently between
    the vmapped and solo compute orders (CPU thread-order noise ~1e-6),
    so instead of demanding identical greedy strings we teacher-force
    the engine's tokens through solo decode and require each one to sit
    within a tight epsilon of the solo argmax: a position/kv bookkeeping
    bug shifts logits by O(1), a reduction-order tie flip by O(1e-6)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.serving.engine import ModelEngine
    from repro.serving.scheduler import ContinuousBatchScheduler, Request
    cfg = get_config("qwen2.5-14b").reduced().replace(remat=False,
                                                      dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 7)]

    eng = ModelEngine(params, cfg, n_slots=2, max_len=64)
    sched = ContinuousBatchScheduler(eng)
    for i, p in enumerate(prompts):          # 3 reqs > 2 slots: staggered
        sched.submit(Request(rid=i, tokens=p, max_new=4))
    done = {r.rid: r.out for r in sched.drain()}
    assert sorted(done) == [0, 1, 2]
    EPS = 1e-3

    for i, toks in enumerate(prompts):
        assert len(done[i]) == 4
        cache = lm.init_cache(cfg, 1, 64)
        lg, cache = lm.prefill(params, cfg,
                               {"tokens": jnp.asarray(toks)[None]}, cache)
        pos = len(toks)
        for step, tok in enumerate(done[i]):
            top = float(jnp.max(lg[0]))
            got = float(lg[0][tok])
            assert got >= top - EPS, (i, step, tok, got, top)
            t = jnp.asarray([[tok]], jnp.int32)
            lg, cache = lm.decode_step(params, cfg, t, cache,
                                       jnp.asarray(pos, jnp.int32))
            pos += 1


def _vmapped_decode(lm, cfg, cache):
    """The engine's former decode: each slot as its own batch of one at a
    scalar position, vmapped over the slot axis (axis 1 of every cache
    leaf (n_layers, n_slots, ...))."""
    import jax
    cache_axes = jax.tree.map(lambda _: 1, cache)

    def _one(params, tokens, cache, pos):
        cache1 = jax.tree.map(lambda a: a[:, None], cache)
        logits, new_cache, routed = lm.decode_step(
            params, cfg, tokens[None], cache1, pos,
            kv_len=(pos + 1)[None], routing=True)
        return (logits[0], jax.tree.map(lambda a: a[:, 0], new_cache),
                None if routed is None else routed[0])

    return jax.vmap(_one, in_axes=(None, 0, cache_axes, 0),
                    out_axes=(0, cache_axes, 0))


@pytest.mark.parametrize("arch", ["minicpm3-4b", "qwen2.5-14b", "rwkv6-7b",
                                  "zamba2-7b"],
                         ids=["mla", "gqa", "rwkv6", "zamba2"])
def test_engine_batched_decode_matches_vmapped_slots(arch, rng):
    """The engine's one decode step over all slots gives the tokens of
    the former formulation, a one-slot step vmapped over the slots, through
    staggered prefills and a release: slots at different positions, one
    of them reused. The recurrent kinds (rwkv6; mamba2 with zamba's shared
    attention) step the same way."""
    import jax
    from repro.models import lm
    from repro.serving.engine import ModelEngine
    cfg = get_config(arch).reduced().replace(remat=False, dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    new = ModelEngine(params, cfg, n_slots=3, max_len=32)
    old = ModelEngine(params, cfg, n_slots=3, max_len=32)
    old._jit_decode = jax.jit(_vmapped_decode(lm, cfg, old.cache))
    toks = np.zeros(3, np.int32)

    def prefill(slot, n):
        prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        toks[slot] = new.prefill_into(slot, prompt)
        assert old.prefill_into(slot, prompt) == toks[slot]

    def decode(steps):
        for _ in range(steps):
            got, want = new.decode_active(toks), old.decode_active(toks)
            active = new.active
            np.testing.assert_array_equal(got[active], want[active])
            np.testing.assert_array_equal(new.pos, old.pos)
            toks[active] = got[active]

    prefill(0, 5)
    decode(2)
    prefill(2, 9)
    decode(3)
    new.release(0)
    old.release(0)
    prefill(0, 3)
    prefill(1, 7)
    decode(4)
    for a, b in zip(jax.tree.leaves(new.cache), jax.tree.leaves(old.cache)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def test_cache_admission_skips_engine(rng):
    import jax
    from repro.core.siso import SISO, SISOConfig
    from repro.models import lm
    from repro.serving.engine import ModelEngine
    from repro.serving.scheduler import ContinuousBatchScheduler, Request
    cfg = get_config("qwen3-14b").reduced().replace(remat=False)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ModelEngine(params, cfg, n_slots=2, max_len=48)
    d = 16
    siso = SISO(SISOConfig(dim=d, answer_dim=d, capacity=32,
                           dynamic_threshold=False, theta_r=0.9))
    vecs = _unit(rng, 50, d)
    siso.bootstrap(vecs, vecs)
    sched = ContinuousBatchScheduler(eng, cache=siso)
    # query an entry that is certainly cached: a kept centroid itself
    hot = siso.cache.centroids.vectors[0]
    sched.submit(Request(rid=0, tokens=np.asarray([1, 2, 3], np.int32),
                         max_new=4, vector=hot))
    assert sched.done and sched.done[0].served_by == "cache"


# ---------------------------------------------------------------------------
# serve entry point (launch/serve.py)
# ---------------------------------------------------------------------------


def test_reduced_is_a_switch():
    """--reduced (the default) gives the toy width; --no-reduced the
    published one."""
    from repro.launch import serve
    small = serve._model_config(serve.parse_args(["--arch", "minicpm3-4b"]))
    full = serve._model_config(
        serve.parse_args(["--arch", "minicpm3-4b", "--no-reduced"]))
    assert small.d_model == 64
    assert full.d_model == get_config("minicpm3-4b").d_model == 2560
    assert full.n_layers == 62 and not full.remat


def test_socket_transport_refused_on_tpu(monkeypatch):
    """One process per replica cannot share a TPU chip: the socket mode
    refuses and names the in-process transport."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "tpu_attached", lambda: True)
    with pytest.raises(SystemExit) as e:
        serve.main(["--mode", "replica", "--transport", "socket"])
    assert "--transport inproc" in str(e.value.code)


def test_tpu_attached_honours_jax_platforms(monkeypatch):
    from repro.launch import serve
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert serve.tpu_attached() is False


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_location(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins where set; otherwise the cache is the
    fixed in-checkout .jax_cache. Run in a child: the setting is global."""
    import os
    import pathlib
    import subprocess
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(root / "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(root / ".jax_cache")
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = want = str(tmp_path)
    code = ("import jax; from repro.launch.serve import "
            "enable_compile_cache as f; print(f()); "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
