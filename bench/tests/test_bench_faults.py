"""What decides ``correct`` catches a broken timed path, and the
lower-precision control: the harness runs at tiny size on the CPU (the
chip check skipped) with the program broken underneath, and ``correct``
comes out false for each fault a one-chip serving cell can have."""
import numpy as np
import pytest

import bench_testlib


@pytest.fixture
def engine_cls():
    from repro.serving.engine import ModelEngine
    return ModelEngine


@pytest.fixture
def cache_cls():
    from repro.core.semantic_cache import SemanticCache
    return SemanticCache


def _stale_state(mp, engine_cls, cache_cls):
    """A decode step that returns its state unchanged."""
    orig = engine_cls.decode_active

    def stale(self, tokens):
        cache = self.cache
        out = orig(self, tokens)
        self.cache = cache
        return out
    mp.setattr(engine_cls, "decode_active", stale)


def _token_altered(mp, engine_cls, cache_cls):
    """A generated token altered where it is produced."""
    orig = engine_cls.decode_active

    def altered(self, tokens):
        return (orig(self, tokens) + 1) % self.cfg.vocab_size
    mp.setattr(engine_cls, "decode_active", altered)


def _half_batch(mp, engine_cls, cache_cls):
    """Half of a lookup batch left out: the second half gets the first
    half's results."""
    from repro.core.semantic_cache import LookupResult
    orig = cache_cls.lookup

    def half(self, queries, theta_r, update_counts=True):
        q = np.atleast_2d(np.asarray(queries, np.float32))
        if len(q) < 2:
            return orig(self, q, theta_r, update_counts)
        h = len(q) // 2
        r = orig(self, q[:h], theta_r, update_counts)
        i = np.arange(len(q)) % h
        return LookupResult(r.hit[i], r.sim[i], r.answer[i], r.answer_id[i],
                            r.entry[i], r.region[i], r.generation)
    mp.setattr(cache_cls, "lookup", half)


def _answer_altered(mp, engine_cls, cache_cls):
    """A hit's answer altered where it is produced."""
    orig = cache_cls.lookup

    def altered(self, queries, theta_r, update_counts=True):
        r = orig(self, queries, theta_r, update_counts)
        r.answer[r.hit] += np.float32(1e-3)
        return r
    mp.setattr(cache_cls, "lookup", altered)


@pytest.mark.parametrize("fault", [_stale_state, _token_altered,
                                   _half_batch, _answer_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_broken_timed_path_is_not_correct(monkeypatch, engine_cls,
                                          cache_cls, fault):
    fault(monkeypatch, engine_cls, cache_cls)
    parts = bench_testlib.tiny(rate=8.0)
    out = bench_testlib.run_tiny(parts=parts)
    assert not out["correct"], out["checks"]


def test_lower_precision_control_fails_a_limit():
    """The control (the reference with float8 weights in the program's
    place, the lookup's reference at the next precision below) reads above
    a limit on at least one number, where the program reads below all, and
    the harness's own verdict on it is not correct."""
    out = bench_testlib.run_tiny(controls=True)
    assert out["correct"], out["checks"]
    lim = bench_testlib.TINY_LIMITS
    over = [k for k, v in out["controls"].items() if v > lim[k]]
    assert over, out["controls"]
    assert {"embed_dist", "token_gap"} <= set(over)
    assert out["control_correct"] is False, out["controls"]
