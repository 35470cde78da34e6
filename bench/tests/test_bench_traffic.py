"""The traffic generator: deterministic by seed, the same work for every
seed, the mix as the file states it, and only warmed batch sizes."""
import collections

import numpy as np
import pytest

import bench_testlib  # noqa: F401  (import paths)
from harness import traffic as T


@pytest.fixture(scope="module")
def mix():
    return T.load_mix(bench_testlib.BENCH, "faq")


def test_same_seed_same_schedule(mix):
    a = T.make_schedule(mix, 12.0, 30.0, 2**33 + 5)
    b = T.make_schedule(mix, 12.0, 30.0, 2**33 + 5)
    for f in ("due", "pool", "rank", "prompt_len", "max_new"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert np.array_equal(T.prompt_tokens(7, T.HOT, 3, 16, 30000),
                          T.prompt_tokens(7, T.HOT, 3, 16, 30000))


def test_every_seed_offers_the_same_work_in_another_order(mix):
    a = T.make_schedule(mix, 12.0, 30.0, 1)
    b = T.make_schedule(mix, 12.0, 30.0, 2)
    assert len(a) == len(b)
    assert not np.array_equal(a.rank, b.rank)
    for f in ("pool", "prompt_len", "max_new"):
        assert sorted(getattr(a, f)) == sorted(getattr(b, f))
    assert a.due[-1] == pytest.approx(b.due[-1])
    assert (a.due < 30.0).all() and (np.diff(a.due) >= 0).all()
    assert not np.array_equal(T.prompt_tokens(1, T.HOT, 0, 16, 30000),
                              T.prompt_tokens(2, T.HOT, 0, 16, 30000))


def test_each_block_holds_the_same_work_for_every_seed(mix):
    """The seeds shuffle only inside blocks: each block has the same
    requests and opens at the same moment, so the work due in any part of
    the window does not change with the seed."""
    a = T.make_schedule(mix, 8.0, 50.0, 2**32 + 11)
    b = T.make_schedule(mix, 8.0, 50.0, 3)
    k = T.BLOCK
    assert len(a) == len(b) > 2 * k
    moved = False
    for s in range(0, len(a), k):
        sl = slice(s, s + k)
        ja = sorted(zip(a.pool[sl], a.rank[sl], a.max_new[sl]))
        jb = sorted(zip(b.pool[sl], b.rank[sl], b.max_new[sl]))
        assert ja == jb
        moved |= not np.array_equal(a.rank[sl], b.rank[sl])
        end = min(s + k, len(a)) - 1
        assert a.due[end] == pytest.approx(b.due[end])
    assert moved


def test_mix_as_stated(mix):
    s = T.make_schedule(mix, 50.0, 400.0, 3)
    n = len(s)
    assert n == pytest.approx(50 * 400, rel=0.05)
    assert (s.pool == T.HOT).mean() == pytest.approx(mix["hot"]["share"],
                                                     abs=0.02)
    # a prompt's length belongs to the prompt: the mix holds over distinct
    # prompts (requests repeat popular prompts, whatever their length)
    distinct = {(p, r): n for p, r, n in zip(s.pool, s.rank, s.prompt_len)}
    counts = collections.Counter(distinct.values())
    for length, p in zip(mix["prompt_len"]["values"],
                         mix["prompt_len"]["p"]):
        assert counts[length] / len(distinct) == pytest.approx(p, abs=0.03)
    o = mix["output_len"]
    assert s.max_new.min() >= o["min"] and s.max_new.max() <= o["max"]
    assert np.median(s.max_new) == pytest.approx(o["median"], rel=0.05)
    # Zipf over the hot set: the most popular prompt is drawn most often
    hot = s.rank[s.pool == T.HOT]
    assert collections.Counter(hot.tolist()).most_common(1)[0][0] == 0
    # a repeated prompt keeps its length
    seen = {}
    for p, r, length in zip(s.pool, s.rank, s.prompt_len):
        assert seen.setdefault((p, r), length) == length
    gaps = np.diff(s.due)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)


def test_batcher_uses_only_warmed_sizes(mix):
    sizes = mix["batch_sizes"]
    got = {T.batch_size(n, sizes) for n in range(0, 100)}
    assert got == {0} | set(sizes)
    assert T.batch_size(3, sizes) == 2 and T.batch_size(17, sizes) == 16


def test_warmup_covers_every_length_and_size(mix):
    warm = T.warmup_requests(mix, 9, 30000)
    lens = collections.Counter(len(t) for _, _, t in warm)
    assert set(lens) == set(mix["prompt_len"]["values"])
    assert min(lens.values()) >= max(mix["batch_sizes"])
