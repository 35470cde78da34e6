"""One general traffic generator, driven by a mix file in ``bench/traffic/``.

A mix file holds parameters only (arrivals, hot and cold prompt pools,
prompt and output lengths, batch sizes). The generator turns a mix, an
offered rate, a window length and a seed into a schedule of requests.

The amount of work does not depend on the seed. A fixed base seed (the
mix's ``base_seed``) draws the sequence of inter-arrival gaps, pool
choices, popularity ranks and output lengths; the run's seed only shuffles
it, inside consecutive blocks of ``BLOCK`` requests, and draws the
prompts' tokens. Every seed therefore offers the same requests, lengths
and gaps in each block, and each block opens at the same moment, in
another order inside it: runs
with different seeds differ by the order and the contents of the work,
not by how much of it falls into any part of the window (so not by how
many of the window's engine tokens a long answer that arrives late cuts
off).

Pieces copied from the program's synthetic workload (``data/synth.py``):
Zipf popularity over a ranked pool, gamma-renewal arrivals with a given
coefficient of variation (Poisson at cv 1), lognormal output lengths, and
the embedding geometry ``normalize(alpha*g + beta*c_k + sigma*n)`` with
``alpha^2 = base_sim`` and ``alpha^2 + beta^2 = dup_sim`` for corpus rows.
"""
from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass

import numpy as np

HOT, COLD, WARM = 0, 1, 2
BLOCK = 16                      # requests a seed shuffles among (~2 s at 8/s)


def load_mix(bench_dir: pathlib.Path, name: str) -> dict:
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def zipf_ranks(rng: np.random.Generator, n_items: int, s: float,
               size: int) -> np.ndarray:
    """``size`` draws of 0-based ranks with P(rank r) ~ (r+1)^-s."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(size)), n_items - 1)


def gaps(rng: np.random.Generator, n: int, rate: float, cv: float
         ) -> np.ndarray:
    """Inter-arrival gaps: exponential at cv 1, gamma renewal otherwise."""
    mean = 1.0 / rate
    if abs(cv - 1.0) < 1e-9:
        return rng.exponential(mean, n)
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, mean / shape, n)


@dataclass
class Schedule:
    """Requests in due order. ``due`` is seconds after the window opens."""
    due: np.ndarray          # (n,) float64
    pool: np.ndarray         # (n,) HOT or COLD
    rank: np.ndarray         # (n,) popularity rank inside the pool
    prompt_len: np.ndarray   # (n,) tokens
    max_new: np.ndarray      # (n,) tokens to generate
    seed: int

    def __len__(self) -> int:
        return len(self.due)


def _length_of(mix: dict, pool: int, ranks: np.ndarray) -> np.ndarray:
    """Prompt length is a property of the prompt: drawn per (pool, rank)
    from the base seed, so a repeated prompt keeps its length."""
    lens = np.asarray(mix["prompt_len"]["values"], np.int64)
    p = np.asarray(mix["prompt_len"]["p"], np.float64)
    out = np.empty(len(ranks), np.int64)
    for i, r in enumerate(ranks):
        rng = np.random.default_rng([mix["base_seed"], 7, pool, int(r)])
        out[i] = lens[rng.choice(len(lens), p=p / p.sum())]
    return out


def output_lengths(rng: np.random.Generator, mix: dict, n: int) -> np.ndarray:
    o = mix["output_len"]
    x = rng.lognormal(np.log(o["median"]), o["sigma"], n)
    return np.clip(np.rint(x), o["min"], o["max"]).astype(np.int64)


def _block_order(rng: np.random.Generator, n: int, block: int
                 ) -> np.ndarray:
    """A permutation of range(n) that moves items only inside consecutive
    blocks of ``block``."""
    out = np.arange(n, dtype=np.int64)
    for s in range(0, n, block):
        out[s:s + block] = s + rng.permutation(len(out[s:s + block]))
    return out


def make_schedule(mix: dict, rate: float, seconds: float, seed: int
                  ) -> Schedule:
    """Every request due inside ``[0, seconds)``, at ``rate`` per second."""
    base = np.random.default_rng(mix["base_seed"])
    cv = float(mix["arrivals"]["cv"])
    g = gaps(base, int(rate * seconds * 2 + 64), rate, cv)
    n = int(np.searchsorted(np.cumsum(g), seconds))
    g = g[:n]
    pool = np.where(base.random(n) < mix["hot"]["share"], HOT, COLD)
    rank = np.where(pool == HOT,
                    zipf_ranks(base, mix["hot"]["n"], mix["hot"]["zipf_s"], n),
                    zipf_ranks(base, mix["cold"]["n"], mix["cold"]["zipf_s"],
                               n))
    max_new = output_lengths(base, mix, n)
    # the run's seed: inside each block, one joint shuffle of the requests
    # and another of the gaps; a block's gaps keep their sum, so every
    # block opens when it does for every seed and all n stay inside
    rng = np.random.default_rng([seed, 1])
    order = _block_order(rng, n, BLOCK)
    pool, rank, max_new = pool[order], rank[order], max_new[order]
    due = np.cumsum(g[_block_order(rng, n, BLOCK)])
    plen = np.empty(n, np.int64)
    for p in (HOT, COLD):
        m = pool == p
        plen[m] = _length_of(mix, p, rank[m])
    return Schedule(due, pool, rank, plen, max_new, seed)


def prompt_tokens(seed: int, pool: int, rank: int, length: int,
                  vocab: int) -> np.ndarray:
    """The tokens of one prompt, from the run's seed: the same prompt
    (pool, rank) always has the same tokens within a run."""
    rng = np.random.default_rng([seed, 2, pool, int(rank)])
    return rng.integers(1, vocab, length).astype(np.int32)


def warmup_requests(mix: dict, seed: int, vocab: int) -> list:
    """Prompts for the warm-up: one per prompt length, as misses, plus
    copies that will be near-duplicates in the corpus, as hits. Returned
    as (pool, rank, tokens) with pool WARM."""
    out = []
    for i, length in enumerate(mix["prompt_len"]["values"]):
        for j in range(max(mix["batch_sizes"])):
            rank = i * 1000 + j
            out.append((WARM, rank,
                        prompt_tokens(seed, WARM, rank, int(length), vocab)))
    return out


def batch_size(n_due: int, sizes: list) -> int:
    """The largest warmed batch size not above the number of requests due
    (0 when none is due)."""
    fit = [s for s in sizes if s <= n_due]
    return max(fit) if fit else 0


def geometry_rows(seed: int, n: int, dim: int, geo: dict) -> np.ndarray:
    """``n`` unit rows with the calibrated anisotropic geometry: a global
    direction shared by all rows, Zipf-popular cluster directions
    orthogonal to it, and per-row noise (``data/synth.py``). Drawn on the
    device in one jitted call from ``seed``, then brought to the host."""
    import jax
    import jax.numpy as jnp
    alpha = float(np.sqrt(geo["base_sim"]))
    beta = float(np.sqrt(max(geo["dup_sim"] - geo["base_sim"], 1e-6)))
    sigma = float(np.sqrt(max(1.0 - geo["dup_sim"], 1e-6)))
    k = int(geo["n_clusters"])
    w = np.arange(1, k + 1, dtype=np.float64) ** -float(geo["zipf_s"])
    cdf = jnp.asarray(np.cumsum(w) / w.sum(), jnp.float32)

    def make(key):
        kg, kc, kz, kn = jax.random.split(key, 4)
        g = jax.random.normal(kg, (dim,))
        g = g / jnp.linalg.norm(g)
        c = jax.random.normal(kc, (k, dim))
        c = c - jnp.outer(c @ g, g)
        c = c / jnp.linalg.norm(c, axis=1, keepdims=True)
        cid = jnp.minimum(jnp.searchsorted(cdf, jax.random.uniform(kz, (n,))),
                          k - 1)
        noise = jax.random.normal(kn, (n, dim))
        noise = noise / jnp.linalg.norm(noise, axis=1, keepdims=True)
        x = alpha * g[None, :] + beta * c[cid] + sigma * noise
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(jax.jit(make)(key))
    return out if out.flags.writeable else out.copy()


def near_duplicates(rng: np.random.Generator, emb: np.ndarray,
                    eps: float) -> np.ndarray:
    """Rows at cosine ~ 1 - eps^2/2 of ``emb`` (``chip_smoke.py``)."""
    noise = rng.standard_normal(emb.shape).astype(np.float32)
    noise *= eps / np.sqrt(emb.shape[1])
    out = emb + noise
    return (out / np.linalg.norm(out, axis=1, keepdims=True)).astype(
        np.float32)
