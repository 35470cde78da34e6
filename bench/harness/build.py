"""Set-up of a cell: the program's serving objects, built from the
configuration file, with the benchmark's own weights and corpus.

The program is the system under test: ``ModelEngine``, the encoder's
``make_embed_fn`` and ``ServingGateway.from_config``. The weights and the
corpus are the benchmark's, made from the seed (``bench/models``,
``harness/traffic``), so the reference can use them without taking
anything the program made.
"""
from __future__ import annotations

import importlib
import time
import zlib
from dataclasses import dataclass, field

import numpy as np

from harness import traffic as T

ID_BASE = 10_000_000          # corpus answer ids; engine answers use rids


def model_module(name: str):
    return importlib.import_module(f"models.{name}")


def engine_config(m: dict):
    """The program's ModelConfig for the configuration file's ``model``
    block: the registered architecture with every size the file states."""
    from repro.configs.base import get_config
    p = m["program"]
    return get_config(p["arch"]).replace(
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], q_lora_rank=m["q_lora_rank"],
        kv_lora_rank=m["kv_lora_rank"], qk_nope_dim=m["qk_nope_head_dim"],
        qk_rope_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), d_head=0,
        dtype=m["dtype"], remat=False)


def encoder_config(e: dict):
    from repro.configs.base import get_config
    from repro.configs.siso_embedder import EMBED_FACTOR_DIM
    if e["embedding_size"] != EMBED_FACTOR_DIM:
        raise ValueError(f"the program's encoder factorizes its embedding at "
                         f"{EMBED_FACTOR_DIM}, the file states "
                         f"{e['embedding_size']}")
    return get_config(e["program"]["arch"]).replace(
        n_layers=e["num_hidden_layers"], d_model=e["hidden_size"],
        n_heads=e["num_attention_heads"],
        n_kv_heads=e["num_attention_heads"],
        d_head=e["hidden_size"] // e["num_attention_heads"],
        d_ff=e["intermediate_size"], vocab_size=e["vocab_size"],
        rope_theta=float(e["rope_theta"]), dtype=e["dtype"])


def answer_of(tokens, dim: int) -> np.ndarray:
    """The answer recorded for an engine completion: a unit vector seeded
    by the generated tokens, so a repeat's hit can be checked against the
    tokens the engine produced."""
    seed = zlib.crc32(np.asarray(tokens, np.int64).tobytes())
    v = np.random.default_rng(seed).standard_normal(dim)
    return (v / np.linalg.norm(v)).astype(np.float32)


@dataclass
class System:
    """What set-up hands to the window: the gateway and everything the
    check needs afterwards."""
    gw: object
    engine: object
    params: object
    eparams: object
    corpus: object              # CentroidStore of the corpus rows
    timings: dict = field(default_factory=dict)


def build(cfg: dict, mix: dict, seed: int, schedule: T.Schedule, warm: list,
          gateway_cls, engine_wrap=lambda e: e) -> System:
    """Weights, encoder, engine, gateway and corpus for one run."""
    import jax
    from repro.core.store import CentroidStore
    from repro.models.embedder import make_embed_fn
    from repro.serving.config import CacheConfig, RefreshConfig, \
        ServingConfig
    from repro.serving.engine import ModelEngine

    m, e, c = cfg["model"], cfg["encoder"], cfg["cache"]
    dim = e["hidden_size"]
    timings = {}
    rows = int(c["corpus_rows"])
    # the corpus rows first, while the device is empty: drawing them takes
    # twice their size on the device for a moment
    t0 = time.perf_counter()
    vecs = T.geometry_rows(seed, rows, e["hidden_size"], c["geometry"])
    timings["corpus_draw_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    mm, em = model_module(m["program"]["reference"]), \
        model_module(e["program"]["reference"])
    params = mm.init_weights(m, seed)
    eparams = em.init_weights(e, seed)
    jax.block_until_ready((params, eparams))
    timings["weights_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    mcfg, ecfg = engine_config(m), encoder_config(e)
    embed_fn = make_embed_fn(eparams, ecfg, e["seq_len"], e["batch"])
    engine = ModelEngine(params, mcfg, n_slots=cfg["engine"]["n_slots"],
                         max_len=cfg["engine"]["max_len"])
    scfg = ServingConfig(
        cache=CacheConfig(dim=dim, answer_dim=dim,
                          capacity=rows + int(c["spill_rows"]),
                          backend=c["backend"], theta_r=float(c["theta_r"]),
                          dynamic_threshold=False,
                          rescore_k=int(c.get("rescore_k", 16))),
        # the corpus stands for the served history; no refresh falls due
        # inside a run
        refresh=RefreshConfig(min=10 ** 12))
    gw = gateway_cls.from_config(scfg, engine=engine_wrap(engine),
                                 embed_fn=embed_fn,
                                 answer_fn=lambda toks: answer_of(toks, dim))
    timings["objects_s"] = time.perf_counter() - t0

    # corpus: calibrated geometry, plus one near-duplicate of every hot
    # prompt this run sends (and of the warm-up's hit prompts), taken from
    # the benchmark's own encoder so that neither the cache nor the check
    # searches rows the program made; the hot prompts it never sends
    # cannot be told apart from unrelated rows, so they are not encoded
    t0 = time.perf_counter()
    rng = np.random.default_rng([seed, 3])
    vocab = min(e["vocab_size"], m["vocab_size"])
    warm_hits = [(p, r, t) for p, r, t in warm if r % 2 == 0]
    n_hot = int(mix["hot"]["n"])
    hot_pos = rng.choice(rows, size=n_hot + len(warm_hits), replace=False)
    hot_len = {int(r): int(n) for p, r, n in
               zip(schedule.pool, schedule.rank, schedule.prompt_len)
               if p == T.HOT}
    ranks = sorted(hot_len)
    toks = [T.prompt_tokens(seed, T.HOT, r, hot_len[r], vocab)
            for r in ranks] + [t for _, _, t in warm_hits]
    slots = [int(hot_pos[r]) for r in ranks] + \
        [int(hot_pos[n_hot + i]) for i in range(len(warm_hits))]
    if toks:
        vecs[slots] = T.near_duplicates(rng, em.encode(eparams, e, toks),
                                        float(c["near_dup_eps"]))
    answers = np.roll(vecs, 1, axis=1)
    store = CentroidStore(
        e["hidden_size"], e["hidden_size"], vectors=vecs, answers=answers,
        cluster_size=np.ones(rows), access_count=np.zeros(rows),
        answer_id=ID_BASE + np.arange(rows, dtype=np.int64),
        ids=np.arange(rows, dtype=np.int64), _next_id=rows)
    cache = gw.frontend.cache
    cache.begin_shadow(rows)
    chunk = 65_536
    for s in range(0, rows, chunk):
        cache.shadow_write(vecs[s:s + chunk], answers[s:s + chunk],
                           store.answer_id[s:s + chunk])
    cache.commit_shadow(store)
    timings["corpus_load_s"] = time.perf_counter() - t0
    return System(gw, engine, params, eparams, store, timings)
