"""What decides ``correct``: what the timed path produced, compared with
the plain reference once the window has closed.

Numbers compared, each with its limit from the cell file:

* ``lookup_sim``: over a sample of the window's lookups (drawn from the
  seed), the largest |served sim - float64 sim of the row it names| (of
  the float64 best row on a miss). The float64 reference runs in numpy
  over the rows the device held at that moment: the corpus plus the spill
  rows inserted so far.
* ``lookup_wrong``: decisions, answer ids and answers that disagree with
  the reference by more than ``lookup_sim`` allows (a hit must name a row
  whose exact sim clears theta_R and tie the exact best unless the whole
  batch hit, when the kernel may accept the first row over theta_R; a
  miss must have no row over theta_R; a hit's answer must be the stored
  answer, bit for bit). Limit 0.
* ``spill_wrong``: rows of the program's spill region that are not the
  engine completions' query vectors and answers, in completion order.
  Limit 0.
* ``embed_dist``: over a sample of the window's batches, the largest L2
  distance between the served embedding and the reference encoder's.
* ``token_gap``: over a sample of the engine-served requests, the
  longest among them, the widest gap by which a served token's reference
  logit lies below the reference's best at its position.
* ``window_compiles``: programs compiled between the window's opening and
  the end of the drain. Limit 0.
"""
from __future__ import annotations

import numpy as np

from harness.build import ID_BASE, answer_of, model_module


def _top1(rows: np.ndarray, q: np.ndarray, chunk: int = 65_536):
    """float64 best sim and first best row of each query over ``rows``."""
    best = np.full(len(q), -np.inf)
    where = np.zeros(len(q), np.int64)
    for s in range(0, len(rows), chunk):
        sims = q @ rows[s:s + chunk].astype(np.float64).T
        i = np.argmax(sims, axis=1)
        v = sims[np.arange(len(q)), i]
        better = v > best
        best[better], where[better] = v[better], s + i[better]
    return best, where


def spill_rows(gw, records: list) -> tuple:
    """The spill region the reference expects: each engine completion's
    query vector (as the window's lookup received it) and the answer of
    its generated tokens, in completion order."""
    rid_vec = {rid: rec.queries[b] for rec in records
               for b, rid in enumerate(rec.rids)}
    done = [r for r in gw.done if r.served_by == "engine"]
    dim = records[0].queries.shape[1]
    vec = np.stack([rid_vec[r.rid] for r in done]) if done else \
        np.zeros((0, dim), np.float32)
    ans = np.stack([answer_of(r.out, dim) for r in done]) if done else \
        vec.copy()
    ids = np.asarray([r.rid for r in done], np.int64)
    return vec, ans, ids


def check_spill(cache, exp_vec, exp_ans, exp_ids) -> int:
    sp = cache.spill
    n = len(sp)
    if n != len(exp_ids):
        return abs(n - len(exp_ids)) + n
    wrong = ~np.all(sp.vectors == exp_vec, axis=1)
    wrong |= ~np.all(sp.answers == exp_ans, axis=1)
    wrong |= sp.answer_id != exp_ids
    return int(wrong.sum())


def check_lookups(corpus, spill: tuple, picks: list, tol: float) -> dict:
    """``picks``: (record, position) pairs. Returns the widest sim gap and
    the count of wrong decisions, answer ids or answers."""
    exp_vec, exp_ans, exp_ids = spill
    q = np.stack([rec.queries[b] for rec, b in picks]).astype(np.float64)
    cbest, crow = _top1(corpus.vectors, q)
    out = {"lookup_sim": 0.0, "lookup_wrong": 0, "checked": len(picks),
           "hits": 0, "early_accepts": 0}
    for k, (rec, b) in enumerate(picks):
        qk = q[k]
        ns = rec.n_spill
        ssims = exp_vec[:ns].astype(np.float64) @ qk
        best, where = cbest[k], ("c", int(crow[k]))
        if ns and ssims.max() > best:
            best, where = float(ssims.max()), ("s", int(ssims.argmax()))
        res = rec.res
        hit = bool(res.hit[b])
        wrong = False
        if hit:
            out["hits"] += 1
            aid = int(res.answer_id[b])
            if aid >= ID_BASE and aid - ID_BASE < len(corpus):
                r = aid - ID_BASE
                exact = float(corpus.vectors[r].astype(np.float64) @ qk)
                want, mine = corpus.answers[r], ("c", r)
            else:
                m = np.flatnonzero(exp_ids[:ns] == aid)
                if len(m) != 1:
                    out["lookup_wrong"] += 1
                    continue
                exact = float(ssims[m[0]])
                want, mine = exp_ans[m[0]], ("s", int(m[0]))
            wrong |= not np.array_equal(res.answer[b], want)
            wrong |= exact < rec.theta - tol
            if mine != where:
                early = bool(res.hit.all())
                out["early_accepts"] += int(early)
                wrong |= not (early or abs(exact - best) <= tol)
        else:
            exact = best
            wrong |= best >= rec.theta + tol
        out["lookup_sim"] = max(out["lookup_sim"],
                                abs(float(res.sim[b]) - exact))
        out["lookup_wrong"] += int(wrong)
    return out


def sample_lookups(records: list, rng, n: int) -> list:
    picks = [(rec, b) for rec in records for b in range(len(rec.queries))]
    if len(picks) > n:
        idx = np.sort(rng.choice(len(picks), n, replace=False))
        picks = [picks[i] for i in idx]
    return picks


def sample_requests(reqs: list, rng, tokens: int) -> list:
    """The longest engine-served request, then others drawn from the seed
    until ``tokens`` served tokens are in the sample."""
    if not reqs:
        return []
    order = sorted(range(len(reqs)), key=lambda i: -len(reqs[i].out))
    first, rest = order[0], list(rng.permutation(order[1:]))
    out, total = [reqs[first]], len(reqs[first].out)
    for i in rest:
        if total >= tokens:
            break
        out.append(reqs[int(i)])
        total += len(reqs[int(i)].out)
    return out


def embed_dist(cfg: dict, eparams, picks: list, lowp: bool = False) -> float:
    em = model_module(cfg["encoder"]["program"]["reference"])
    toks = [rec.tokens[b] for rec, b in picks]
    ref = em.encode(eparams, cfg["encoder"], toks, lowp=lowp)
    got = np.stack([rec.queries[b] for rec, b in picks]).astype(np.float64)
    return float(np.max(np.linalg.norm(got - ref, axis=1)))


def token_gaps(cfg: dict, params, reqs: list, lowp: bool = False) -> float:
    mm = model_module(cfg["model"]["program"]["reference"])
    prompts = [np.asarray(r.tokens, np.int32) for r in reqs]
    outs = [np.asarray(r.out, np.int32) for r in reqs]
    fn = mm.control_gaps if lowp else mm.served_gaps
    gaps = fn(params, cfg["model"], prompts, outs,
              cfg["engine"]["max_len"])
    return float(max(g.max() for g in gaps)) if gaps else 0.0


def control_lookup_sim(corpus, spill: tuple, picks: list,
                       precision: str = "high") -> float:
    """The control for ``lookup_sim``: the reference top-1 computed on the
    device at the next precision below the program's (``high``: three
    bf16 passes, where the program states ``highest``), read the same way:
    the largest |control sim - float64 sim of the row it picks|."""
    import jax
    import jax.numpy as jnp
    exp_vec = spill[0]
    q = np.stack([rec.queries[b] for rec, b in picks]).astype(np.float32)
    qd = jnp.asarray(q)
    prec = getattr(jax.lax.Precision, precision.upper())
    mm = jax.jit(lambda a, b: jnp.matmul(a, b.T, precision=prec))
    best = np.full(len(q), -np.inf, np.float32)
    row = np.zeros(len(q), np.int64)
    chunk = 65_536
    for s in range(0, len(corpus.vectors), chunk):
        blk = corpus.vectors[s:s + chunk]
        if len(blk) < chunk:
            blk = np.concatenate([blk, np.zeros((chunk - len(blk),
                                                 blk.shape[1]), np.float32)])
        sims = np.asarray(mm(qd, jnp.asarray(blk)))
        n = min(chunk, len(corpus.vectors) - s)
        i = np.argmax(sims[:, :n], axis=1)
        v = sims[np.arange(len(q)), i]
        better = v > best
        best[better], row[better] = v[better], s + i[better]
    ssims = np.asarray(mm(qd, jnp.asarray(exp_vec))) if len(exp_vec) \
        else np.zeros((len(q), 0), np.float32)
    out = 0.0
    for k, (rec, b) in enumerate(picks):
        qk = q[k].astype(np.float64)
        ns = rec.n_spill
        sv, sr = best[k], ("c", row[k])
        if ns and ssims[k, :ns].max() > sv:
            sv, sr = ssims[k, :ns].max(), ("s", int(ssims[k, :ns].argmax()))
        vec = corpus.vectors[sr[1]] if sr[0] == "c" else exp_vec[sr[1]]
        out = max(out, abs(float(sv) - float(vec.astype(np.float64) @ qk)))
    return out
