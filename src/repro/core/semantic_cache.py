"""The online semantic cache.

Two regions (paper §5.2.5):
  * centroid region — the Algorithm-1-managed centroids (no per-miss
    replacement; refreshed occasionally by the CacheManager);
  * spill region — any remaining capacity caches individual query vectors
    under plain LRU.

Lookup backends:
  * "dense"  — jitted MXU-style top-1 over a padded matrix (TPU-native
               adaptation of the paper's HNSW; exact, recall = 1);
  * "hnsw"   — locality-ordered HNSW (CPU-fidelity path, §4.3);
  * "pallas" — the cosine_topk kernel (interpret mode on CPU);
  * "pallas_q8" — int8 centroid plane with in-kernel dequant and exact
               theta-margin rescoring (DESIGN.md §15): ~4x rows per
               device byte, accept/reject decisions bit-identical to
               "dense".
Entries are ordered by cluster_size (strong semantic locality first), the
tiled analog of SISO's hot-centroids-in-upper-HNSW-levels layout — it gives
the Pallas kernel's early-exit tiles their hit-mass skew.

Device-resident hot path (DESIGN.md §4): the padded centroid/answer
matrices live as persistent ``jax.Array``s. Offline refreshes
(``set_centroids``) rebuild them once; online spill inserts patch single
rows in place with a donated ``dynamic_update_slice`` instead of
re-uploading the whole region. Threshold compare and answer gather are
fused into the jitted top-1, so a batch lookup is one device round trip
and the host does only O(hits) vectorized numpy bookkeeping — no per-hit
Python loop anywhere on the serving path.

Double-buffered refresh (DESIGN.md §10): an in-flight Algorithm-1 refresh
stages its new centroid region into a *shadow* buffer
(``begin_shadow``/``shadow_write``) while the live mirror keeps serving
untouched; ``commit_shadow`` appends the surviving spill rows, uploads
once, and atomically swaps the mirror pointer — the jitted top-1 never
sees an invalidated or half-built matrix. Every mirror swap/rebuild bumps
``generation``, which each LookupResult carries so callers can prove a
batch was served from exactly one buffer.

Sharded cache plane (DESIGN.md §11): with a ``ShardedCacheConfig`` of
``n_shards > 1`` the mirror is row-sharded over a ``cache`` mesh axis
(round-robin owner mapping, pow2-padded per shard). Lookup runs the same
fused theta-compare top-1 shard-locally plus one cross-shard argmax
reduction; spill inserts route to the owner shard; the shadow buffer is
staged directly in per-shard layout and committed with the same single
upload + atomic pointer swap. All host-side bookkeeping (LRU clocks,
access counts, victim selection) is unchanged, so sharded results are
element-wise identical to the 1-device reference; ``n_shards == 1`` keeps
this file's single-device hot path bit-identical.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.core.clustering import _pow2_pad
from repro.core.store import CentroidStore
from repro.distributed.cache_plane import (ShardedCacheConfig,
                                           ShardedDeviceState,
                                           ShardedQuantState, shard_pad)
from repro.kernels.cosine_topk.ops import quantize_rows

# Absolute slack added to the quant rescoring margin (DESIGN.md §15) on
# top of the Cauchy-Schwarz bound ||q|| * err_max: absorbs the f32
# accumulation-order difference between the int8 kernel's tiled matmul
# and the exact bound's real-arithmetic model. Oversizing it never breaks
# exactness — it only widens the candidate window (more rescored rows /
# rare dense fallbacks), so it is set generously.
QUANT_SLACK = 1e-3

# Every f32 lookup contraction runs at full f32 precision. On a TPU v5e the
# default f32 matmul is one bf16 pass: unit-vector sims at dim 768 were off
# by up to 4e-4 against float64, enough to flip hit decisions near theta_R;
# at HIGHEST by 2.5e-8. On CPU, f32 is exact either way.
LOOKUP_PRECISION = jax.lax.Precision.HIGHEST


def _lane_pad(d: int) -> int:
    """Lane-width (128) padded feature dim for device mirrors."""
    return (max(d, 1) + 127) // 128 * 128


@jax.jit
def _fused_top1(queries: jax.Array, mat: jax.Array, ans: jax.Array,
                valid: jax.Array, aid: jax.Array, theta):
    """Top-1 + theta compare + answer gather in one compiled program.

    queries (B, D) x mat (pad, D) -> per query: best sim, its row, the hit
    mask at theta_R, and the gathered answer/answer_id (zero / -1 on miss).
    """
    sims = jnp.matmul(queries, mat.T,
                      precision=LOOKUP_PRECISION)            # (B, pad)
    sims = jnp.where(valid[None, :], sims, -1.0)
    idx = jnp.argmax(sims, axis=1)
    best = jnp.take_along_axis(sims, idx[:, None], axis=1)[:, 0]
    hit = best >= theta
    answer = jnp.where(hit[:, None], ans[idx], 0.0)
    answer_id = jnp.where(hit, aid[idx], -1)
    return hit, best, idx.astype(jnp.int32), answer, answer_id


@jax.jit
def _gather_hits(ans: jax.Array, aid: jax.Array, idx: jax.Array,
                 hit: jax.Array):
    """Answer gather for backends that produce (idx, hit) themselves."""
    safe = jnp.maximum(idx, 0)
    answer = jnp.where(hit[:, None], ans[safe], 0.0)
    answer_id = jnp.where(hit, aid[safe], -1)
    return answer, answer_id


def _write_row_impl(mat, ans, valid, aid, row, vec, answer, answer_id):
    mat = jax.lax.dynamic_update_slice(mat, vec[None, :], (row, 0))
    ans = jax.lax.dynamic_update_slice(ans, answer[None, :], (row, 0))
    valid = valid.at[row].set(True)
    aid = aid.at[row].set(answer_id)
    return mat, ans, valid, aid


# Donation makes the row patch a true in-place update on TPU/GPU; the CPU
# runtime ignores donation (with a warning), so only donate off-CPU.
_write_row_donated = jax.jit(_write_row_impl, donate_argnums=(0, 1, 2, 3))
_write_row_plain = jax.jit(_write_row_impl)


def _write_qrow_impl(codes, scales, valid, row, crow, scale):
    codes = jax.lax.dynamic_update_slice(codes, crow[None, :], (row, 0))
    scales = scales.at[row].set(scale)
    valid = valid.at[row].set(True)
    return codes, scales, valid


_write_qrow_donated = jax.jit(_write_qrow_impl, donate_argnums=(0, 1, 2))
_write_qrow_plain = jax.jit(_write_qrow_impl)


@jax.jit
def _rescore_mm(queries: jax.Array, mat: jax.Array) -> jax.Array:
    """Full-precision similarity block for the quant rescoring pass.

    Must be the exact contraction `_fused_top1` uses (queries @ mat.T on
    device): XLA keeps a row's dot product bitwise independent of which
    *other* rows share the matmul, so rescoring a gathered row subset
    reproduces the f32 reference similarities bit for bit.
    """
    return jnp.matmul(queries, mat.T, precision=LOOKUP_PRECISION)


@dataclass
class _DeviceState:
    """Persistent device-resident mirror of centroid + spill regions."""
    mat: jax.Array      # (pad, dim) float32
    ans: jax.Array      # (pad, answer_dim) float32
    valid: jax.Array    # (pad,) bool
    aid: jax.Array      # (pad,) int32
    pad: int

    @property
    def rows(self) -> int:
        """Addressable rows before the mirror must regrow (matches the
        sharded plane's ``rows`` so insert_spill is layout-agnostic)."""
        return self.pad

    def write_row(self, row: int, vec: np.ndarray, answer: np.ndarray,
                  answer_id: int) -> None:
        fn = _write_row_plain if jax.default_backend() == "cpu" \
            else _write_row_donated
        # jnp.array (copy) — asarray would zero-copy-alias caller numpy
        # buffers that may be mutated while the async write is in flight
        self.mat, self.ans, self.valid, self.aid = fn(
            self.mat, self.ans, self.valid, self.aid,
            jnp.int32(row), jnp.array(vec, jnp.float32),
            jnp.array(answer, jnp.float32), jnp.int32(answer_id))


@dataclass
class _QuantDeviceState:
    """Device mirror for the int8 plane (backend "pallas_q8", DESIGN.md
    §15): per-row symmetric codes + scales, no answer matrix — answers
    stay host-side (gathered per hit), which is where most of the >=2x
    capacity-per-byte comes from on top of the 4x code compression."""
    codes: jax.Array    # (pad, dpad) int8, lane-padded codes
    scales: jax.Array   # (pad,) float32 per-row scales
    valid: jax.Array    # (pad,) bool
    pad: int
    dpad: int
    err_max: float      # running max per-row dequant L2 error (monotone
                        # across row patches; exact after a full rebuild)

    @property
    def rows(self) -> int:
        return self.pad

    def write_row(self, row: int, vec: np.ndarray, answer: np.ndarray,
                  answer_id: int) -> None:
        """Donated in-place spill patch: quantize the row host-side, write
        the code row + scale in one jitted update. ``answer``/``answer_id``
        are ignored — the quant plane never holds answers on device."""
        crow, scale, err = quantize_rows(
            np.asarray(vec, np.float32).reshape(1, -1), width=self.dpad)
        fn = _write_qrow_plain if jax.default_backend() == "cpu" \
            else _write_qrow_donated
        self.codes, self.scales, self.valid = fn(
            self.codes, self.scales, self.valid, jnp.int32(row),
            jnp.array(crow[0]), jnp.float32(scale[0]))
        self.err_max = max(self.err_max, float(err[0]))


@dataclass
class LookupResult:
    hit: np.ndarray        # (B,) bool
    sim: np.ndarray        # (B,) float32 best similarity
    answer: np.ndarray     # (B, answer_dim) float32 (zeros on miss)
    answer_id: np.ndarray  # (B,) int64 (-1 on miss)
    entry: np.ndarray      # (B,) int64 row index (-1 on miss)
    region: np.ndarray     # (B,) int8: 0 centroid, 1 spill, -1 miss
    generation: int = -1   # serving-state generation (DESIGN.md §10);
                           # -1 for frontends without a device mirror
    tiles: Optional[tuple] = None  # (tiles computed, tiles in the grid)
                                   # of the f32 kernel's scan; None for
                                   # the other backends


class SemanticCache:
    def __init__(self, dim: int, answer_dim: int, capacity: int,
                 backend: str = "dense", spill_lru: bool = True,
                 shard: Optional[ShardedCacheConfig] = None,
                 rescore_k: int = 16):
        if backend not in ("dense", "hnsw", "pallas", "pallas_q8"):
            raise ValueError(f"unknown cache backend {backend!r}")
        self.dim = dim
        self.answer_dim = answer_dim
        self.capacity = capacity
        self.backend = backend
        self.spill_lru = spill_lru
        # quant plane (DESIGN.md §15): top-C quant candidates fetched per
        # query for the exact full-precision rescore; larger C lowers the
        # dense-fallback rate, never changes results
        self.rescore_k = rescore_k
        self.quant_rescored = 0     # full-precision rows rescored
        self.quant_fallbacks = 0    # margin-coverage misses -> dense ref
        self._quant_restore: Optional[dict] = None
        # n_shards == 1 deliberately degrades to shard=None: the 1-device
        # mesh path IS the single-device path, bit for bit (DESIGN.md §11)
        self.shard = shard if shard is not None and shard.n_shards > 1 \
            else None
        self._reject_hnsw_shard()
        self.centroids = CentroidStore(dim, answer_dim)
        self.spill = CentroidStore(dim, answer_dim)
        self._spill_clock = 0
        self._spill_last_use: np.ndarray = np.zeros((0,), np.int64)
        self._dev: Optional[_DeviceState] = None
        self._hnsw = None
        self.hits = 0
        self.misses = 0
        # observability: how many times the device mirror was rebuilt from
        # scratch vs patched in place (bench_gateway reads these); dev_swaps
        # counts double-buffered refresh commits (DESIGN.md §10)
        self.dev_rebuilds = 0
        self.dev_row_writes = 0
        self.dev_swaps = 0
        # bumped whenever a NEW device state starts serving (rebuild or
        # shadow swap): lookups stamp it into LookupResult.generation
        self.generation = 0
        # generation the HNSW fallback index was built at — guarded
        # against the device mirror's generation at every graph lookup
        self._hnsw_gen = 0
        self._shadow: Optional[dict] = None
        # set by load_state: the next mirror (re)build reproduces the
        # snapshot's serving state, so it must NOT advance the generation
        # (restored lookups stay element-wise identical to an
        # uninterrupted run, DESIGN.md §12)
        self._restore_pending = False
        # demotion tap (DESIGN.md §13): when set, every evicted entry
        # (spill LRU victim, spill trim, Algorithm-1 filter eviction) is
        # handed to the sink as
        #   sink(vectors, answers, answer_id, cluster_size, access_count,
        #        kind)
        # instead of being silently discarded. None (the default) keeps
        # every eviction path bit-identical to the single-tier behavior.
        self.evict_sink = None
        # multi-tenant fair-share eviction (DESIGN.md §14): when both are
        # set (SISO wires them from its TenancyConfig), spill victim
        # selection charges each row to its owning namespace — resolved
        # from answer_id through ``tenant_of`` — and evicts from the
        # most-over-budget namespace first. Defaults keep the unweighted
        # LRU path bit-identical.
        self.fair_share_eviction = False
        self.tenant_of = None     # answer_ids -> tenants, or None

    def _reject_hnsw_shard(self) -> None:
        """The hnsw backend serves from a host graph and would silently
        ignore a sharded device plane. Checked at construction AND at
        every graph lookup — the serving-time check catches configs that
        reach the hnsw branch through post-construction mutation, which
        the constructor guard alone let fall through silently."""
        if self.shard is not None and self.backend == "hnsw":
            raise ValueError("sharded cache plane needs a device-resident "
                             "backend (dense/pallas); hnsw is host-graph")

    # ----------------------------------------------------------------- state

    @property
    def spill_capacity(self) -> int:
        return max(0, self.capacity - len(self.centroids))

    def set_centroids(self, store: CentroidStore) -> None:
        order = np.argsort(-store.cluster_size, kind="stable")
        store = store.copy()
        store.take(order)  # locality-first layout
        self.centroids = store
        self._trim_spill()
        self._restore_pending = False   # a real new state supersedes restore
        self._quant_restore = None
        self._invalidate()

    def _trim_spill(self) -> None:
        """LRU-evict spill rows that no longer fit the leftover capacity
        (shared by the blocking set_centroids and the double-buffered
        commit_shadow so both refresh paths trim identically)."""
        if len(self.spill) > self.spill_capacity:  # spill shrank
            drop = len(self.spill) - self.spill_capacity
            if self.fair_share_eviction and self.tenant_of is not None:
                # tenant-weighted trim (DESIGN.md §14): over-budget
                # namespaces give up rows first, LRU within each
                from repro.core.tenancy import fair_share_take
                victims = fair_share_take(
                    self.tenant_of(self.spill.answer_id),
                    self._spill_last_use, drop)
            else:
                victims = np.argsort(self._spill_last_use)[:drop]
            dead = None
            if self.evict_sink is not None:
                rows = np.sort(victims)
                dead = (self.spill.vectors[rows].copy(),
                        self.spill.answers[rows].copy(),
                        self.spill.answer_id[rows].copy(),
                        self.spill.cluster_size[rows].copy(),
                        self.spill.access_count[rows].copy())
            keep = np.setdiff1d(np.arange(len(self.spill)), victims)
            self.spill.take(keep)
            self._spill_last_use = self._spill_last_use[keep]
            if dead is not None:    # sink fires after the rows left
                self.evict_sink(*dead, "spill_trim")

    def drop_spill_ids(self, answer_ids: np.ndarray) -> int:
        """Remove spill rows whose answer identity (>= 0) appears in
        ``answer_ids``. The tiered wrapper calls this right before a
        refresh commit: a logged answer promoted into the new centroid
        region must not keep a second live copy in its spill staging row
        (DESIGN.md §13 one-copy-per-identity). Invalidates the device
        mirror — callers run it immediately before a commit that rebuilds
        or swaps the mirror anyway, so no extra upload happens."""
        ids = np.asarray(answer_ids)
        ids = ids[ids >= 0]
        if not len(ids) or not len(self.spill):
            return 0
        dup = np.isin(self.spill.answer_id, ids)
        n = int(dup.sum())
        if n:
            keep = np.where(~dup)[0]
            self.spill.take(keep)
            self._spill_last_use = self._spill_last_use[keep]
            self._quant_restore = None
            self._invalidate()
        return n

    def apply_chunk(self, chunk: CentroidStore, first: bool) -> None:
        """Progressive update entry point (CacheManager.update_chunks)."""
        if first:
            self._staging = CentroidStore(self.dim, self.answer_dim)
        self._staging.add(chunk.vectors, chunk.answers, chunk.cluster_size,
                          chunk.access_count, chunk.answer_id)

    def finish_update(self) -> None:
        self.set_centroids(self._staging)
        del self._staging

    def _invalidate(self):
        """Full invalidation: only the offline refresh path (centroid set
        replaced) and state restore call this. Online spill inserts patch
        the device mirror in place instead."""
        self._dev = None
        self._hnsw = None

    # ---------------------------------------------------------------- device

    def _bump_generation(self) -> None:
        """A mirror/index rebuild normally starts a NEW serving state —
        except the one rebuild that re-materializes a restored snapshot,
        which must reproduce the snapshot's generation exactly."""
        if self._restore_pending:
            self._restore_pending = False
        else:
            self.generation += 1

    @property
    def _mat_width(self) -> int:
        """Feature width of the f32 device mirror. The pallas backend
        stores the mirror lane-padded (multiple of 128) so the kernel's
        pre-padded fast path applies — zero columns beyond ``dim``
        contribute exactly 0.0 to every dot product, so results are
        bit-identical to the unpadded layout."""
        return _lane_pad(self.dim) if self.backend == "pallas" else self.dim

    def _quantize_all(self, vecs: np.ndarray) -> tuple:
        """(codes, scales, err_max) for the full host row set, honoring a
        pending snapshot restore (codes+scales round-trip the snapshot so
        a warm restart serves from the very same quantized plane)."""
        n = len(vecs)
        dpad = _lane_pad(self.dim)
        restore, self._quant_restore = self._quant_restore, None
        if restore is not None:
            codes = np.asarray(restore["codes"], np.int8)
            scales = np.asarray(restore["scales"], np.float32)
            if len(codes) == n and codes.shape[1] == dpad \
                    and len(scales) == n:
                return codes, scales, float(restore["err_max"])
        codes, scales, err = quantize_rows(vecs, width=dpad)
        return codes, scales, float(err.max()) if n else 0.0

    def _device_state(self):
        if self._dev is None:
            nc = len(self.centroids)
            n = nc + len(self.spill)

            def cat(attr):
                a = getattr(self.centroids, attr)
                return a if not len(self.spill) else \
                    np.concatenate([a, getattr(self.spill, attr)])

            if self.backend == "pallas_q8":   # int8 plane (DESIGN.md §15)
                codes, scales, err_max = self._quantize_all(
                    cat("vectors").reshape(n, self.dim))
                dpad = _lane_pad(self.dim)
                if self.shard is not None:
                    self._dev = ShardedQuantState.build(
                        self.shard.make_mesh(), self.shard.n_shards,
                        codes, scales, err_max=err_max,
                        pad_floor=max(self.shard.pad_floor, 128))
                else:
                    pad = _pow2_pad(n)
                    cp = np.zeros((pad, dpad), np.int8)
                    sp = np.zeros((pad,), np.float32)
                    valid = np.zeros((pad,), bool)
                    cp[:n], sp[:n], valid[:n] = codes, scales, True
                    self._dev = _QuantDeviceState(
                        jnp.asarray(cp), jnp.asarray(sp),
                        jnp.asarray(valid), pad, dpad, err_max)
                self.dev_rebuilds += 1
                self._bump_generation()
                return self._dev
            if self.shard is not None:   # mesh plane (DESIGN.md §11)
                self._dev = ShardedDeviceState.build(
                    self.shard.make_mesh(), self.shard.n_shards,
                    cat("vectors").reshape(n, self.dim),
                    cat("answers").reshape(n, self.answer_dim),
                    cat("answer_id"), pad_floor=self.shard.pad_floor,
                    backend=self.backend)
                self.dev_rebuilds += 1
                self._bump_generation()
                return self._dev
            pad = _pow2_pad(n)
            mat = np.zeros((pad, self._mat_width), np.float32)
            ans = np.zeros((pad, self.answer_dim), np.float32)
            valid = np.zeros((pad,), bool)
            aid = np.full((pad,), -1, np.int32)
            if nc:
                mat[:nc, :self.dim] = self.centroids.vectors
                ans[:nc] = self.centroids.answers
                aid[:nc] = self.centroids.answer_id
            if len(self.spill):
                mat[nc:n, :self.dim] = self.spill.vectors
                ans[nc:n] = self.spill.answers
                aid[nc:n] = self.spill.answer_id
            valid[:n] = True
            self._dev = _DeviceState(jnp.asarray(mat), jnp.asarray(ans),
                                     jnp.asarray(valid), jnp.asarray(aid),
                                     pad)
            self.dev_rebuilds += 1
            self._bump_generation()
        return self._dev

    # --------------------------------------------- double-buffered refresh

    def begin_shadow(self, n_new: int) -> None:
        """Open the shadow buffer for a refresh in flight (DESIGN.md §10).

        The new centroid region (n_new rows, final locality-sorted order)
        is staged here chunk by chunk via :meth:`shadow_write` while the
        live device mirror keeps serving; one :meth:`commit_shadow` makes
        it live. Sized with headroom for the spill rows that survive the
        swap (regrown at commit if spill outgrew it meanwhile).

        Sharded plane: the staging buffers are allocated directly in the
        per-shard (S, pad, ...) owner layout, so every staged chunk is
        already routed to its owner shard and the commit upload is one
        shard-local transfer per shard (DESIGN.md §11)."""
        keep_spill = min(len(self.spill), max(0, self.capacity - n_new))
        if self.backend == "pallas_q8":
            # quant staging (DESIGN.md §15): codes + scales are built in
            # the same host buffers and committed in the same single
            # upload + atomic pointer swap as the f32 mirror; no answer
            # matrix is staged (answers never live on the quant device)
            dpad = _lane_pad(self.dim)
            if self.shard is not None:
                S = self.shard.n_shards
                pad = shard_pad(n_new + keep_spill, S,
                                max(self.shard.pad_floor, 128))
                self._shadow = {
                    "codes": np.zeros((S, pad, dpad), np.int8),
                    "scales": np.zeros((S, pad), np.float32),
                    "valid": np.zeros((S, pad), bool),
                    "err_max": 0.0, "n_new": n_new, "filled": 0}
                return
            pad = _pow2_pad(n_new + keep_spill)
            self._shadow = {
                "codes": np.zeros((pad, dpad), np.int8),
                "scales": np.zeros((pad,), np.float32),
                "valid": np.zeros((pad,), bool),
                "err_max": 0.0, "n_new": n_new, "filled": 0}
            return
        if self.shard is not None:
            S = self.shard.n_shards
            pad = shard_pad(n_new + keep_spill, S, self.shard.pad_floor)
            self._shadow = {
                "mat": np.zeros((S, pad, self.dim), np.float32),
                "ans": np.zeros((S, pad, self.answer_dim), np.float32),
                "valid": np.zeros((S, pad), bool),
                "aid": np.full((S, pad), -1, np.int32),
                "n_new": n_new, "filled": 0}
            return
        pad = _pow2_pad(n_new + keep_spill)
        self._shadow = {
            "mat": np.zeros((pad, self._mat_width), np.float32),
            "ans": np.zeros((pad, self.answer_dim), np.float32),
            "valid": np.zeros((pad,), bool),
            "aid": np.full((pad,), -1, np.int32),
            "n_new": n_new, "filled": 0}

    def _shadow_scatter(self, rows: np.ndarray, vectors: np.ndarray,
                        answers: np.ndarray, answer_id: np.ndarray) -> None:
        """Scatter host rows into the per-shard staging layout (vectorized
        owner routing: shard r % S, local row r // S)."""
        sh, S = self._shadow, self.shard.n_shards
        s, l = rows % S, rows // S
        sh["mat"][s, l] = vectors
        sh["ans"][s, l] = answers
        sh["aid"][s, l] = answer_id
        sh["valid"][s, l] = True

    def shadow_write(self, vectors: np.ndarray, answers: np.ndarray,
                     answer_id: np.ndarray) -> None:
        """Stage one bounded chunk of the new centroid region (host-side
        memcpy — the live mirror is untouched)."""
        sh = self._shadow
        s, k = sh["filled"], len(vectors)
        if self.backend == "pallas_q8":
            codes, scales, err = quantize_rows(
                np.asarray(vectors, np.float32).reshape(k, self.dim),
                width=_lane_pad(self.dim))
            if len(err):
                sh["err_max"] = max(sh["err_max"], float(err.max()))
            if self.shard is not None:
                rows = np.arange(s, s + k)
                S = self.shard.n_shards
                sd, l = rows % S, rows // S
                sh["codes"][sd, l] = codes
                sh["scales"][sd, l] = scales
                sh["valid"][sd, l] = True
            else:
                sh["codes"][s:s + k] = codes
                sh["scales"][s:s + k] = scales
                sh["valid"][s:s + k] = True
        elif self.shard is not None:
            self._shadow_scatter(np.arange(s, s + k), vectors, answers,
                                 answer_id)
        else:
            sh["mat"][s:s + k, :self.dim] = vectors
            sh["ans"][s:s + k] = answers
            sh["aid"][s:s + k] = answer_id
            sh["valid"][s:s + k] = True
        sh["filled"] = s + k

    def commit_shadow(self, store: CentroidStore) -> None:
        """Atomic swap ending a double-buffered refresh.

        ``store`` must be the full new centroid region in final
        locality-sorted order, with every row already staged through
        :meth:`shadow_write`. Installs the store, LRU-trims the spill to
        the new leftover capacity, appends the surviving spill rows, then
        uploads once and swaps the mirror pointer — lookups either see the
        complete old generation or the complete new one, never a partial
        rebuild."""
        sh = self._shadow
        if sh is None or sh["filled"] != sh["n_new"] \
                or sh["n_new"] != len(store):
            raise ValueError("commit_shadow: shadow incomplete or store "
                             "size mismatch")
        self.centroids = store
        self._trim_spill()
        nc, ns = len(store), len(self.spill)
        need = nc + ns
        if self.backend == "pallas_q8":
            self._commit_shadow_q8(nc, ns, need)
        elif self.shard is not None:
            self._commit_shadow_sharded(nc, ns, need)
        else:
            mat, ans, valid, aid = (sh["mat"], sh["ans"], sh["valid"],
                                    sh["aid"])
            if need > len(mat):  # spill grew past the headroom: regrow
                pad = _pow2_pad(need)
                mat2 = np.zeros((pad, self._mat_width), np.float32)
                ans2 = np.zeros((pad, self.answer_dim), np.float32)
                valid2 = np.zeros((pad,), bool)
                aid2 = np.full((pad,), -1, np.int32)
                mat2[:nc], ans2[:nc] = mat[:nc], ans[:nc]
                valid2[:nc], aid2[:nc] = valid[:nc], aid[:nc]
                mat, ans, valid, aid = mat2, ans2, valid2, aid2
            if ns:
                mat[nc:need, :self.dim] = self.spill.vectors
                ans[nc:need] = self.spill.answers
                aid[nc:need] = self.spill.answer_id
                valid[nc:need] = True
            self._dev = _DeviceState(jnp.asarray(mat), jnp.asarray(ans),
                                     jnp.asarray(valid), jnp.asarray(aid),
                                     len(mat))
        self._hnsw = None        # graph path stays rebuild-based
        self._shadow = None
        self._restore_pending = False   # a real new state supersedes restore
        self._quant_restore = None
        self.generation += 1
        self.dev_swaps += 1

    def _commit_shadow_sharded(self, nc: int, ns: int, need: int) -> None:
        """Sharded tail of :meth:`commit_shadow`: append surviving spill
        rows to their owner shards, then one shard-local upload per shard
        + the same atomic pointer swap (DESIGN.md §11)."""
        sh, S = self._shadow, self.shard.n_shards
        if shard_pad(need, S, self.shard.pad_floor) > sh["mat"].shape[1]:
            pad = shard_pad(need, S, self.shard.pad_floor)   # regrow
            old = sh["mat"].shape[1]
            for key, fill in (("mat", 0), ("ans", 0), ("valid", False),
                              ("aid", -1)):
                grown = np.full((S, pad) + sh[key].shape[2:], fill,
                                sh[key].dtype)
                grown[:, :old] = sh[key]
                sh[key] = grown
        if ns:
            self._shadow_scatter(np.arange(nc, need), self.spill.vectors,
                                 self.spill.answers, self.spill.answer_id)
        self._dev = ShardedDeviceState.from_shard_layout(
            self.shard.make_mesh(), S, sh["mat"], sh["ans"], sh["valid"],
            sh["aid"], backend=self.backend)

    def _commit_shadow_q8(self, nc: int, ns: int, need: int) -> None:
        """Quant tail of :meth:`commit_shadow`: quantize the surviving
        spill rows into the staged codes/scales, regrow if the spill
        outgrew the headroom, then the same one-upload atomic swap."""
        sh = self._shadow
        dpad = _lane_pad(self.dim)
        if self.shard is not None:
            S = self.shard.n_shards
            floor = max(self.shard.pad_floor, 128)
            if shard_pad(need, S, floor) > sh["codes"].shape[1]:
                pad = shard_pad(need, S, floor)
                old = sh["codes"].shape[1]
                for key, fill in (("codes", 0), ("scales", 0.0),
                                  ("valid", False)):
                    grown = np.full((S, pad) + sh[key].shape[2:], fill,
                                    sh[key].dtype)
                    grown[:, :old] = sh[key]
                    sh[key] = grown
            if ns:
                codes, scales, err = quantize_rows(self.spill.vectors,
                                                   width=dpad)
                if len(err):
                    sh["err_max"] = max(sh["err_max"], float(err.max()))
                rows = np.arange(nc, need)
                sd, l = rows % S, rows // S
                sh["codes"][sd, l] = codes
                sh["scales"][sd, l] = scales
                sh["valid"][sd, l] = True
            self._dev = ShardedQuantState.from_shard_layout(
                self.shard.make_mesh(), S, sh["codes"], sh["scales"],
                sh["valid"], err_max=sh["err_max"])
            return
        codes, scales, valid = sh["codes"], sh["scales"], sh["valid"]
        if need > len(codes):   # spill grew past the headroom: regrow
            pad = _pow2_pad(need)
            codes2 = np.zeros((pad, dpad), np.int8)
            scales2 = np.zeros((pad,), np.float32)
            valid2 = np.zeros((pad,), bool)
            codes2[:nc], scales2[:nc] = codes[:nc], scales[:nc]
            valid2[:nc] = valid[:nc]
            codes, scales, valid = codes2, scales2, valid2
        if ns:
            sc, ss, err = quantize_rows(self.spill.vectors, width=dpad)
            if len(err):
                sh["err_max"] = max(sh["err_max"], float(err.max()))
            codes[nc:need], scales[nc:need] = sc, ss
            valid[nc:need] = True
        self._dev = _QuantDeviceState(jnp.asarray(codes),
                                      jnp.asarray(scales),
                                      jnp.asarray(valid), len(codes), dpad,
                                      sh["err_max"])

    # ---------------------------------------------------------------- lookup

    def lookup(self, queries: np.ndarray, theta_r: float,
               update_counts: bool = True) -> LookupResult:
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        B = len(queries)
        nc = len(self.centroids)
        n = nc + len(self.spill)
        if n == 0:
            if update_counts:
                self.misses += B
            return LookupResult(np.zeros(B, bool), np.full(B, -1.0, np.float32),
                                np.zeros((B, self.answer_dim), np.float32),
                                np.full(B, -1, np.int64),
                                np.full(B, -1, np.int64),
                                np.full(B, -1, np.int8),
                                generation=self.generation)
        tiles = None
        if self.backend == "hnsw":
            sims, idx = self._hnsw_lookup(queries)
            hit = sims >= theta_r
            answer, answer_id = self._host_gather(hit, idx, nc, B)
        else:
            with trace.span("lookup.scan", n=B):
                hit, sims, idx, answer, answer_id, tiles = \
                    self._device_lookup(queries, theta_r, nc, B)
        idx = np.asarray(idx, np.int64)
        region = np.where(~hit, -1, np.where(idx < nc, 0, 1)).astype(np.int8)
        if update_counts:
            # batched bookkeeping — O(hits) numpy, no Python loop
            cent_rows = idx[hit & (idx < nc)]
            if len(cent_rows):
                np.add.at(self.centroids.access_count, cent_rows, 1.0)
            spill_rows = idx[hit & (idx >= nc)] - nc
            if len(spill_rows):
                # per-hit clock ticks in batch order (duplicates keep the
                # latest tick, same as the sequential loop would)
                self._spill_last_use[spill_rows] = \
                    self._spill_clock + 1 + np.arange(len(spill_rows))
                self._spill_clock += len(spill_rows)
            self.hits += int(hit.sum())
            self.misses += int(B - hit.sum())
        entry = np.where(hit, idx, -1).astype(np.int64)
        return LookupResult(hit, sims.astype(np.float32), answer, answer_id,
                            entry, region, generation=self.generation,
                            tiles=tiles)

    def _device_lookup(self, queries: np.ndarray, theta_r: float, nc: int,
                       B: int) -> tuple:
        """The device backends' lookup: (hit, sims, idx, answer, answer_id,
        tiles), each a host array (tiles: see LookupResult)."""
        if self.backend == "pallas_q8":
            # int8 plane (DESIGN.md §15): fused dequant-cosine top-C on
            # device, exact margin rescore host-driven; answers are host
            # resident — the same vectorized gather the hnsw path uses
            sims, idx = self._quant_lookup(queries, theta_r)
            # f32-exact compare: the device reference compares f32 sims
            # against f32(theta), so the host must too (a float64 theta
            # can sit strictly between a sim and its f32 rounding)
            hit = sims >= np.float32(theta_r)
            answer, answer_id = self._host_gather(hit, idx, nc, B)
            return hit, sims, idx, answer, answer_id, None
        tiles = None
        if self.shard is not None:
            # mesh plane: shard-local fused top-1 + cross-shard argmax
            # (dense or pallas shard-local compute — DESIGN.md §11)
            out = self._device_state().lookup(queries, theta_r)
        elif self.backend == "pallas":
            from repro.kernels.cosine_topk import ops as ctk_ops
            dev = self._device_state()
            # early-accept only for real serving thresholds: probe lookups
            # (T2HTable.build passes theta_r=-1.0) need exact top-1 sims,
            # and with theta <= 0 every row clears the bar after tile 0.
            s, i, h, t = ctk_ops.cosine_topk(
                jnp.asarray(queries), dev.mat, k=1,
                valid=dev.valid, theta=theta_r,
                early_exit=bool(theta_r > 0), return_hit=True,
                return_tiles=True)
            a, ai = _gather_hits(dev.ans, dev.aid, i[:, 0], h)
            out, tiles = (h, s[:, 0], i[:, 0], a, ai), t
        else:
            dev = self._device_state()
            out = _fused_top1(jnp.asarray(queries), dev.mat, dev.ans,
                              dev.valid, dev.aid, theta_r)
        with trace.span("lookup.wait"):
            out, tiles = jax.device_get((out, tiles))
        hit, sims, idx, answer, answer_id = (np.array(x) for x in out)
        if tiles is not None:
            tiles = (int(tiles[0]), int(tiles[1]))
        return hit, sims, idx, answer, answer_id.astype(np.int64), tiles

    def _quant_lookup(self, queries: np.ndarray, theta_r: float
                      ) -> tuple[np.ndarray, np.ndarray]:
        """Quantized top-1 with exact rescoring (DESIGN.md §15).

        Device pass: fused int8 dequant-cosine top-C (C = rescore_k) per
        query — shard-local + slim (sim, host_row) all-gather when
        sharded. Host pass: margin-coverage check, then one f32 matmul
        over the union of candidate rows reproduces the reference
        similarities bit for bit (see _rescore_mm). Returns ((B,) exact
        best sims f32, (B,) best rows int64) with reference (first-max)
        tie-breaking, element-wise identical to the dense f32 backend.
        """
        dev = self._device_state()
        if isinstance(dev, ShardedQuantState):
            C = min(self.rescore_k, dev.pad)
            s3, r3 = dev.candidates(queries, C)       # (B, S, C) np
            cand_s = s3.reshape(len(queries), -1)
            cand_r = r3.reshape(len(queries), -1)
            kth = s3[:, :, -1]                        # per-shard C-th sim
        else:
            from repro.kernels.cosine_topk import ops as ctk_ops
            C = min(self.rescore_k, dev.rows)
            s, i = ctk_ops.cosine_topk_q8(
                jnp.asarray(queries), dev.codes, dev.scales, k=C,
                valid=dev.valid, theta=theta_r, early_exit=False)
            with trace.span("lookup.wait"):
                cand_s, cand_r = (np.array(x)
                                  for x in jax.device_get((s, i)))
            kth = cand_s[:, -1:]
        return self._rescore_exact(queries, cand_s, cand_r, kth,
                                   dev.err_max)

    def _rescore_exact(self, queries: np.ndarray, cand_s: np.ndarray,
                       cand_r: np.ndarray, kth: np.ndarray,
                       err_max: float) -> tuple[np.ndarray, np.ndarray]:
        """Exact top-1 from quantized candidates (proof in DESIGN.md §15).

        Per query, quant sims deviate from the exact f32 sims by at most
        eps = err_max * ||q||_2 (+ slack for f32 accumulation). If the
        C-th candidate sim sits strictly below (max candidate - 2*eps),
        every row tied at the true best must already be a candidate and
        every non-candidate row is strictly below it — so one f32 rescore
        over the candidate-row union, argmax with first-max (lowest-row)
        tie-breaking, IS the reference answer. Queries whose margin
        window isn't covered (rare: near-ties deeper than C) fall back to
        the dense f32 reference, which is exact by construction.
        """
        with trace.span("lookup.rescore", n=len(queries)):
            B = len(queries)
            qn = np.linalg.norm(queries.astype(np.float64), axis=1)
            eps = err_max * qn + QUANT_SLACK                     # (B,)
            finite = np.isfinite(cand_s)
            m = np.max(np.where(finite, cand_s, -np.inf), axis=1,
                       initial=-np.inf)
            # covered: per (query, shard-window) either the window was
            # exhausted (C-th is -inf) or its C-th quant sim is strictly
            # below the safe bar — no candidate can be missing
            bar = (m - 2.0 * eps)[:, None]
            covered = ((~np.isfinite(kth)) | (kth < bar)).all(axis=1)
            if not covered.all():
                self.quant_fallbacks += 1
                return self._dense_reference_lookup(queries)
            rows = np.unique(cand_r[finite].astype(np.int64))    # sorted asc
            if not len(rows):                                    # B == 0
                return (np.full(B, -1.0, np.float32),
                        np.zeros(B, np.int64))
            self.quant_rescored += int(len(rows))
            nc = len(self.centroids)
            n = nc + len(self.spill)
            # Scatter the fetched rows at their original positions inside a
            # zero matrix of the REFERENCE shape (_pow2_pad(n) rows — the
            # dense mirror's padding rule). XLA CPU's contraction blocking
            # (and hence the f32 reduction order) depends on the operand
            # shape: a compacted (U, D) submatrix can differ from the full
            # matmul in the last ulp on some hosts. Same shape + same row
            # position == the reference computation with non-candidate rows
            # zeroed, bit for bit.
            vecs = np.zeros((_pow2_pad(n), self.dim), np.float32)
            c_rows = rows < nc
            if c_rows.any():
                vecs[rows[c_rows]] = self.centroids.vectors[rows[c_rows]]
            if (~c_rows).any():
                vecs[rows[~c_rows]] = self.spill.vectors[rows[~c_rows] - nc]
            sims = _rescore_mm(jnp.asarray(queries), jnp.asarray(vecs))
            with trace.span("lookup.rescore.wait"):
                sims = np.asarray(sims)[:, rows]                 # (B, U)
            pos = np.argmax(sims, axis=1)        # first max -> lowest row
            best = sims[np.arange(B), pos]
            return best.astype(np.float32), rows[pos]

    def _dense_reference_lookup(self, queries: np.ndarray
                                ) -> tuple[np.ndarray, np.ndarray]:
        """Margin-coverage fallback: materialize the full f32 row set and
        run the reference contraction on device — bitwise the dense
        backend's answer, at dense-backend cost (counted, rare)."""
        nc = len(self.centroids)
        n = nc + len(self.spill)
        # reference shape (see _rescore_exact): pad rows are zero and
        # excluded from the argmax by the [:, :n] slice
        vecs = np.zeros((_pow2_pad(n), self.dim), np.float32)
        vecs[:nc] = self.centroids.vectors
        if len(self.spill):
            vecs[nc:n] = self.spill.vectors
        sims = np.asarray(_rescore_mm(jnp.asarray(queries),
                                      jnp.asarray(vecs)))[:, :n]
        pos = np.argmax(sims, axis=1)
        best = sims[np.arange(len(queries)), pos]
        return best.astype(np.float32), pos.astype(np.int64)

    def _host_gather(self, hit: np.ndarray, idx: np.ndarray, nc: int,
                     B: int) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized host-side answer gather (hnsw + quant backends)."""
        answer = np.zeros((B, self.answer_dim), np.float32)
        answer_id = np.full(B, -1, np.int64)
        hc = hit & (idx < nc)
        hs = hit & (idx >= nc)
        if hc.any():
            answer[hc] = self.centroids.answers[idx[hc]]
            answer_id[hc] = self.centroids.answer_id[idx[hc]]
        if hs.any():
            sj = idx[hs] - nc
            answer[hs] = self.spill.answers[sj]
            answer_id[hs] = self.spill.answer_id[sj]
        return answer, answer_id

    def _hnsw_lookup(self, queries: np.ndarray):
        from repro.core.hnsw import HNSW
        self._reject_hnsw_shard()   # serving-time guard, not just __init__
        if self._hnsw is None:
            vecs = np.concatenate([self.centroids.vectors, self.spill.vectors]) \
                if len(self.spill) else self.centroids.vectors
            size = np.concatenate([self.centroids.cluster_size,
                                   np.zeros(len(self.spill))]) \
                if len(self.spill) else self.centroids.cluster_size
            self._hnsw = HNSW.build(vecs, locality=size)
            if self._dev is None:
                # pure graph serving: an index rebuild IS a new serving
                # state, so bump the generation exactly like a device
                # mirror rebuild would — LookupResult.generation then
                # tracks refreshes instead of reporting a stale counter
                # (unless this rebuild re-materializes a restored snapshot)
                self._bump_generation()
            self._hnsw_gen = self.generation
        if self._hnsw_gen != self.generation:
            # a device rebuild/shadow swap advanced the serving state
            # without invalidating the graph — serving from it would mix
            # generations mid-refresh
            raise RuntimeError(
                f"HNSW index generation {self._hnsw_gen} is stale vs "
                f"serving generation {self.generation}")
        return self._hnsw.search_batch(queries, k=1)

    # ----------------------------------------------------------------- spill

    def insert_spill(self, vector: np.ndarray, answer: np.ndarray,
                     answer_id: int = -1, cluster_size: float = 1.0) -> None:
        """LRU insert of an individual query vector into free space.

        The device mirror is patched in place (one donated row write); a
        full rebuild only happens when the padded matrix must grow, which
        pow2 sizing makes O(log capacity) times over the cache lifetime.
        ``cluster_size`` defaults to 1 (an individual vector); the tiered
        promotion path passes the entry's real locality weight through so
        a later demotion keeps it (DESIGN.md §13).
        """
        if not self.spill_lru or self.spill_capacity == 0:
            return
        nc = len(self.centroids)
        self._quant_restore = None   # snapshot codes no longer match
        self._spill_clock += 1
        if len(self.spill) >= self.spill_capacity:
            if self.fair_share_eviction and self.tenant_of is not None:
                # fair-share victim (DESIGN.md §14): charge the incoming
                # row to its namespace, then evict from the largest-
                # occupancy namespace (its own LRU row) — a flooding
                # tenant consumes its own rows first
                from repro.core.tenancy import fair_share_take
                incoming = int(self.tenant_of(
                    np.asarray([answer_id], np.int64))[0])
                victim = int(fair_share_take(
                    self.tenant_of(self.spill.answer_id),
                    self._spill_last_use, 1, incoming=incoming)[0])
            else:
                victim = int(np.argmin(self._spill_last_use))
            # copies: set_row overwrites these slots in place below; the
            # sink fires only AFTER the row left the device so a tiered
            # sink sees a consistent "not in device anymore" view
            dead = (self.spill.vectors[victim:victim + 1].copy(),
                    self.spill.answers[victim:victim + 1].copy(),
                    self.spill.answer_id[victim:victim + 1].copy(),
                    self.spill.cluster_size[victim:victim + 1].copy(),
                    self.spill.access_count[victim:victim + 1].copy()) \
                if self.evict_sink is not None else None
            self.spill.set_row(victim, vector, answer, answer_id,
                               cluster_size=cluster_size)
            self._spill_last_use[victim] = self._spill_clock
            if dead is not None:
                self.evict_sink(*dead, "spill_evict")
            row = nc + victim
        else:
            self.spill.add(vector, answer, cluster_size,
                           answer_id=answer_id)
            self._spill_last_use = np.append(self._spill_last_use,
                                             self._spill_clock)
            row = nc + len(self.spill) - 1
        if self._dev is not None:
            if row < self._dev.rows:    # owner-shard routed when sharded
                self._dev.write_row(row, vector, answer, answer_id)
                self.dev_row_writes += 1
            else:               # outgrew the padding: rebuild (pow2 growth)
                self._dev = None
        self._hnsw = None       # graph path stays rebuild-based

    def update_spill_row(self, row: int, vector: np.ndarray,
                         answer: np.ndarray) -> None:
        """In-place overwrite of a live spill row's vector + answer,
        keeping its answer identity and LRU recency (newest-answer-wins
        replication merge, DESIGN.md §16). Recency deliberately does NOT
        move: a peer's answer refresh is not a local access, and bumping
        it would let replication traffic distort the local LRU order.
        The device mirror gets the same donated single-row patch as
        ``insert_spill``."""
        vector = np.asarray(vector, np.float32)
        answer = np.asarray(answer, np.float32)
        self._quant_restore = None   # snapshot codes no longer match
        self.spill.vectors[row] = vector
        self.spill.answers[row] = answer
        drow = len(self.centroids) + row
        if self._dev is not None:
            if drow < self._dev.rows:
                self._dev.write_row(drow, vector, answer,
                                    int(self.spill.answer_id[row]))
                self.dev_row_writes += 1
            else:
                self._dev = None
        self._hnsw = None

    def merge_access(self, ids: np.ndarray, access: np.ndarray) -> int:
        """Fold a peer's centroid access counts into ours by per-id max
        (replication merge policy, DESIGN.md §16). Operates on the id
        intersection only — after a same-epoch check the regions are
        normally identical, but a row evicted locally just stays absent.
        Access counts live host-side only, so no mirror invalidation.
        Returns the number of rows whose count was raised."""
        ids = np.asarray(ids, np.int64)
        access = np.asarray(access, np.float64)
        if not len(ids) or not len(self.centroids):
            return 0
        order = np.argsort(self.centroids.ids, kind="stable")
        sorted_ids = self.centroids.ids[order]
        loc = np.minimum(np.searchsorted(sorted_ids, ids),
                         len(sorted_ids) - 1)
        present = sorted_ids[loc] == ids
        rows = order[loc[present]]
        if not len(rows):
            return 0
        peer = access[present]
        raised = peer > self.centroids.access_count[rows]
        self.centroids.access_count[rows[raised]] = peer[raised]
        return int(raised.sum())

    # --------------------------------------------------------------- metrics

    @property
    def hit_ratio(self) -> float:
        t = self.hits + self.misses
        return self.hits / t if t else 0.0

    def layout_dict(self) -> dict:
        """Device-mirror layout descriptor (DESIGN.md §11/§12): how the
        host rows are placed on the accelerator plane. Informational in a
        snapshot — a restore may legally re-shard (the owner mapping is a
        pure function of (row, n_shards), and lookups are shard-count
        invariant), so the saved layout documents the dead process's
        plane rather than constraining the new one."""
        S = self.shard.n_shards if self.shard is not None else 1
        if self._dev is not None:
            if hasattr(self._dev, "layout_dict"):   # sharded plane
                return self._dev.layout_dict()
            return {"n_shards": np.asarray(1),
                    "rows": np.asarray(self._dev.rows),
                    "pad": np.asarray(self._dev.pad)}
        n = len(self.centroids) + len(self.spill)
        floor = (max(self.shard.pad_floor, 128)
                 if self.shard is not None and self.backend == "pallas_q8"
                 else self.shard.pad_floor if self.shard is not None else 0)
        pad = (shard_pad(n, S, floor) if self.shard is not None
               else _pow2_pad(n))
        return {"n_shards": np.asarray(S), "rows": np.asarray(pad * S),
                "pad": np.asarray(pad)}

    def memory_bytes(self) -> dict:
        """Bytes-level accounting of the device mirror (gateway.report
        surfaces this so capacity-per-byte is observable, DESIGN.md §15).
        Codes vs scales are split out for the quant plane; per-shard
        numbers divide the (uniformly sharded) device totals."""
        S = self.shard.n_shards if self.shard is not None else 1
        out = {"backend": self.backend, "n_shards": S,
               "mirror_live": self._dev is not None,
               "rows": len(self.centroids) + len(self.spill),
               "centroid_bytes": 0, "answer_bytes": 0,
               "codes_bytes": 0, "scales_bytes": 0, "meta_bytes": 0}
        dev = self._dev
        if dev is not None:
            if isinstance(dev, (_QuantDeviceState, ShardedQuantState)):
                out["codes_bytes"] = int(dev.codes.nbytes)
                out["scales_bytes"] = int(dev.scales.nbytes)
                out["centroid_bytes"] = (out["codes_bytes"]
                                         + out["scales_bytes"])
                out["meta_bytes"] = int(dev.valid.nbytes)
            else:
                out["centroid_bytes"] = int(dev.mat.nbytes)
                out["answer_bytes"] = int(dev.ans.nbytes)
                out["meta_bytes"] = int(dev.valid.nbytes
                                        + dev.aid.nbytes)
        out["device_total_bytes"] = (out["centroid_bytes"]
                                     + out["answer_bytes"]
                                     + out["meta_bytes"])
        out["per_shard_bytes"] = out["device_total_bytes"] // S
        out["host_store_bytes"] = int(
            self.centroids.vectors.nbytes + self.centroids.answers.nbytes
            + self.spill.vectors.nbytes + self.spill.answers.nbytes)
        return out

    def state_dict(self) -> dict:
        """Full snapshot: every piece of live state a warm restart needs
        to serve element-wise identical lookups (DESIGN.md §12)."""
        st = self._quant_state_entries() \
            if self.backend == "pallas_q8" else {}
        return {**st,
                "centroids": self.centroids.state_dict(),
                "spill": self.spill.state_dict(),
                "spill_last_use": self._spill_last_use,
                "spill_clock": np.asarray(self._spill_clock),
                "hits": np.asarray(self.hits),
                "misses": np.asarray(self.misses),
                "generation": np.asarray(self.generation),
                # was a serving mirror/index materialized at snapshot
                # time? If yes, the restore-rebuild reproduces it (no
                # generation bump); if an invalidation was pending, the
                # uninterrupted run would have bumped on its next lookup,
                # so the restored run must too
                "mirror_live": np.asarray(self._dev is not None
                                          or self._hnsw is not None),
                "dev_rebuilds": np.asarray(self.dev_rebuilds),
                "dev_row_writes": np.asarray(self.dev_row_writes),
                "dev_swaps": np.asarray(self.dev_swaps),
                "quant_rescored": np.asarray(self.quant_rescored),
                "quant_fallbacks": np.asarray(self.quant_fallbacks),
                "layout": self.layout_dict()}

    def _quant_state_entries(self) -> dict:
        """Snapshot of the int8 plane (DESIGN.md §15): codes + scales for
        the full [centroids; spill] row set, so a warm restart serves
        from the *same* quantized plane without requantizing. Derived by
        requantizing the host rows (bit-deterministic — identical to the
        live codes, which came from the same function on the same rows);
        err_max keeps the live mirror's running max so restored margins
        are never narrower than the dead process's."""
        vecs = np.concatenate([self.centroids.vectors, self.spill.vectors]) \
            if len(self.spill) else self.centroids.vectors
        codes, scales, err = quantize_rows(
            vecs.reshape(len(vecs), self.dim), width=_lane_pad(self.dim))
        err_max = float(err.max()) if len(err) else 0.0
        if self._dev is not None:
            err_max = max(err_max, float(self._dev.err_max))
        return {"quant": {"codes": codes, "scales": scales,
                          "err_max": np.asarray(err_max)}}

    def state_delta(self) -> dict:
        """Delta snapshot: everything that mutates *between* refresh
        commits. The centroid region's vectors/answers/ids/cluster_size
        only change at a commit (which writes a full snapshot), so a
        delta carries just the centroid access counts plus the whole
        (small, churning) spill region, recency state, and counters.
        The centroid ids ride along as the witness that the delta and
        its base describe the same centroid region."""
        return {"centroid_ids": self.centroids.ids,
                "centroid_access": self.centroids.access_count,
                "mirror_live": np.asarray(self._dev is not None
                                          or self._hnsw is not None),
                "spill": self.spill.state_dict(),
                "spill_last_use": self._spill_last_use,
                "spill_clock": np.asarray(self._spill_clock),
                "hits": np.asarray(self.hits),
                "misses": np.asarray(self.misses),
                "generation": np.asarray(self.generation),
                "dev_rebuilds": np.asarray(self.dev_rebuilds),
                "dev_row_writes": np.asarray(self.dev_row_writes),
                "dev_swaps": np.asarray(self.dev_swaps),
                "quant_rescored": np.asarray(self.quant_rescored),
                "quant_fallbacks": np.asarray(self.quant_fallbacks)}

    def _load_common(self, state: dict) -> None:
        # np.array (copy): in-process restores must not alias the donor's
        # live recency buffer
        self._spill_last_use = np.array(state["spill_last_use"], np.int64)
        self._spill_clock = int(state["spill_clock"])
        self.hits = int(state["hits"])
        self.misses = int(state["misses"])
        self.generation = int(state.get("generation", self.generation))
        self.dev_rebuilds = int(state.get("dev_rebuilds", self.dev_rebuilds))
        self.dev_row_writes = int(state.get("dev_row_writes",
                                            self.dev_row_writes))
        self.dev_swaps = int(state.get("dev_swaps", self.dev_swaps))
        self.quant_rescored = int(state.get("quant_rescored",
                                            self.quant_rescored))
        self.quant_fallbacks = int(state.get("quant_fallbacks",
                                             self.quant_fallbacks))

    def load_state(self, state: dict) -> None:
        cent = CentroidStore.from_state(state["centroids"])
        if cent.vectors.shape[1] != self.dim:
            raise ValueError(f"snapshot dim {cent.vectors.shape[1]} != "
                             f"cache dim {self.dim}")
        self.centroids = cent
        self.spill = CentroidStore.from_state(state["spill"])
        self._load_common(state)
        self._quant_restore = state.get("quant")
        self._restore_pending = bool(state.get("mirror_live",
                                               "generation" in state))
        self._invalidate()

    def load_delta(self, state: dict) -> None:
        """Overlay a delta snapshot on an already-restored base (the full
        snapshot of the same refresh epoch — the caller checks epochs)."""
        access = np.array(state["centroid_access"], np.float64)
        ids = np.asarray(state.get("centroid_ids", ()), np.int64)
        if len(access) != len(self.centroids) \
                or not np.array_equal(ids, self.centroids.ids):
            raise ValueError(
                "delta centroid region does not match the restored base "
                "— the delta belongs to another refresh epoch")
        self.centroids.access_count = access
        self.spill = CentroidStore.from_state(state["spill"])
        self._load_common(state)
        # the delta's spill supersedes any stashed full-snapshot codes;
        # the rebuild requantizes (bit-deterministic, so still identical)
        self._quant_restore = None
        self._restore_pending = bool(state.get("mirror_live", True))
        self._invalidate()

    def rebuild_mirror(self) -> None:
        """Eagerly re-materialize the serving state from the restored host
        arrays (warm restart, DESIGN.md §12): device mirror for the
        dense/pallas/sharded paths, graph index for hnsw. The rebuild
        keeps the restored generation — it reproduces the snapshot's
        serving state, it does not start a new one."""
        if len(self.centroids) + len(self.spill) == 0:
            self._restore_pending = False
            return
        if self.backend == "hnsw":
            self._hnsw_lookup(np.zeros((1, self.dim), np.float32))
        else:
            self._device_state()
