"""Sharded device-resident cache plane (DESIGN.md §11).

Row-shards the SemanticCache's persistent centroid/answer mirror across a
one-axis ``cache`` mesh so total cache capacity scales with shard count
instead of being bounded by a single device's HBM. SISO's centroid design
partitions cleanly: lookup has no cross-entry coupling, so each shard runs
the same fused theta-compare top-1 the single-device path runs, and only
O(B * n_shards) candidate scalars cross the wire for the final argmax
(``collectives.cross_shard_top1``).

Partitioning scheme (owner mapping)
-----------------------------------
Host row ``r`` (the row index in the cache's concatenated
[centroids; spill] order) is owned by shard ``r % S`` at local row
``r // S`` — round-robin. Two properties make this the right mapping for
a cache whose spill region grows online:

  * appends never remap existing rows: host row ``n`` always lands on
    shard ``n % S``, so spill inserts and LRU victim patches are a single
    donated in-place row write on the owner shard;
  * the locality-first layout (hottest centroids at low host rows) is
    striped evenly across shards instead of concentrating the hit mass
    on shard 0.

Each shard holds ``pad`` rows (pow2-padded per shard, so steady-state
lookups are compile-free); the device arrays are one global
``(S * pad, dim)`` jax.Array sharded ``P("cache", None)``, i.e. shard
``s`` physically owns device rows ``[s*pad, (s+1)*pad)`` and host row
``r`` lives at device row ``(r % S) * pad + r // S``.

A ``ShardedCacheConfig(n_shards=1)`` is the degenerate case: SemanticCache
then keeps the single-device `_DeviceState` hot path, bit-identical to an
unsharded cache (no shard_map, no collectives).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# per-shard pow2 pad floor — smaller than the host mirror's 128 floor so an
# 8-way split of a small cache doesn't inflate 8x
SHARD_PAD_FLOOR = 32


def _pow2_pad(n: int, floor: int) -> int:
    # local copy of clustering._pow2_pad: importing repro.core here would
    # cycle (core.semantic_cache imports this module via core.__init__)
    return max(floor, 1 << (n - 1).bit_length()) if n else floor


def owner_shard(row, n_shards: int):
    """Shard owning host row(s) ``row`` (round-robin)."""
    return row % n_shards


def shard_local_row(row, n_shards: int):
    """Local row of host row(s) ``row`` on its owner shard."""
    return row // n_shards


def shard_pad(n_rows: int, n_shards: int, floor: int = SHARD_PAD_FLOOR
              ) -> int:
    """Per-shard pow2 pad that fits ``n_rows`` total host rows."""
    return _pow2_pad(-(-n_rows // n_shards) if n_rows else 0, floor)


@dataclass
class ShardedCacheConfig:
    """Configuration of the sharded cache plane (DESIGN.md §11).

    ``n_shards=1`` keeps the single-device hot path (bit-identical to an
    unsharded cache). The mesh is built lazily through
    ``launch.mesh.make_cache_mesh`` so constructing the config never
    touches jax device state; pass an explicit one-axis ``("cache",)``
    mesh to co-locate the plane with an existing device assignment.
    """
    n_shards: int = 1
    mesh: Optional[Mesh] = None
    pad_floor: int = SHARD_PAD_FLOOR

    def make_mesh(self) -> Mesh:
        if self.mesh is None:
            from repro.launch.mesh import make_cache_mesh
            self.mesh = make_cache_mesh(self.n_shards)
        return self.mesh


@functools.lru_cache(maxsize=None)
def _plane_fns(mesh: Mesh, n_shards: int, backend: str):
    """Compiled (lookup, write_plain, write_donated) for one mesh/backend.

    Module-level cache: every rebuild/shadow-swap of the plane state reuses
    the same jitted callables, so steady-state refresh cycles (whose pow2
    tile shapes are stable) stay compile-free.
    """
    S = n_shards
    from repro.distributed.collectives import cross_shard_top1

    def look_kern(q, mat, ans, valid, aid, theta):
        # operands are the shard-local (pad, ...) blocks
        if backend == "pallas":
            from repro.kernels.cosine_topk.ops import cosine_top1_local
            best, l = cosine_top1_local(q, mat, valid)
        else:
            # (B, pad) local, full f32 like the single-device lookup
            sims = jnp.matmul(q, mat.T, precision=jax.lax.Precision.HIGHEST)
            sims = jnp.where(valid[None, :], sims, -1.0)
            l = jnp.argmax(sims, axis=1)
            best = jnp.take_along_axis(sims, l[:, None], axis=1)[:, 0]
        me = jax.lax.axis_index("cache").astype(jnp.int32)
        host_row = l.astype(jnp.int32) * S + me      # globalize (round-robin)
        # slim merge: only (sim, host_row) cross the wire; the full local
        # ans/aid blocks stay put and the winner's row is psum-fetched
        return cross_shard_top1(best, host_row, ans, aid, theta)

    def write_kern(mat, ans, valid, aid, row, vec, answer, answer_id):
        # owner-shard routed in-place row patch: every shard traces the
        # update, only the owner keeps it — data moves on one shard only
        me = jax.lax.axis_index("cache").astype(jnp.int32)
        mine = (row % S) == me
        l = row // S
        mat2 = jax.lax.dynamic_update_slice(mat, vec[None, :], (l, 0))
        ans2 = jax.lax.dynamic_update_slice(ans, answer[None, :], (l, 0))
        valid2 = valid.at[l].set(True)
        aid2 = aid.at[l].set(answer_id)
        keep = lambda new, old: jnp.where(mine, new, old)
        return (keep(mat2, mat), keep(ans2, ans), keep(valid2, valid),
                keep(aid2, aid))

    row_specs = (P("cache", None), P("cache", None), P("cache"), P("cache"))
    # replication checking off: cross_shard_top1 returns identical values
    # on every shard on purpose
    look = jax.jit(jax.shard_map(
        look_kern, mesh=mesh,
        in_specs=(P(), *row_specs, P()),
        out_specs=(P(), P(), P(), P(), P()), check_vma=False))
    write_sm = jax.shard_map(write_kern, mesh=mesh,
                             in_specs=(*row_specs, P(), P(), P(), P()),
                             out_specs=row_specs, check_vma=False)
    # CPU ignores donation (with a warning), so only donate off-CPU —
    # same policy as the single-device row patch
    return look, jax.jit(write_sm), jax.jit(write_sm,
                                            donate_argnums=(0, 1, 2, 3))


@dataclass
class ShardedDeviceState:
    """Persistent mesh-sharded mirror of the centroid + spill regions.

    Drop-in replacement for the single-device ``_DeviceState``: same
    ``write_row`` contract, plus a ``lookup`` that fuses the shard-local
    top-1 with the cross-shard reduction (one device round trip).
    """
    mat: jax.Array      # (S*pad, dim) float32, row-sharded over "cache"
    ans: jax.Array      # (S*pad, answer_dim) float32
    valid: jax.Array    # (S*pad,) bool
    aid: jax.Array      # (S*pad,) int32
    pad: int            # rows per shard
    n_shards: int
    mesh: Mesh
    backend: str = "dense"

    @property
    def rows(self) -> int:
        """Total addressable host rows before the plane must regrow."""
        return self.pad * self.n_shards

    @classmethod
    def from_shard_layout(cls, mesh: Mesh, n_shards: int,
                          mat: np.ndarray, ans: np.ndarray,
                          valid: np.ndarray, aid: np.ndarray,
                          backend: str = "dense") -> "ShardedDeviceState":
        """Upload host staging already in (S, pad, ...) owner layout —
        one transfer per array, placed shard-local by NamedSharding."""
        S, pad = mat.shape[0], mat.shape[1]
        rows2 = NamedSharding(mesh, P("cache", None))
        rows1 = NamedSharding(mesh, P("cache"))
        return cls(
            mat=jax.device_put(mat.reshape(S * pad, -1), rows2),
            ans=jax.device_put(ans.reshape(S * pad, -1), rows2),
            valid=jax.device_put(valid.reshape(S * pad), rows1),
            aid=jax.device_put(aid.reshape(S * pad), rows1),
            pad=pad, n_shards=S, mesh=mesh, backend=backend)

    @classmethod
    def build(cls, mesh: Mesh, n_shards: int,
              vectors: np.ndarray, answers: np.ndarray,
              answer_id: np.ndarray, pad_floor: int = SHARD_PAD_FLOOR,
              backend: str = "dense") -> "ShardedDeviceState":
        """Scatter host rows (host-row order) into the owner layout and
        upload. Full rebuild path — online writes use ``write_row``."""
        n, dim = vectors.shape
        pad = shard_pad(n, n_shards, pad_floor)
        mat = np.zeros((n_shards, pad, dim), np.float32)
        ans = np.zeros((n_shards, pad, answers.shape[1]), np.float32)
        valid = np.zeros((n_shards, pad), bool)
        aid = np.full((n_shards, pad), -1, np.int32)
        if n:
            rows = np.arange(n)
            s, l = rows % n_shards, rows // n_shards
            mat[s, l] = vectors
            ans[s, l] = answers
            valid[s, l] = True
            aid[s, l] = answer_id
        return cls.from_shard_layout(mesh, n_shards, mat, ans, valid, aid,
                                     backend=backend)

    def lookup(self, queries: np.ndarray, theta):
        """Batch top-1 over all shards: shard-local fused theta-compare
        top-1, then ``cross_shard_top1``. Returns device arrays
        (hit, best sim, winning host row, answer, answer_id)."""
        look, _, _ = _plane_fns(self.mesh, self.n_shards, self.backend)
        return look(jnp.asarray(queries), self.mat, self.ans, self.valid,
                    self.aid, jnp.float32(theta))

    def write_row(self, row: int, vec: np.ndarray, answer: np.ndarray,
                  answer_id: int) -> None:
        """Owner-shard routed in-place row patch (host row ``row``)."""
        _, plain, donated = _plane_fns(self.mesh, self.n_shards,
                                       self.backend)
        fn = plain if jax.default_backend() == "cpu" else donated
        # jnp.array (copy) — asarray would zero-copy-alias caller numpy
        # buffers that may be mutated while the async write is in flight
        self.mat, self.ans, self.valid, self.aid = fn(
            self.mat, self.ans, self.valid, self.aid,
            jnp.int32(row), jnp.array(vec, jnp.float32),
            jnp.array(answer, jnp.float32), jnp.int32(answer_id))

    def layout_dict(self) -> dict:
        """Serializable per-shard layout descriptor (rides in snapshots,
        DESIGN.md §12): host row ``r`` lives on shard ``r % n_shards`` at
        local row ``r // n_shards``, ``pad`` rows per shard. The mapping
        is a pure function of (row, n_shards), so a warm restart on a
        different shard count legally rebuilds a different-but-equivalent
        plane; the descriptor records the plane the snapshot was serving
        from."""
        return {"n_shards": np.asarray(self.n_shards),
                "rows": np.asarray(self.rows),
                "pad": np.asarray(self.pad)}

    def nbytes_per_shard(self) -> int:
        """Device bytes each shard holds — the HBM-per-device proxy the
        capacity-scaling bench reports (EXPERIMENTS.md §Shard)."""
        per_row = (self.mat.dtype.itemsize * self.mat.shape[1]
                   + self.ans.dtype.itemsize * self.ans.shape[1]
                   + self.valid.dtype.itemsize + self.aid.dtype.itemsize)
        return self.pad * per_row


@functools.lru_cache(maxsize=None)
def _quant_plane_fns(mesh: Mesh, n_shards: int, k: int):
    """Compiled (candidates, write_plain, write_donated) for the int8
    plane (DESIGN.md §15). The candidate kernel runs the fused
    dequant-cosine top-k shard-locally, then all-gathers only the
    (sim, host_row) candidate lists — 2 * B * S * k scalars; no answer
    payload ever rides the collective (answers are host-resident for the
    quant plane)."""
    S = n_shards

    def cand_kern(q, codes, scales, valid):
        from repro.kernels.cosine_topk.ops import cosine_topk_q8
        s, i = cosine_topk_q8(q, codes, scales, k=k, valid=valid,
                              early_exit=False)
        me = jax.lax.axis_index("cache").astype(jnp.int32)
        gr = jnp.where(i >= 0, i * S + me, -1)       # globalize; keep -1
        sg = jax.lax.all_gather(s, "cache", axis=1)  # (B, S, k)
        rg = jax.lax.all_gather(gr, "cache", axis=1)
        return sg, rg

    def write_kern(codes, scales, valid, row, crow, scale):
        me = jax.lax.axis_index("cache").astype(jnp.int32)
        mine = (row % S) == me
        l = row // S
        codes2 = jax.lax.dynamic_update_slice(codes, crow[None, :], (l, 0))
        scales2 = scales.at[l].set(scale)
        valid2 = valid.at[l].set(True)
        keep = lambda new, old: jnp.where(mine, new, old)
        return (keep(codes2, codes), keep(scales2, scales),
                keep(valid2, valid))

    row_specs = (P("cache", None), P("cache"), P("cache"))
    look = jax.jit(jax.shard_map(cand_kern, mesh=mesh,
                                 in_specs=(P(), *row_specs),
                                 out_specs=(P(), P()), check_vma=False))
    write_sm = jax.shard_map(write_kern, mesh=mesh,
                             in_specs=(*row_specs, P(), P(), P()),
                             out_specs=row_specs, check_vma=False)
    return look, jax.jit(write_sm), jax.jit(write_sm,
                                            donate_argnums=(0, 1, 2))


@dataclass
class ShardedQuantState:
    """Mesh-sharded int8 mirror (backend "pallas_q8", DESIGN.md §15).

    Same round-robin owner mapping as ``ShardedDeviceState`` but holding
    codes + per-row scales only — no device answer matrix (answers are
    gathered host-side from the winning row), which is most of the >=2x
    capacity-per-device-byte. Lookup returns top-C *candidates* per
    (query, shard) instead of a final argmax: the exact margin rescore
    happens in SemanticCache, shared with the 1-device quant path.
    """
    codes: jax.Array    # (S*pad, dpad) int8, row-sharded over "cache"
    scales: jax.Array   # (S*pad,) float32
    valid: jax.Array    # (S*pad,) bool
    pad: int            # rows per shard
    n_shards: int
    mesh: Mesh
    err_max: float      # running max per-row dequant L2 error

    @property
    def rows(self) -> int:
        return self.pad * self.n_shards

    @property
    def dpad(self) -> int:
        return self.codes.shape[1]

    @classmethod
    def from_shard_layout(cls, mesh: Mesh, n_shards: int,
                          codes: np.ndarray, scales: np.ndarray,
                          valid: np.ndarray, err_max: float
                          ) -> "ShardedQuantState":
        """Upload host staging already in (S, pad, ...) owner layout —
        one transfer per array, placed shard-local by NamedSharding."""
        S, pad = codes.shape[0], codes.shape[1]
        rows2 = NamedSharding(mesh, P("cache", None))
        rows1 = NamedSharding(mesh, P("cache"))
        return cls(
            codes=jax.device_put(codes.reshape(S * pad, -1), rows2),
            scales=jax.device_put(scales.reshape(S * pad), rows1),
            valid=jax.device_put(valid.reshape(S * pad), rows1),
            pad=pad, n_shards=S, mesh=mesh, err_max=float(err_max))

    @classmethod
    def build(cls, mesh: Mesh, n_shards: int, codes: np.ndarray,
              scales: np.ndarray, err_max: float,
              pad_floor: int = 128) -> "ShardedQuantState":
        """Scatter quantized host rows (host-row order) into the owner
        layout and upload. The pad floor is >= 128 so each shard block is
        already kernel-tile shaped (no per-call padding in the lookup)."""
        n, dpad = codes.shape
        pad = shard_pad(n, n_shards, pad_floor)
        cp = np.zeros((n_shards, pad, dpad), np.int8)
        sp = np.zeros((n_shards, pad), np.float32)
        valid = np.zeros((n_shards, pad), bool)
        if n:
            rows = np.arange(n)
            s, l = rows % n_shards, rows // n_shards
            cp[s, l] = codes
            sp[s, l] = scales
            valid[s, l] = True
        return cls.from_shard_layout(mesh, n_shards, cp, sp, valid,
                                     err_max)

    def candidates(self, queries: np.ndarray, k: int
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Quant top-k candidates per (query, shard): ((B, S, k) sims
        f32, (B, S, k) host rows i32, -1 for exhausted slots)."""
        look, _, _ = _quant_plane_fns(self.mesh, self.n_shards, k)
        s, r = look(jnp.asarray(queries), self.codes, self.scales,
                    self.valid)
        s, r = jax.device_get((s, r))
        return np.array(s), np.array(r)

    def write_row(self, row: int, vec: np.ndarray, answer: np.ndarray,
                  answer_id: int) -> None:
        """Owner-shard routed in-place code-row + scale patch. The
        answer/answer_id stay host-side (ignored here), same contract as
        the single-device quant mirror."""
        from repro.kernels.cosine_topk.ops import quantize_rows
        crow, scale, err = quantize_rows(
            np.asarray(vec, np.float32).reshape(1, -1), width=self.dpad)
        _, plain, donated = _quant_plane_fns(self.mesh, self.n_shards, 1)
        fn = plain if jax.default_backend() == "cpu" else donated
        self.codes, self.scales, self.valid = fn(
            self.codes, self.scales, self.valid, jnp.int32(row),
            jnp.array(crow[0]), jnp.float32(scale[0]))
        self.err_max = max(self.err_max, float(err[0]))

    def layout_dict(self) -> dict:
        return {"n_shards": np.asarray(self.n_shards),
                "rows": np.asarray(self.rows),
                "pad": np.asarray(self.pad)}

    def nbytes_per_shard(self) -> int:
        per_row = (self.codes.dtype.itemsize * self.codes.shape[1]
                   + self.scales.dtype.itemsize
                   + self.valid.dtype.itemsize)
        return self.pad * per_row
