"""LLM engines.

Two tiers (DESIGN.md §9.2):

* ``AnalyticEngine`` — the latency box the paper's M/D/1 model abstracts the
  GPU server into. Per-request E2E = TTFT(tokens_in) + TBT * (tokens_out-1),
  with per-token costs derived from model size and the hardware constants
  used in the roofline analysis (197 TFLOP/s bf16, 819 GB/s HBM per chip).
  Drives the discrete-event SLO simulator.

* ``ModelEngine`` — a real JAX model from the zoo behind jitted prefill
  into a slot + one jitted decode step over all slots, each at its own
  position, used by the runnable examples and the continuous-batching
  scheduler.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.configs.base import ModelConfig

# Hardware constants (TPU v5e class; see EXPERIMENTS.md §Roofline)
PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link


@dataclass(frozen=True)
class EngineModel:
    """Analytic per-request latency model of a serving instance."""
    name: str
    n_active_params: int       # per-token matmul params (6ND convention)
    n_chips: int = 8
    kv_bytes_per_token: float = 0.0   # KV-cache bytes appended per token
    weight_bytes: float = 0.0         # bytes read per decode step (weights)
    mfu_prefill: float = 0.5          # fraction of peak during prefill
    bwu_decode: float = 0.6           # fraction of HBM bw during decode
    overhead_s: float = 0.02          # fixed per-request overhead

    @classmethod
    def from_config(cls, cfg: ModelConfig, n_chips: int = 8,
                    dtype_bytes: int = 2) -> "EngineModel":
        n_act = cfg.active_params
        if cfg.attn_kind == "mla":
            kv_tok = cfg.n_layers * (cfg.kv_lora_rank + cfg.qk_rope_dim) \
                * dtype_bytes
        elif cfg.ssm_kind:
            kv_tok = 0.0          # O(1) state
        else:
            kv_tok = cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim \
                * dtype_bytes
        return cls(name=cfg.name, n_active_params=n_act, n_chips=n_chips,
                   kv_bytes_per_token=kv_tok,
                   weight_bytes=cfg.total_params * dtype_bytes)

    # --- latency terms -----------------------------------------------------

    def ttft(self, tokens_in: float) -> float:
        """Prefill: compute-bound, 2*N*L FLOPs over the chips."""
        flops = 2.0 * self.n_active_params * tokens_in
        return self.overhead_s + flops / (self.n_chips * PEAK_FLOPS
                                          * self.mfu_prefill)

    def tbt(self, kv_tokens: float = 0.0, batch: int = 1) -> float:
        """Decode: memory-bound — weights (amortized over the batch) + this
        request's KV stream per generated token."""
        bytes_per_step = self.weight_bytes / max(batch, 1) \
            + self.kv_bytes_per_token * kv_tokens
        return bytes_per_step / (self.n_chips * HBM_BW * self.bwu_decode)

    def e2e(self, tokens_in: float, tokens_out: float,
            batch: int = 1) -> float:
        """Zero-load end-to-end latency (paper §5.1's SLO reference):
        TTFT + TBT x (#generated - 1)."""
        kv_mid = tokens_in + tokens_out / 2.0   # average KV length
        return self.ttft(tokens_in) + max(tokens_out - 1, 0) \
            * self.tbt(kv_mid, batch)


@dataclass
class ServiceStats:
    served: int = 0
    busy_until: float = 0.0
    total_busy: float = 0.0


class AnalyticEngine:
    """Single FIFO server with deterministic service times (the 'D' in
    M/D/1). ``concurrency`` > 1 models continuous batching: up to C
    requests share the server; decode TBT amortizes weight reads over the
    live batch."""

    def __init__(self, model: EngineModel, concurrency: int = 1):
        self.model = model
        self.concurrency = concurrency
        self._free_at = np.zeros(concurrency, dtype=np.float64)
        self.stats = ServiceStats()

    def reset(self) -> None:
        self._free_at[:] = 0.0
        self.stats = ServiceStats()

    def mean_service_time(self, tokens_in: float, tokens_out: float) -> float:
        return self.model.e2e(tokens_in, tokens_out, batch=self.concurrency)

    def submit(self, arrival: float, tokens_in: int, tokens_out: int
               ) -> tuple[float, float]:
        """Returns (start_time, completion_time) under FIFO dispatch to the
        earliest-free lane."""
        lane = int(np.argmin(self._free_at))
        start = max(arrival, self._free_at[lane])
        live = int((self._free_at > start).sum()) + 1
        service = self.model.e2e(tokens_in, tokens_out,
                                 batch=min(live, self.concurrency))
        done = start + service
        self._free_at[lane] = done
        self.stats.served += 1
        self.stats.total_busy += service
        self.stats.busy_until = float(self._free_at.max())
        return start, done


# ---------------------------------------------------------------------------
# Real-model engine (examples / scheduler)
# ---------------------------------------------------------------------------


class ModelEngine:
    """Slot-based engine over a zoo model: jitted prefill into a slot +
    one decode step over all slots, each slot at its own position/kv_len
    (the requirement for continuous batching): the slots are the batch of
    one ``lm.decode_step`` with a (n_slots,) position, so each slot's new
    cache row is written in place, with no loop over the slots.

    For an MoE model each step also returns its routing counter, the
    assignments each held expert received in each MoE layer, fetched with
    the step's tokens: prefills add theirs to ``prefill_routed``; a decode
    step's, over its active slots, is noted on its ``engine.decode`` span
    while spans record (``repro.trace``)."""

    def __init__(self, params, cfg: ModelConfig, n_slots: int = 4,
                 max_len: int = 256):
        from repro.models import lm
        self.params, self.cfg, self.lm = params, cfg, lm
        self.n_slots, self.max_len = n_slots, max_len
        self.cache = lm.init_cache(cfg, n_slots, max_len)
        self.pos = np.zeros(n_slots, np.int32)        # next write index
        self.active = np.zeros(n_slots, bool)
        self.prefill_routed = None    # (n_moe_layers, E_held) running total
        self._jit_prefill = jax.jit(partial(lm.prefill, cfg=cfg,
                                            routing=True))
        self._jit_decode = jax.jit(
            lambda params, tokens, cache, pos: lm.decode_step(
                params, cfg, tokens, cache, pos, kv_len=pos + 1,
                routing=True))

    def free_slots(self) -> list[int]:
        return [i for i in range(self.n_slots) if not self.active[i]]

    def prefill_into(self, slot: int, tokens: np.ndarray) -> int:
        """Prefill a (Lp,) prompt into `slot`; returns the first token."""
        lp = len(tokens)
        with trace.span("engine.prefill", n=lp):
            batch = {"tokens": jnp.asarray(tokens, jnp.int32)[None]}
            cache1 = self.lm.init_cache(self.cfg, 1, self.max_len)
            logits, cache1, routed = self._jit_prefill(
                self.params, batch=batch, cache=cache1)

            def place(full, one):
                idx = [0] * full.ndim
                idx[1] = slot
                return jax.lax.dynamic_update_slice(
                    full, one.astype(full.dtype), tuple(idx))

            self.cache = jax.tree.map(place, self.cache, cache1)
            self.pos[slot] = lp
            self.active[slot] = True
            first = jnp.argmax(logits[0])
            with trace.span("engine.prefill.wait"):
                first, routed = jax.device_get((first, routed))
            if routed is not None:
                if self.prefill_routed is None:
                    self.prefill_routed = np.zeros(routed.shape, np.int64)
                self.prefill_routed += routed
            return int(first)

    def decode_active(self, tokens: np.ndarray) -> np.ndarray:
        """One decode step for every slot (inactive slots decode garbage
        that callers ignore). tokens: (n_slots,) last token per slot.

        tokens/pos MUST be copied onto the device (jnp.array, not
        jnp.asarray): on CPU, asarray zero-copy-aliases the caller's
        numpy buffers, and both are mutated immediately after dispatch
        (pos below, tokens by the scheduler's retire loop) while the
        async computation may still be reading them — a data race that
        surfaced as run-to-run nondeterministic decode output."""
        with trace.span("engine.decode") as sp:
            logits, self.cache, routed = self._jit_decode(
                self.params, jnp.array(tokens, jnp.int32)[:, None],
                self.cache, jnp.array(self.pos))
            active = self.active.copy()
            self.pos[active] += 1
            nxt = jnp.argmax(logits, axis=-1)
            with trace.span("engine.decode.wait"):
                nxt, routed = jax.device_get((nxt, routed))
            if routed is not None and trace.enabled():
                # per slot (n_slots, n_moe_layers, E_held): idle slots'
                # tokens route too and do not count
                sp.note(routed=routed[active].sum(axis=0))
            return nxt

    def release(self, slot: int) -> None:
        self.active[slot] = False
        self.pos[slot] = 0
