"""The DeepSeek-V2 configuration: its file against the registered
architecture, the serving engine against the benchmark's reference at a
small size, the cell end to end at tiny size, and the decode step's
roofline share read by hand."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib
from harness.build import engine_config, model_module
from harness.cell import load_cell
from harness.report import RunData, load_reader
from harness.serve import Spans, Window

from repro import trace
from repro.configs.base import get_config
from repro.serving.engine import ModelEngine

CELL = "faq-dsv2"
CONFIG = bench_testlib.BENCH / "configs" / "deepseek-v2-ep8-f32-500k.json"
SMALL = dict(num_hidden_layers=3, hidden_size=64, intermediate_size=128,
             moe_intermediate_size=16, num_attention_heads=4,
             num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             vocab_size=4096)


@pytest.fixture(autouse=True)
def empty_ring():
    yield
    trace.clear()


def test_the_routing_keys_are_the_registered_architectures():
    """The harness takes every MoE size from the registered architecture,
    so the file's must agree with it; the file holds 20 of the published
    160 routed experts, one routing group of the 8 that ep_size spreads
    over 8 chips."""
    f = json.loads(CONFIG.read_text())
    arch = get_config(f["model"]["program"]["arch"])
    for block in (f, f["model"]):
        assert block["n_routed_experts"] * block["ep_size"] == arch.n_experts
        assert arch.n_experts // arch.n_group == block["n_routed_experts"]
        assert block["num_experts_per_tok"] == arch.top_k
        assert block["n_group"] == arch.n_group
        assert block["topk_group"] == arch.topk_group
        assert block["routed_scaling_factor"] == arch.routed_scaling_factor
        assert block["norm_topk_prob"] == arch.norm_topk_prob
        assert block["n_shared_experts"] == arch.n_shared_experts
        assert block["moe_intermediate_size"] == arch.d_ff_expert
        assert block["first_k_dense_replace"] == arch.first_dense_layers
    assert f["published"]["n_routed_experts"] == arch.n_experts
    assert "n_routed_experts" in f["reduced"]
    # the harness's block states what the file's top level states
    for k, v in f["model"].items():
        if k not in ("program", "dtype"):
            assert f[k] == v, k


def _small_model():
    m = json.loads(CONFIG.read_text())["model"]
    m.update(SMALL, dtype="float32")
    return m


def test_engine_matches_the_reference_through_slots():
    """Prefill into slots at different times and decode through them, as
    the scheduler does: at every position the engine's logits are the
    reference's full forward's. Both compute in f32 at width 64 and route
    the same experts; they differ in the order of their sums (latent-space
    decode attention, the dispatch buffer), a few ulps of the logits."""
    m = _small_model()
    mm = model_module("mla_moe")
    params = mm.init_weights(m, 5, dtype=jnp.float32)
    eng = ModelEngine(params, engine_config(m), n_slots=4, max_len=64)
    seen = []
    for name in ("_jit_prefill", "_jit_decode"):
        fn = getattr(eng, name)

        def rec(*a, fn=fn, **kw):
            out = fn(*a, **kw)
            seen.append(np.asarray(out[0]))
            return out

        setattr(eng, name, rec)
    rng = np.random.default_rng(0)
    seqs, logits = {}, {}
    toks = np.zeros(4, np.int32)
    for slot, n, steps in ((0, 7, 3), (2, 12, 4), (1, 3, 3)):
        seqs[slot] = list(rng.integers(0, m["vocab_size"], n))
        toks[slot] = eng.prefill_into(slot, np.asarray(seqs[slot]))
        logits[slot] = [seen[-1][0]]
        for _ in range(steps):
            active = np.flatnonzero(eng.active)
            nxt = eng.decode_active(toks)
            for s in active:
                seqs[s].append(int(toks[s]))
                logits[s].append(seen[-1][s])
            toks[active] = nxt[active]
    for s, seq in seqs.items():
        x, _ = mm._forward(params, m, jnp.asarray([seq]), False)
        ref = np.asarray(mm._logits(params, m, x, False))[0]
        got = np.stack(logits[s])
        want = ref[len(seq) - len(got):]
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   atol=1e-5 * np.abs(want).max())


def _tiny():
    """The cell at a test's size. Its token_gap limit lies between the
    readings at this size (9 seeds on the CPU): the bf16 engine's largest
    0.059 and the float8 control's smallest 0.29. The engine reads above
    the dense model's 0.002 because a near tie in the routing of an
    earlier position still reaches a judged one through attention."""
    cfg, mix, cell = bench_testlib.tiny(CELL)
    cfg["model"].update(SMALL)
    cell["limits"]["token_gap"] = 0.15
    return cfg, mix, cell


def _taken(mm, s, logits):
    return np.asarray(mm.gate_weights(jnp.asarray(logits), s)[..., :20] > 0)


def test_a_decided_routing_survives_every_small_change_of_the_logits():
    """Where the reference calls the held experts' gates decided at a
    margin, no change of the router logits by at most half the margin
    takes or drops a held expert; random logits at the published routing
    counts, random and sign-pattern changes."""
    m = json.loads(CONFIG.read_text())["model"]
    mm = model_module("mla_moe")
    s = mm.sizes(m)
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(4000, 160)).astype(np.float32)
    margin = 0.04
    decided = np.asarray(mm.held_gates_decided(jnp.asarray(logits), s,
                                                margin))
    assert np.asarray(mm.held_gates_decided(jnp.asarray(logits), s,
                                            0.0)).all()
    assert 0.3 < decided.mean() < 0.95
    base = _taken(mm, s, logits)[decided]
    for _ in range(20):
        # the change just inside half the margin, each logit up or down
        step = 0.999 * margin / 2 * rng.choice([-1.0, 1.0], logits.shape)
        moved = _taken(mm, s, (logits + step).astype(np.float32))
        np.testing.assert_array_equal(moved[decided], base)


def test_a_near_tie_at_the_top_6_boundary_is_not_decided():
    """Held expert 0 sixth among the kept experts, expert 40 (group 2,
    kept) seventh by 0.01: decided at a margin of 0.005, not at 0.04; a
    change of 0.006 in each makes 40 take 0's place."""
    m = json.loads(CONFIG.read_text())["model"]
    mm = model_module("mla_moe")
    s = mm.sizes(m)
    lg = np.full(160, -5.0, np.float32)
    lg[[1, 2, 21, 22, 41]] = [4.0, 3.5, 3.8, 3.2, 3.6]   # groups 0, 1, 2
    lg[0], lg[40] = 2.0, 1.99
    lg = jnp.asarray(lg[None])
    assert bool(mm.held_gates_decided(lg, s, 0.005)[0])
    assert not bool(mm.held_gates_decided(lg, s, 0.04)[0])
    assert _taken(mm, s, lg)[0, 0]
    moved = np.asarray(lg).copy()
    moved[0, 0] -= 0.006
    moved[0, 40] += 0.006
    assert not _taken(mm, s, moved)[0, 0]


def test_cell_runs_end_to_end_at_tiny_size(tmp_path):
    """The cell as `bench/run.py` runs it, cut to a test's size and traced:
    correct, and every decode step in the traced part notes its routing
    counter on its span. The CPU has no device plane, so the reader of
    the decode step's roofline share finds nothing there."""
    cfg, mix, cell = _tiny()
    out = bench_testlib.run_tiny(cell_name=CELL, parts=(cfg, mix, cell),
                                 trace=True, out_dir=tmp_path)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    decodes = [s for s in trace.spans() if s.name == "engine.decode"]
    assert decodes and all(s.counts["routed"].shape == (2, 20)
                           for s in decodes)
    assert "decode_roofline" not in out["metrics"]


MS = 1e-3
T0 = 100.0
OFF_NS = 5e9                # trace host clock - perf_counter, in ns


def _roofline_run(note: bool):
    """One decode step of the published model over two slots with kv
    lengths 100 and 200: benchmark span [1, 9] ms, the program's span
    inside it noting 3 held experts that received a token (7 assignments),
    and 8 ms of device ops."""
    f = json.loads(CONFIG.read_text())
    routed = np.zeros((4, 20), np.int32)
    routed[0, 1], routed[2, 5], routed[3, 19] = 4, 2, 1
    times = iter([T0 + 2 * MS, T0 + 8 * MS])
    clock = trace.clock
    trace.clock = lambda: next(times)
    try:
        with trace.recording():
            with trace.span("engine.decode") as sp:
                if note:
                    sp.note(routed=routed)
    finally:
        trace.clock = clock
    spans = Spans()
    spans.add("decode", T0 + MS, T0 + 9 * MS, np.array([100, 200]))
    lo, hi = T0 * 1e9 + OFF_NS, (T0 + 10 * MS) * 1e9 + OFF_NS
    tr = {"spans": [["bench.traced", lo, hi],
                    ["bench.decode", lo + 1e6, lo + 9e6]],
          "devices": {"/device:TPU:0": [["%while.1", lo + 1e6, lo + 9e6]]}}
    win = Window(T0 - 40, T0 + 10 * MS, T0 + 1, [], 0, 0,
                 trace_span=(T0, T0 + 10 * MS))
    return RunData(f, {}, win, spans, [], 0, 40.0, 1.0, "TPU v5 lite",
                   trace=tr, trace_window=(lo, hi),
                   trace_host=(T0, T0 + 10 * MS),
                   reduced={"clock_offset_ns": 0.0},
                   peaks={"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9})


def test_decode_roofline_by_hand():
    # bf16 bytes read: every weight outside the routed experts (5 x 149.2M
    # attention, the dense layer's 188.7M, 4 x (0.82M router + 47.2M
    # shared), the head's 524.3M), 3 held experts of 23.6M, the latent
    # rows (512 + 64) of 300 positions in 5 layers, 2 embedding rows
    params = 5 * 149_225_472 + 188_743_680 + 4 * 48_005_120 + 524_288_000 \
        + 3 * 23_592_960
    nbytes = 2 * params + 2 * 5 * 300 * 576 + 2 * 2 * 5120
    # memory-bound: 2 tokens' operations take far less than the bytes
    least = nbytes / 819e9
    got = load_reader("decode_roofline")(_roofline_run(note=True))
    assert got == pytest.approx(100 * least / 8e-3, rel=1e-9)


def test_decode_roofline_reads_nothing_without_the_counter():
    """The parent of the change notes no routing counter."""
    assert load_reader("decode_roofline")(_roofline_run(note=False)) is None
