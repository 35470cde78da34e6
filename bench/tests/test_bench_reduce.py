"""The reduction from a trace to busy, idle and per-op time, and the
operation and byte counts behind the roofline and MFU shares, against
numbers worked out by hand."""
import json
import math

import pytest

import bench_testlib  # noqa: F401  (import paths)
import trace_reduce
from counts import embed, engine, lookup
from harness.report import load_peaks

DATA = bench_testlib.BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def small():
    return json.loads((DATA / "small_trace.json").read_text())


def test_busy_idle_and_ops_on_a_small_trace(small):
    r = trace_reduce.reduce(small, *small["window"])
    # chip 0: [100, 400] + [600, 700] = 400 ns; chip 1: [0, 520] +
    # [900, 1000] (clipped to the window) = 620 ns; mean 510 of 1000
    assert r["busy_s"] == pytest.approx(510e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["idle_share"] == pytest.approx(0.49)
    assert r["n_chips"] == 2
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({"fusion.1": 700e-9, "fusion.2": 150e-9,
                                 "copy": 100e-9, "lookup_op": 70e-9,
                                 "late": 100e-9})
    # chip 0's gaps [0,100], [400,600], [700,1000] fall in submit, lookup
    # and step: the innermost span open at each gap's middle
    assert r["idle_gaps"] == [["step", pytest.approx(300e-9)],
                              ["lookup", pytest.approx(200e-9)],
                              ["submit", pytest.approx(100e-9)]]
    assert trace_reduce.window_of(small) == (0, 1000)


def test_device_time_inside_spans(small):
    ns, n = trace_reduce.device_time_in(small, "bench.lookup", 0, 1000)
    assert (ns, n) == (70, 1)
    assert trace_reduce.device_time_in(small, "bench.lookup", 500, 1000) \
        == (0.0, 0)


def _early_device_trace(early_ns: float, turns: int = 40) -> dict:
    """Turns of embed, lookup and decode spans (host clock, ns), each
    waiting for its device work, with the device's ops read ``early_ns``
    early, as a v5e's trace reads them; the decode nests an op in
    another, as a layer loop does."""
    ms = 1e6
    spans, ops = [], []
    for k in range(turns):
        t = k * 40 * ms
        spans += [["bench.submit", t, t + 8.3 * ms],
                  ["bench.embed", t, t + 2.6 * ms],
                  ["bench.lookup", t + 2.6 * ms, t + 8.2 * ms],
                  ["bench.step", t + 8.25 * ms, t + 39.65 * ms],
                  ["bench.decode", t + 8.3 * ms, t + 39.6 * ms]]
        ops += [["%embed", t + 0.4 * ms, t + 1.1 * ms],
                ["%cosine_topk.1", t + 4.4 * ms, t + 6.65 * ms],
                ["%while.14", t + 11 * ms, t + 38.5 * ms],
                ["%fusion.180", t + 12 * ms, t + 13 * ms]]
    hi = turns * 40 * ms
    spans.append(["bench.traced", 0, hi])
    dev = [[n, a - early_ns, b - early_ns] for n, a, b in ops]
    return {"devices": {"/device:TPU:0": dev}, "spans": spans}, hi


@pytest.mark.parametrize("early_ms", [0.0, 1.7, 3.0])
def test_device_clock_is_moved_onto_the_spans(early_ms):
    """The shift that puts the most device time inside the spans that wait
    for it lies where every op sits inside its own span; with it, the
    lookup spans hold the kernel's 2.25 ms a turn, and nothing of the
    embed or the decode."""
    trace, hi = _early_device_trace(early_ms * 1e6)
    shift = trace_reduce.clock_offset(trace, 0, hi)
    # the embed op may move 1.5 ms later and 0.4 ms earlier, the kernel
    # 1.55 ms and 1.8 ms: each inside its span
    assert early_ms * 1e6 - 0.4e6 <= shift <= early_ms * 1e6 + 1.5e6
    ns, n = trace_reduce.device_time_in(trace, "bench.lookup", 0, hi, shift)
    assert n == 40 and ns == pytest.approx(40 * 2.25e6)
    r = trace_reduce.reduce(trace, 0, hi)
    assert r["clock_offset_ns"] == shift
    # the layer loop and the op nested in it are busy 27.5 ms, not 28.5
    busy = 40 * (0.7 + 2.25 + 27.5) * 1e6
    assert r["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-3)
    labels = [g[0] for g in r["idle_gaps"]]
    assert labels[:2] == ["decode", "lookup"] and "embed" in labels


def test_no_tpu_plane_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {}, "spans": []}, 0, 1)


def test_lookup_counts_by_hand():
    # f32 plane: 2 queries over 1000 rows of 768
    assert lookup.least(2, 1000, 768, "f32") == (3_072_000, 3_072_000)
    # int8 plane: codes + a 4-byte scale a row, 10 rows rescored in f32
    assert lookup.least(2, 1000, 768, "int8", 10) == (3_102_720, 802_720)
    peak = load_peaks("TPU v5 lite")
    assert lookup.least_seconds(3_072_000, 3_072_000, peak) == \
        pytest.approx(3_072_000 / 819e9)
    assert lookup.rows_read(5000, None) == 5000
    assert lookup.rows_read(5000, [100, 1300]) == 1536
    assert lookup.rows_read(1000, [999]) == 1000


def test_engine_counts_by_hand():
    cfg = bench_testlib.tiny()[0]["model"]
    m = dict(cfg, num_hidden_layers=62, hidden_size=2560,
             intermediate_size=6400, num_attention_heads=40, q_lora_rank=768,
             kv_lora_rank=256, qk_nope_head_dim=64, qk_rope_head_dim=32,
             v_head_dim=64, vocab_size=73448)
    # attention 13,516,800 + MLP 49,152,000 matmul parameters a layer
    assert engine.layer_matmul_params(m) == 62_668_800
    # one decode token at kv length 100: 62 x (2 x 62,668,800 + 2 x 40 x
    # 100 x 160) + 2 x 2560 x 73448
    assert engine.token_ops(m, 100, True) == 8_226_344_960
    assert engine.decode_ops(m, [100, 100]) == 2 * 8_226_344_960
    # a 3-token prompt: positions attend 1, 2, 3 tokens; logits once
    per = 62 * 2 * 40 * 160
    assert engine.prefill_ops(m, 3) == 3 * 62 * 2 * 62_668_800 + per * 6 \
        + 2 * 2560 * 73448


def test_embed_counts_by_hand():
    e = {"batch": 2, "seq_len": 4, "hidden_size": 8, "intermediate_size": 16,
         "embedding_size": 4, "num_hidden_layers": 3}
    tok = 8
    per_layer = 2 * tok * (4 * 64 + 2 * 128) + 2 * 2 * 2 * 16 * 8
    assert embed.bucket_ops(e) == 2 * tok * 4 * 8 + 3 * per_layer


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        load_peaks("cpu")
    assert not math.isnan(load_peaks("TPU v5 lite")["bf16_flops_per_s"])
