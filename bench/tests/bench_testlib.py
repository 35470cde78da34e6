"""Shared set-up of the benchmark's CPU tests: the import paths, and a cell
at tiny size (a reduced engine, a small corpus, short outputs; the encoder
keeps its published widths, since reduced, its random embeddings of
unrelated prompts are too alike to tell hits from misses)."""
import copy
import os
import pathlib
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# limits for the tiny cell on the CPU, from its readings: the program's
# lookup sims are off by f32 rounding alone (<= 1e-6), its bf16 encoder and
# engine by 0.003 and 0.002; the float8 controls by 0.09 each
TINY_LIMITS = {"lookup_sim": 2e-6, "embed_dist": 0.02, "token_gap": 0.02}
TEST_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny(cell_name: str = "faq-f32", rate: float = 8.0):
    """(cfg, mix, cell) of ``cell_name`` cut to a size a test can hold."""
    from harness.cell import load_cell
    _, _, cfg, mix, cell = load_cell(cell_name)
    cfg, mix, cell = copy.deepcopy(cfg), copy.deepcopy(mix), \
        copy.deepcopy(cell)
    cfg["model"].update(
        num_hidden_layers=2, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, vocab_size=4096)
    cfg["engine"] = {"n_slots": 4, "max_len": 512}
    cfg["cache"].update(corpus_rows=2000, spill_rows=512)
    mix["hot"]["n"], mix["cold"]["n"] = 200, 5000
    mix["output_len"].update(median=20, max=40)
    cell.update(rate=rate, drain_s=30)
    cell["check"].update(lookups=64, embeds=16, tokens=100)
    cell["limits"] = dict(TINY_LIMITS)
    return cfg, mix, cell


def run_tiny(seconds: float = 4.0, seed: int = 12345678901, trace=False,
             controls=False, cell_name="faq-f32", bench=BENCH, parts=None,
             out_dir=None):
    from harness.cell import run_cell
    cfg, mix, cell = parts or tiny(cell_name)
    return run_cell(cell_name, seed, seconds, trace,
                    t_start=time.perf_counter(), cfg=cfg, mix=mix, cell=cell,
                    peaks=TEST_PEAKS, controls=controls, bench=bench,
                    out_dir=out_dir, log=lambda m: None)
