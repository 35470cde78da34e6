"""Operations and bytes an exact top-1 lookup needs, from its shapes.

The least any scan in row order reads is the stored keys of every valid
row, except for a batch whose every query clears theta_R in the
reference: a scan may stop after the tile in which the last of its
queries first clears it (``rows_read``). Rescoring reads the candidate
rows' f32 keys once more.
"""
from __future__ import annotations

TILE = 512                      # rows per tile of a row-order scan


def rows_read(valid_rows: int, first_clear: list | None) -> int:
    """Rows a row-order scan must read: all valid rows, or up to the end
    of the tile holding the last query's first row over theta_R when
    every query has one (``first_clear`` lists those rows, else None)."""
    if not first_clear:
        return valid_rows
    last = max(first_clear)
    return min(valid_rows, (last // TILE + 1) * TILE)


def plane_of(cache: dict) -> str:
    """The key plane a configuration's ``cache`` block stores."""
    return "int8" if cache["backend"] == "pallas_q8" else "f32"


def key_bytes(rows: int, dim: int, plane: str) -> int:
    """Bytes of stored keys: f32 rows, or int8 codes plus an f32 scale."""
    if plane == "f32":
        return rows * dim * 4
    if plane == "int8":
        return rows * (dim + 4)
    raise ValueError(plane)


def least(batch: int, rows: int, dim: int, plane: str,
          rescored_rows: int = 0) -> tuple:
    """(operations, bytes) of one lookup of ``batch`` queries."""
    ops = 2 * batch * rows * dim + 2 * batch * rescored_rows * dim
    nbytes = key_bytes(rows, dim, plane) + rescored_rows * dim * 4
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time: the larger of the compute and the
    memory bound, at the chip's highest rates."""
    return max(ops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
