"""Operations and bytes of one ``knn_merge_cand`` launch, from its shapes.

The candidate-fused merge generates each row's candidates from its
sources, scores them, drops duplicates and merges them into the row's
sorted list.  Counted as the algorithm needs them, every row at its
unpadded width ``w`` (M for the HD call, d for the LD call):

bytes per query row
  query row w*4; current ids K*4, and current distances K*4 where they
  are passed in (HD); per candidate its row w*4, its activity flag 1,
  the first-table entry its source reads 4 (one- and two-hop) and the
  second-table entry a two-hop pick reads 4; for LD the K current rows
  w*4 each again, re-scored because the embedding moved; outputs ids and
  distances K*8 and the improved flag 1.
operations per query row
  3w per scored row (difference, square, accumulate); C*(K+C) duplicate
  compares; K+C merge compares.
"""
from __future__ import annotations


def launch(n: int, w: int, k: int, sources, *, rescore_current: bool,
           with_cur_d: bool) -> tuple:
    """(ops, bytes) of one launch over ``n`` rows.  ``sources``: tuples
    (kind, count) with kind "one_hop", "two_hop" or "uniform"."""
    c = sum(cnt for _, cnt in sources)
    table = sum(cnt * {"one_hop": 4, "two_hop": 8, "uniform": 0}[kind]
                for kind, cnt in sources)
    scored = c + (k if rescore_current else 0)
    row_bytes = (w * 4 + k * 4 + (k * 4 if with_cur_d else 0)
                 + c * (w * 4 + 1) + table
                 + (k * w * 4 if rescore_current else 0)
                 + k * 8 + 1)
    row_ops = scored * 3 * w + c * (k + c) + (k + c)
    return float(n * row_ops), float(n * row_bytes)


def hd(config: dict, fs: dict) -> tuple:
    sources = (("two_hop", fs["c_hd_non"]), ("one_hop", fs["c_hd_ld"]),
               ("two_hop", fs["c_hd_ld_non"]), ("uniform", fs["c_hd_rand"]))
    return launch(config["n"], config["dim_hd"], fs["k_hd"], sources,
                  rescore_current=False, with_cur_d=True)


def ld(config: dict, fs: dict) -> tuple:
    sources = (("two_hop", fs["c_ld_non"]), ("one_hop", fs["c_ld_hd"]),
               ("uniform", fs["c_ld_rand"]))
    return launch(config["n"], config["dim_ld"], fs["k_ld"], sources,
                  rescore_current=True, with_cur_d=False)
