"""Operations and bytes of one ``ne_forces_gather`` launch in edge mode.

One launch evaluates the variable-tail forces of every row over its
concatenated neighbour axis: the HD list (attraction, k_hd), the LD list
(repulsion, k_ld) and the negative samples (repulsion, n_negatives), and
writes the per-edge forces of the first two segments for the symmetric
reactions.  Counted as the algorithm needs them, rows at width d:

bytes per row
  query row d*4; per edge its id 4, its coefficient 4 and its neighbour
  row d*4; outputs per segment the aggregate d*4 and the weight sum 4,
  and per emitted edge its force d*4.
operations per edge
  3d+2 for the distance and the kernel base (difference, square and
  accumulate, divide and add); attraction 2d+3 more (reciprocal,
  coefficient product, weight sum, d products, d sums); repulsion 2d+6
  more (logarithm, two exponentials, two products, weight sum, d
  products, d sums).
"""
from __future__ import annotations


def launch(n: int, d: int, fs: dict) -> tuple:
    k_a = fs["k_hd"]
    k_r = fs["k_ld"] + fs["n_negatives"]
    edges = k_a + k_r
    emitted = fs["k_hd"] + fs["k_ld"]
    row_bytes = d * 4 + edges * (8 + d * 4) + 3 * (d * 4 + 4) \
        + emitted * d * 4
    row_ops = edges * (3 * d + 2) + k_a * (2 * d + 3) + k_r * (2 * d + 6)
    return float(n * row_ops), float(n * row_bytes)


def edge_mode(config: dict, fs: dict) -> tuple:
    return launch(config["n"], config["dim_ld"], fs)
