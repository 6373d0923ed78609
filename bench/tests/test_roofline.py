"""Kernel op and byte models on tiny shapes, counted by hand."""
from bench import common

FS = {"k_hd": 2, "k_ld": 1, "n_negatives": 1, "c_hd_non": 1, "c_hd_ld": 1,
      "c_hd_ld_non": 0, "c_hd_rand": 1, "c_ld_non": 1, "c_ld_hd": 0,
      "c_ld_rand": 1}
CONFIG = {"n": 3, "dim_hd": 4, "dim_ld": 2}


def _model(name):
    return common.load_module(common.BENCH / "roofline" / f"{name}.py")


def test_knn_merge_cand_hd_by_hand():
    # per row, w=4, K=2, C=3 (two_hop, one_hop, uniform):
    # bytes: query 16 + ids 8 + dists 8 + cands 3*(16+1) + tables 8+4
    #        + outputs 16 + 1 = 112
    # ops: 3 scored rows * 12 + dedup 3*(2+3) + merge 5 = 56
    ops, nbytes = _model("knn_merge_cand").hd(CONFIG, FS)
    assert (ops, nbytes) == (3 * 56, 3 * 112)


def test_knn_merge_cand_ld_by_hand():
    # per row, w=2, K=1, C=2 (two_hop, uniform), current row re-scored:
    # bytes: query 8 + ids 4 + cands 2*(8+1) + tables 8 + current row 8
    #        + outputs 8 + 1 = 55
    # ops: 3 scored rows * 6 + dedup 2*(1+2) + merge 3 = 27
    ops, nbytes = _model("knn_merge_cand").ld(CONFIG, FS)
    assert (ops, nbytes) == (3 * 27, 3 * 55)


def test_ne_forces_gather_by_hand():
    # per row, d=2, edges: 2 attraction + 2 repulsion (1 LD + 1 negative)
    # bytes: query 8 + 4 edges*(8+8) + 3 segments*(8+4) + 3 emitted*8 = 132
    # ops: 4*(8) + 2*(7) + 2*(10) = 66
    ops, nbytes = _model("ne_forces_gather").edge_mode(CONFIG, FS)
    assert (ops, nbytes) == (3 * 66, 3 * 132)
