"""Plain NumPy reference of one step's two neighbour refinements.

FUnc-SNE refines each row's HD list (against the data) and LD list
(against the embedding) by merging a few sampled candidates into it
(paper Sec. 3): friends of friends in either space, entries of the
other space's list, and uniform probes.  HD refinement runs behind a
stochastic gate whose probability follows the share of rows that
improved lately; LD refinement runs every step and re-scores the
current list too, since the embedding moved.

Given the state before a step, this redraws the gate and every row's
candidates from the same counters as the program (``rng.py``), forms
each row's union of current list and valid candidates (a candidate is
valid unless it is the row itself, already listed, repeated within the
row, or empty), and judges the program's new lists against it: every
kept entry must come from the union, and no entry of the union may be
left out while the list keeps a farther one.
"""
from __future__ import annotations

import numpy as np

from bench.reference import rng as rng_ref
from bench.reference import step as step_ref

# a left-out entry counts when it is nearer than the list's farthest by
# more than this share of max(farthest, median listed distance): float32
# distances tie to within ~1e-6 of that scale
TIE = 1e-5


def hd_sources(fs: dict) -> tuple:
    """HD candidate groups in slot order: friends of HD friends, LD
    friends, friends of LD friends, uniform probes."""
    if int(fs.get("c_hd_rev", 0)):
        raise ValueError("reverse-edge candidates have no reference")
    return (("two_hop", 0, 0, fs["c_hd_non"]), ("one_hop", 1, fs["c_hd_ld"]),
            ("two_hop", 1, 1, fs["c_hd_ld_non"]),
            ("uniform", fs["c_hd_rand"]))


def ld_sources(fs: dict) -> tuple:
    """LD candidate groups in slot order: friends of LD friends, HD
    friends, uniform probes."""
    return (("two_hop", 0, 0, fs["c_ld_non"]), ("one_hop", 1, fs["c_ld_hd"]),
            ("uniform", fs["c_ld_rand"]))


def gate_fires(pre: dict, fs: dict) -> bool:
    """Whether the step refines the HD lists: a counter draw under
    ``min_refresh_prob + (1 - min_refresh_prob) * ema_new_frac``
    (float32)."""
    f32 = np.float32
    base = rng_ref.key_salt(pre["key"])
    u = rng_ref.counter_uniform01(rng_ref.hash3(base, int(pre["step"]),
                                                rng_ref.TAG_GATE))
    m = float(fs["min_refresh_prob"])
    p = f32(m) + f32(1.0 - m) * f32(pre["ema_new_frac"])
    return bool(u < np.clip(p, f32(0.0), f32(1.0)))


def _salt(pre: dict, tag: int):
    return rng_ref.hash3(rng_ref.key_salt(pre["key"]), int(pre["step"]), tag)


def hd_union(pre: dict, fs: dict) -> np.ndarray:
    """(n, K + C) ids the new HD lists may hold, -1 where a candidate is
    invalid; no candidates where the gate stays shut."""
    cur = pre["hd_idx"].astype(np.int64)
    if not gate_fires(pre, fs):
        return cur
    ld = pre["ld_idx"].astype(np.int64)
    salt, sources = _salt(pre, rng_ref.TAG_HD), hd_sources(fs)
    return _union(cur, lambda s, e: rng_ref.candidates(
        salt, sources, (cur, ld), (cur, ld), cur.shape[0], s, e),
        pre["active"])


def ld_union(pre: dict, post: dict, fs: dict) -> np.ndarray:
    """(n, K + C) ids the new LD lists may hold; the HD friends drawn are
    those of the step's own new HD lists."""
    cur = pre["ld_idx"].astype(np.int64)
    hd = post["hd_idx"].astype(np.int64)
    salt, sources = _salt(pre, rng_ref.TAG_LD), ld_sources(fs)
    return _union(cur, lambda s, e: rng_ref.candidates(
        salt, sources, (cur, hd), (cur,), cur.shape[0], s, e),
        pre["active"])


def _union(cur, draw, active) -> np.ndarray:
    """Each row's list ``cur`` and its candidates ``draw(s, e)`` (rows
    ``[s, e)``), -1 where a candidate is invalid."""
    n = cur.shape[0]

    def block(s, e):
        cb, rows = draw(s, e), np.arange(s, e)[:, None]
        c = cb.shape[1]
        tri = np.tri(c, c, -1, dtype=bool)[None]
        bad = (cb < 0) | (cb >= n) | (cb == rows)
        bad |= np.any(cb[:, :, None] == cur[s:e, None, :], axis=-1)
        bad |= np.any((cb[:, :, None] == cb[:, None, :]) & tri, axis=-1)
        bad |= ~active[np.clip(cb, 0, n - 1)]
        return np.concatenate([cur[s:e], np.where(bad, -1, cb)], axis=1)

    return np.concatenate(step_ref.by_rows(block, n))


def union_sqdist(A, union, r) -> np.ndarray:
    """Squared distances of every union entry, inf where it is invalid."""
    d = step_ref.sqdist(A, np.clip(union, 0, A.shape[0] - 1), r)
    return np.where(union >= 0, d, np.inf)


def best(union, d_union, k: int) -> np.ndarray:
    """The reference's new lists: the ``k`` nearest of each union."""
    order = np.argsort(d_union, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(union, order, axis=1)


def merge_faults(new, union, d_union) -> int:
    """Entries of the new lists not in the union, plus union entries left
    out while the list keeps one farther by more than ``TIE``."""
    n, k = new.shape
    d_kept = np.full((n, k), np.inf)
    found = np.zeros((n, k), bool)
    left_out = np.zeros(union.shape, bool)

    def match(s, e):
        hit = union[s:e, None, :] == new[s:e, :, None]
        found[s:e] = hit.any(axis=2)
        d_kept[s:e] = np.where(found[s:e], np.take_along_axis(
            d_union[s:e], hit.argmax(axis=2), axis=1), np.inf)
        left_out[s:e] = (union[s:e] >= 0) & ~hit.any(axis=1)

    step_ref.by_rows(match, n)
    # the median over every row's kept distances
    median = np.median(d_kept[found]) if found.any() else 0.0

    def faults(s, e):
        worst = np.max(np.where(found[s:e], d_kept[s:e], -np.inf), axis=1)
        scale = np.maximum(worst, median)
        missed = left_out[s:e] & \
            (d_union[s:e] < (worst - TIE * scale)[:, None])
        return int(np.sum(~found[s:e]) + np.sum(missed))

    return sum(step_ref.by_rows(faults, n))
