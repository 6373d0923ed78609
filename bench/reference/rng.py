"""NumPy copy of the program's counter hash and the draws made from it.

The step draws its refinement gate, its refinement candidates and its
negative samples as ``hash3(salt, row, draw)`` of a 'lowbias32'
finalizer (``core/knn.py``), with one stream tag per phase.  The
reference draws the same ones from the same counters; uint32 arithmetic
here wraps exactly as the program's int32 arithmetic with logical
shifts.
"""
from __future__ import annotations

import numpy as np

_MIX1 = np.uint32(0x21f0aaad)
_MIX2 = np.uint32(0xd35a2d97)
_KEY_ROW = np.uint32(0x85ebca6b)
_KEY_DRAW = np.uint32(0xc2b2ae35)
_POS_MASK = np.uint32(0x7fffffff)
# stream tags of the step's phases: gate, HD and LD candidates, negatives
TAG_GATE, TAG_HD, TAG_LD, TAG_NEG = 1, 2, 3, 4
# an empty list slot
SENTINEL = int(np.iinfo(np.int32).max)


def _u32(x) -> np.ndarray:
    return np.asarray(x).astype(np.int64).astype(np.uint32)


def hash_mix(h):
    h = _u32(h)
    with np.errstate(over="ignore"):
        h = h ^ (h >> np.uint32(16))
        h = h * _MIX1
        h = h ^ (h >> np.uint32(15))
        h = h * _MIX2
        h = h ^ (h >> np.uint32(15))
    return h


def hash3(salt, row, draw):
    with np.errstate(over="ignore"):
        h = hash_mix(_u32(salt) ^ (_u32(row) * _KEY_ROW))
        return hash_mix(h ^ (_u32(draw) * _KEY_DRAW))


def key_salt(key_words) -> np.uint32:
    """Fold a PRNG key's raw 32-bit words into one salt."""
    salt = np.uint32(0)
    for w in np.asarray(key_words).reshape(-1):
        salt = hash_mix(salt ^ _u32(w))
    return salt


def counter_randint(salt, row, draw, bound: int) -> np.ndarray:
    """Uniform ints in [0, bound) from the counter hash (31-bit mod)."""
    return ((hash3(salt, row, draw) & _POS_MASK).astype(np.int64)
            % int(bound))


def counter_uniform01(h) -> np.float32:
    """Hash bits -> float32 uniform in [0, 1) from the top 24 bits."""
    return np.float32(_u32(h) >> np.uint32(8)) * np.float32(1.0 / (1 << 24))


def candidates(salt, sources, firsts, seconds, n_total: int, start: int = 0,
               stop: int | None = None) -> np.ndarray:
    """(rows, C) candidates of rows ``[start, stop)`` (all by default):
    slot ``g`` of row ``r`` draws ``hash3(salt, r, 2g)`` and, for a
    two-hop pick, ``hash3(salt, r, 2g + 1)``.  ``sources`` lists the
    candidate groups in slot order: ``("uniform", c)`` over [0, n_total),
    ``("one_hop", f, c)`` entries of the row's own list ``firsts[f]``,
    ``("two_hop", f, s, c)`` picks ``seconds[s][firsts[f][r, a], b]`` (an
    empty first hop falls back to the row itself)."""
    stop = firsts[0].shape[0] if stop is None else stop
    rows = np.arange(start, stop, dtype=np.int64)[:, None]
    firsts = [f[start:stop] for f in firsts]
    parts, g = [], 0
    for src in sources:
        kind, c = src[0], int(src[-1])
        if c == 0:
            continue
        slots = g + np.arange(c, dtype=np.int64)[None, :]
        if kind == "uniform":
            cand = counter_randint(salt, rows, 2 * slots, n_total)
        elif kind == "one_hop":
            first = firsts[src[1]]
            a = counter_randint(salt, rows, 2 * slots, first.shape[1])
            cand = np.take_along_axis(first, a, axis=1)
        elif kind == "two_hop":
            first, second = firsts[src[1]], seconds[src[2]]
            a = counter_randint(salt, rows, 2 * slots, first.shape[1])
            mid = np.take_along_axis(first, a, axis=1).astype(np.int64)
            mid = np.where(mid == SENTINEL, rows % second.shape[0], mid)
            mid = np.clip(mid, 0, second.shape[0] - 1)
            b = counter_randint(salt, rows, 2 * slots + 1, second.shape[1])
            cand = second[mid, b]
        else:
            raise ValueError(f"candidate source {kind!r} has no reference")
        parts.append(np.asarray(cand, np.int64))
        g += c
    if not parts:
        return np.zeros((rows.shape[0], 0), np.int64)
    return np.concatenate(parts, axis=1)
