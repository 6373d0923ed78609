"""Iterative joint KNN refinement (the paper's novel ANN subroutine).

Neighbour sets are fixed-width sorted arrays (idx, d2) of shape (n, K),
ascending in d2; invalid slots hold (SENTINEL, +inf).  Each iteration
generates a fixed number of candidates per point from several *sources*
(paper Sec. 3):

  - neighbours-of-neighbours within the same space (NND-style local join),
  - cross-space: LD neighbours (and their neighbours) proposed as HD
    candidates and vice versa -- this is the positive-feedback-loop channel,
  - uniform random probes (escape local minima; paper Fig. 7 'Disjointed'),
  - optionally reverse edges (Dong et al.'s local join; used by the NND
    baseline, off by default for FUnc-SNE).

All shapes are static -> one fused XLA/TPU program per iteration; the GPU
paper's ragged atomically-updated lists become a dense top-k merge.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

SENTINEL = jnp.iinfo(jnp.int32).max  # invalid-slot index marker


# --------------------------------------------------------------------------
# Counter-based hash RNG (§Perf H17: candidate-fused sampling)
#
# A splittable, order-invariant uniform generator: every draw is a pure
# int32 hash of ``(salt, row, draw)`` -- no carried PRNG state, no
# threefry chain in the step HLO, and the exact same arithmetic runs
# vectorised in jnp (the reference sampler below) and as scalar ops
# inside the Pallas gather kernel, so kernel-vs-ref parity is bit-exact.
# The mixer is the 'lowbias32' xorshift-multiply finalizer (Wellons'
# hash-prospector output); constants are pre-wrapped into int32 so
# multiplication relies only on two's-complement wraparound, which jnp,
# XLA and Mosaic all share.

_MIX1 = np.int32(np.uint32(0x21f0aaad))
_MIX2 = np.int32(np.uint32(0xd35a2d97))
_KEY_ROW = np.int32(np.uint32(0x85ebca6b))
_KEY_DRAW = np.int32(np.uint32(0xc2b2ae35))
_POS_MASK = np.int32(0x7fffffff)


def hash_mix(h):
    """lowbias32 finalizer on int32 bits (wrapping multiply semantics)."""
    h = h ^ jax.lax.shift_right_logical(h, 16)
    h = h * _MIX1
    h = h ^ jax.lax.shift_right_logical(h, 15)
    h = h * _MIX2
    h = h ^ jax.lax.shift_right_logical(h, 15)
    return h


def hash3(salt, row, draw):
    """Counter hash of ``(salt, row, draw)`` -> int32 uniform bits.

    All inputs are int32 scalars/arrays (broadcasting); two mix rounds so
    row and draw each pass through a full-avalanche finalizer.  Inputs
    are coerced to int32 so Python-int keys take the same wrapping
    multiply path as traced values (no eager-numpy overflow).
    """
    row = jnp.asarray(row, jnp.int32)
    draw = jnp.asarray(draw, jnp.int32)
    h = hash_mix(jnp.asarray(salt, jnp.int32) ^ (row * _KEY_ROW))
    return hash_mix(h ^ (draw * _KEY_DRAW))


def counter_randint(salt, row, draw, bound):
    """Uniform int32 in [0, bound) from the counter hash (31-bit mod)."""
    return (hash3(salt, row, draw) & _POS_MASK) % bound


def counter_uniform01(h):
    """int32 hash bits -> f32 uniform in [0, 1) (top 24 bits, exact)."""
    bits = jax.lax.shift_right_logical(h, 8)
    return bits.astype(jnp.float32) * np.float32(1.0 / (1 << 24))


def key_salt(rng):
    """Fold a PRNG key's raw bits into one int32 salt (no threefry ops).

    The key is only *read* (``jax.random.key_data``), never advanced, so
    deriving per-step salts from the carried state key adds zero random-op
    HLO to the step.
    """
    data = jax.lax.bitcast_convert_type(
        jax.random.key_data(rng).reshape(-1), jnp.int32)
    salt = jnp.int32(0)
    for i in range(data.shape[0]):
        salt = hash_mix(salt ^ data[i])
    return salt


def as_salt(rng_or_salt):
    """Coerce a phase RNG argument to an int32 salt.

    The step driver passes the already-folded base salt (an int32
    scalar, passthrough); direct phase calls (tests, external drivers)
    may still hand a PRNG key, whose raw bits are folded via
    :func:`key_salt`.
    """
    x = jnp.asarray(rng_or_salt)
    if x.ndim == 0 and x.dtype == jnp.int32:
        return x
    return key_salt(rng_or_salt)


def counter_candidates(salt, rows, sources, first_tables=(),
                       second_tables=(), n_total=None, extra=None):
    """Pure-jnp reference of the candidate-fused sampler (§Perf H17).

    Generates the (B, C) candidate block that ``knn_merge``'s
    candidate-fused kernel derives in-kernel, with bit-identical draws:
    slot ``g`` of row ``r`` consumes ``hash3(salt, rows[r], 2g)`` (the
    'a' stream) and, for two-hop slots, ``hash3(salt, rows[r], 2g+1)``
    (the 'b' stream).  Being keyed on *global* row ids makes the draws
    order- and shard-invariant: a row samples the same candidates
    whichever device or batch slice it lands in.

    ``sources`` is a static tuple describing the candidate layout:
      ("uniform", c)           c uniform probes over [0, n_total)
      ("one_hop", f, c)        c entries of ``first_tables[f]`` (own row)
      ("two_hop", f, s, c)     c chained picks
                               ``second_tables[s][first_tables[f][r, a], b]``
                               (SENTINEL mids fall back to the row id);
                               the gather is flat
                               (``reshape(-1)``), so no (B, c, K2)
                               broadcast exists in the HLO
      ("extra", c)             c precomputed candidates from ``extra``
                               (e.g. cached reverse edges); consumes slot
                               ids but no draws
    """
    b = rows.shape[0]
    rows_c = rows.astype(jnp.int32)[:, None]
    parts = []
    g = 0
    e0 = 0
    for src in sources:
        kind, c = src[0], src[-1]
        if c == 0:
            continue
        slots = g + jnp.arange(c, dtype=jnp.int32)[None, :]
        if kind == "uniform":
            cand = counter_randint(salt, rows_c, 2 * slots, n_total)
        elif kind == "one_hop":
            f = first_tables[src[1]]
            a = counter_randint(salt, rows_c, 2 * slots, f.shape[1])
            cand = jnp.take_along_axis(f, a, axis=1)
        elif kind == "two_hop":
            cand = two_hop_picks(salt, rows_c, slots,
                                 first_tables[src[1]],
                                 second_tables[src[2]])
        elif kind == "extra":
            cand = extra[:, e0:e0 + c]
            e0 += c
        else:
            raise ValueError(f"unknown candidate source {kind!r}")
        parts.append(cand.astype(jnp.int32))
        g += c
    if not parts:
        return jnp.zeros((b, 0), jnp.int32)
    return jnp.concatenate(parts, axis=1)


def two_hop_picks(salt, rows_c, slots, first, second):
    """``second[first[r, a], b]`` for counter draws (a, b) of ``slots``.

    rows_c: (B, 1) global row ids; slots: (1, c) slot ids; first: (B, K1)
    the rows' own lists; second: (N2, K2) global table.  SENTINEL mids
    fall back to the row id.  The gather is flat
    (``reshape(-1)``), so no (B, c, K2) broadcast exists in the HLO.
    """
    n2, k2 = second.shape
    a = counter_randint(salt, rows_c, 2 * slots, first.shape[1])
    mid = jnp.take_along_axis(first, a, axis=1)
    mid = jnp.where(mid == SENTINEL, rows_c % n2, mid)
    mid = jnp.clip(mid, 0, n2 - 1)
    b = counter_randint(salt, rows_c, 2 * slots + 1, k2)
    return second.reshape(-1)[mid * k2 + b]


def counter_fill(salt, n, r):
    """(n, r) uniform fill table for ``reverse_neighbors`` (counter RNG)."""
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    draws = jnp.arange(r, dtype=jnp.int32)[None, :]
    return counter_randint(salt, rows, draws, n)


def init_knn_idx(rng, n_rows, n_total, k, row_offset: int = 0):
    """Random initial neighbour sets (paper: 'randomly initialised').

    Rows are (random base + 0..k-1) mod n: distinct within a row by
    construction (duplicate entries would double-count forces and violate
    the merge invariants); diversity comes from the first refinements.
    """
    assert k <= n_total - 1, (k, n_total)
    base = jax.random.randint(rng, (n_rows, 1), 0, n_total, dtype=jnp.int32)
    rows = row_offset + jnp.arange(n_rows, dtype=jnp.int32)[:, None]
    # offsets in [1, n_total-1]: distinct and never 0 (no self-loops)
    offs = 1 + (base + jnp.arange(k, dtype=jnp.int32)[None, :]) \
        % (n_total - 1)
    return ((rows + offs) % n_total).astype(jnp.int32)


def reverse_neighbors(idx, n_total, r, *, fill):
    """Sampled reverse edges: up to ``r`` points that list i as a neighbour.

    Built with one argsort over the E = n*K directed edges (TPU-friendly
    replacement for the GPU scatter-append).  Rows with fewer than r reverse
    edges are padded from the caller's (n_total, r) ``fill`` table of
    uniform random points (:func:`counter_fill`).

    The full rebuild costs an argsort over all n*K directed edges, so
    callers cache the result in state and refresh it every
    ``rev_refresh`` steps (``rev_refresh=1`` == a rebuild every
    iteration, bit-for-bit).
    """
    n, k = idx.shape
    tgt = idx.reshape(-1)
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    order = jnp.argsort(tgt)
    tgt_s = tgt[order]
    src_s = src[order]
    starts = jnp.searchsorted(tgt_s, jnp.arange(n_total, dtype=jnp.int32))
    counts = jnp.diff(jnp.append(starts, tgt_s.shape[0]))
    pos = starts[:, None] + jnp.arange(r)[None, :]
    valid = jnp.arange(r)[None, :] < counts[:, None]
    gathered = src_s[jnp.clip(pos, 0, src_s.shape[0] - 1)]
    return jnp.where(valid, gathered, fill)


def dedup_candidates(rows, cur_idx, cand_idx):
    """Mark duplicate candidates invalid.

    A candidate is invalid if it equals the row's own id, an existing
    neighbour, or an earlier candidate in the same row.  Returns a bool mask.
    """
    self_dup = cand_idx == rows[:, None]
    in_cur = jnp.any(cand_idx[:, :, None] == cur_idx[:, None, :], axis=-1)
    earlier = cand_idx[:, :, None] == cand_idx[:, None, :]
    c = cand_idx.shape[1]
    tri = jnp.tril(jnp.ones((c, c), bool), k=-1)
    within = jnp.any(earlier & tri[None], axis=-1)
    sentinel = cand_idx == SENTINEL
    return ~(self_dup | in_cur | within | sentinel)


def merge_knn(cur_idx, cur_d, cand_idx, cand_d, valid_mask):
    """Merge candidates into the sorted K-NN arrays.

    Returns (idx, d, row_improved).  row_improved is True iff at least one
    candidate was admitted (drives the paper's refresh probability and the
    sigma refresh flags).
    """
    k = cur_idx.shape[1]
    cand_d = jnp.where(valid_mask, cand_d, jnp.inf)
    all_idx = jnp.concatenate([cur_idx, cand_idx], axis=1)
    all_d = jnp.concatenate([cur_d, cand_d], axis=1)
    neg_top, pos = jax.lax.top_k(-all_d, k)       # k smallest distances
    new_d = -neg_top
    new_idx = jnp.take_along_axis(all_idx, pos, axis=1)
    worst = cur_d[:, -1]
    improved = jnp.any(cand_d < worst[:, None], axis=1)
    return new_idx, new_d, improved


@functools.partial(jax.jit, static_argnames=("k",))
def exact_knn(X, k: int, active=None):
    """O(N^2) exact KNN (ground truth for tests/benchmarks; small N only)."""
    n = X.shape[0]
    n2 = jnp.sum(X * X, axis=1)
    d2 = n2[:, None] + n2[None, :] - 2.0 * (X @ X.T)
    d2 = jnp.maximum(d2, 0.0)
    d2 = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, d2)  # not eye*inf: 0*inf=NaN
    if active is not None:
        d2 = jnp.where(active[None, :], d2, jnp.inf)
    neg_top, idx = jax.lax.top_k(-d2, k)
    return idx.astype(jnp.int32), -neg_top


@functools.partial(jax.jit, static_argnames=("k", "block"))
def exact_knn_rows(X, rows, k: int, block: int = 4096):
    """Exact KNN of the query rows ``X[rows]`` over all of ``X``, in blocks.

    The reference at any n: columns stream in blocks of ``block`` with a
    running top-k merge, so memory is O(len(rows) * block) and the n x n
    matrix of :func:`exact_knn` is never built.  Self-matches are
    excluded; distance ties keep the lower index first, as ``exact_knn``
    does.  Products run at HIGHEST precision (a TPU rounds f32 matmul
    operands to bf16 by default, which would reorder near neighbours).
    Returns ((S, k) int32 ids, (S, k) f32 squared distances).
    """
    X = X.astype(jnp.float32)
    n = X.shape[0]
    rows = rows.astype(jnp.int32)
    q = X[rows]
    qn = jnp.sum(q * q, axis=1)[:, None]
    n_blocks = -(-n // block)
    Xp = jnp.pad(X, ((0, n_blocks * block - n), (0, 0)))
    xn = jnp.sum(Xp * Xp, axis=1)

    def body(best, j):
        best_d, best_i = best
        xb = jax.lax.dynamic_slice_in_dim(Xp, j * block, block)
        xnb = jax.lax.dynamic_slice_in_dim(xn, j * block, block)
        d2 = qn + xnb[None, :] - 2.0 * jnp.dot(
            q, xb.T, precision=jax.lax.Precision.HIGHEST)
        col = j * block + jnp.arange(block, dtype=jnp.int32)[None, :]
        d2 = jnp.where((col == rows[:, None]) | (col >= n), jnp.inf,
                       jnp.maximum(d2, 0.0))
        neg_top, pos = jax.lax.top_k(
            -jnp.concatenate([best_d, d2], axis=1), k)
        # positions < k are the running best, the rest this block's cols
        kept = jnp.take_along_axis(best_i, jnp.minimum(pos, k - 1), axis=1)
        idx = jnp.where(pos < k, kept, j * block + pos - k)
        return (-neg_top, idx), None

    s = rows.shape[0]
    init = (jnp.full((s, k), jnp.inf, jnp.float32),
            jnp.full((s, k), -1, jnp.int32))
    (d, idx), _ = jax.lax.scan(body, init,
                               jnp.arange(n_blocks, dtype=jnp.int32))
    return idx, d
