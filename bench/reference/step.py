"""Plain NumPy reference of one FUnc-SNE update, and of the stored distances.

Given the state before a step (embedding, velocity, gains, Z estimate,
step count, key) and the neighbour lists and bandwidths the step's
refinement phases left, this recomputes what the step's force phase
must produce: the HD affinities from squared distances it computes
itself, the variable-tail kernel forces over the HD list (attraction),
the LD list and the counter-drawn negative samples (repulsion), the
symmetric reactions, the Z estimate, and the gains and momentum update
(paper Eqs. 1, 4 and 6; t-SNE gains).  It also recomputes the squared
distances the refinement phases store for their lists.

``precision="float64"`` is the reference.  ``"bfloat16"`` rounds every
intermediate to bfloat16: the control that a check must refuse.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

from bench.reference import rng as rng_ref

ROW_BLOCK = 8192
# NumPy releases the GIL in its ufuncs, gathers and reductions, so blocks
# of rows run on threads, one per host core up to this many
MAX_THREADS = 32


def threads() -> int:
    """Threads that work on blocks of rows: one per host core, at most
    ``MAX_THREADS``."""
    return min(os.cpu_count() or 1, MAX_THREADS)


def pool_map(fn, items) -> list:
    """``fn`` of each item on ``threads()`` threads, results in order."""
    with ThreadPoolExecutor(threads()) as pool:
        return list(pool.map(fn, items))


def by_rows(fn, n: int, rows: int | None = None) -> list:
    """``fn(s, e)`` for each block ``[s, e)`` of ``rows`` (by default
    ``ROW_BLOCK``) rows of ``n`` (one empty block where ``n`` is 0),
    results in block order.  Work that is independent by row gives the
    same bits in any block."""
    rows = rows or ROW_BLOCK
    return pool_map(lambda s: fn(s, min(s + rows, n)),
                    range(0, max(n, 1), rows))


def rounder(precision: str):
    if precision == "float64":
        return lambda a: np.asarray(a, np.float64)
    if precision == "bfloat16":
        return lambda a: np.asarray(a, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def schedule(hp: dict, step: int, n_iter: int) -> dict:
    """The batch schedule the configuration runs: early exaggeration x12
    at momentum 0.5 for the first quarter of ``n_iter``, then a linear
    learning-rate decay to a tenth (float32 arithmetic)."""
    f32 = np.float32
    ee_until = max(1, n_iter // 4)
    out = dict(hp)
    early = step < ee_until
    out["exaggeration"] = f32((12.0 if early else 1.0) * hp["exaggeration"])
    out["momentum"] = f32(0.5 if early else hp["momentum"])
    frac = max(f32(0.0), f32(step - ee_until) / f32(max(1, n_iter - ee_until)))
    out["lr"] = f32(hp["lr"]) * (f32(1.0) - f32(0.9) * f32(frac))
    return out


def sqdist(A, idx, r):
    """Squared distances ``|A[i] - A[idx[i, k]]|^2`` for every row i, in
    blocks of rows."""
    A = r(A)
    out = np.empty(idx.shape, np.float64)

    def block(s, e):
        diff = r(A[idx[s:e]] - A[s:e, None, :])
        out[s:e] = r(np.sum(r(diff * diff), axis=-1))

    # a block gathers as many entries as ROW_BLOCK rows of 32 scalars
    per_row = max(1, idx.shape[1] * A.shape[1])
    by_rows(block, idx.shape[0], max(1, ROW_BLOCK * 32 // per_row))
    return out


def _segment(Y, own, idx, coef, alpha, mode, r):
    """Per-edge forces of one neighbour segment (variable-tail kernel
    w = (1 + d2/alpha)^-alpha) of the rows ``own`` (rows of ``Y``) with
    neighbours ``idx``: returns (agg, edge, wsum, sum |edge|)."""
    delta = r(Y[idx] - own[:, None, :])
    d2 = r(np.sum(r(delta * delta), axis=-1))
    base = r(1.0 + r(d2 / alpha))
    if mode == "attraction":
        wexp = r(1.0 / base)                       # w^(1/alpha)
        c = r(coef * wexp)
        edge = r(c[..., None] * delta)             # pull towards the nbr
        wsum = r(np.sum(c, axis=-1))
    else:
        wexp = r(np.power(base, -(alpha + 1.0)))  # w^(1 + 1/alpha)
        w = r(np.power(base, -alpha))
        edge = r(r(coef * wexp)[..., None] * -delta)
        wsum = r(np.sum(r(coef * w), axis=-1))
    return r(np.sum(edge, axis=1)), edge, wsum, np.sum(np.abs(edge), 1)


def _scatter(*pairs) -> list:
    """For each ``(idx, edge)``: out[idx[i, k]] += edge[i, k] (where the
    symmetric reactions go).  Each column is one ``bincount`` over all
    rows; the columns run on threads."""
    def column(job):
        idx, edge, j = job
        return np.bincount(idx.reshape(-1), weights=edge[..., j].reshape(-1),
                           minlength=idx.shape[0])

    d = pairs[0][1].shape[-1]
    sums = pool_map(column, [(idx, edge, j) for idx, edge in pairs
                             for j in range(d)])
    return [np.stack(sums[i:i + d], axis=1) for i in range(0, len(sums), d)]


def one_step(pre: dict, post: dict, hd_d, hp: dict, fs: dict,
             precision: str = "float64") -> dict:
    """The force phase and update of one step.

    ``pre``: Y, vel, gains (n, d), zhat, step, key (raw words).
    ``post``: hd_idx, ld_idx, beta after the step's refinement phases.
    ``hd_d``: the reference's squared distances of the HD lists.
    ``fs``: the configuration's FuncSNEConfig fields (k_ld, n_negatives).
    Returns the reference's vel, Y, gains, zhat, and ``scale``: per entry
    the magnitude of what the new velocity sums (|momentum * vel| plus
    lr * gains * 4 * the absolute force contributions), the yardstick of
    its rounding.
    """
    r = rounder(precision)
    Y = r(pre["Y"])
    n, d = Y.shape
    hd_idx = np.clip(post["hd_idx"].astype(np.int64), 0, n - 1)
    ld_idx = np.clip(post["ld_idx"].astype(np.int64), 0, n - 1)
    alpha = float(hp["alpha"])
    salt = rng_ref.hash3(rng_ref.key_salt(pre["key"]), int(pre["step"]),
                         rng_ref.TAG_NEG)
    n_neg = int(fs["n_negatives"])

    def rows(s, e):
        # attraction coefficients p_{j|i} / (2 n) over the HD list (Eq. 1)
        hd_b = hd_d[s:e]
        dmin = hd_b.min(axis=1, keepdims=True)
        ex = r(np.exp(r(-r(post["beta"][s:e])[:, None] * r(hd_b - dmin))))
        p = r(ex / np.maximum(r(np.sum(ex, axis=1, keepdims=True)), 1e-30))
        coef_a = r(p / (2.0 * n))
        # negative samples: the program's counter draws
        own = np.arange(s, e, dtype=np.int64)[:, None]
        neg = rng_ref.counter_randint(salt, own, np.arange(n_neg)[None, :],
                                      n)
        neg = np.where(neg == own, (neg + 1) % n, neg)
        agg_a, edge_a, _, abs_a = _segment(Y, Y[s:e], hd_idx[s:e], coef_a,
                                           alpha, "attraction", r)
        rep = _segment(Y, Y[s:e], ld_idx[s:e], 0.5, alpha, "repulsion", r)
        agg_n, _, wsum_n, abs_n = _segment(Y, Y[s:e], neg, 1.0, alpha,
                                           "repulsion", r)
        return (agg_a, edge_a, abs_a) + rep + (agg_n, wsum_n, abs_n)

    (agg_a, edge_a, abs_a, agg_r, edge_r, wsum_r, abs_r,
     agg_n, wsum_n, abs_n) = (np.concatenate(part)
                              for part in zip(*by_rows(rows, n)))

    scale_neg = max(n - 1.0 - int(fs["k_ld"]), 1.0) / n_neg
    z_est = max(float(r(2.0 * np.sum(wsum_r) + scale_neg * np.sum(wsum_n))),
                1e-8)
    zhat = z_est if int(pre["step"]) == 0 else \
        0.9 * float(pre["zhat"]) + 0.1 * z_est
    zhat = float(r(zhat))
    attr_s = float(hp["attraction"]) * float(hp["exaggeration"])
    rep_s = float(hp["repulsion"]) / zhat

    react_a, react_r, mag_a, mag_r = _scatter(
        (hd_idx, r(attr_s * edge_a)), (ld_idx, r(rep_s * edge_r)),
        (hd_idx, np.abs(edge_a)), (ld_idx, np.abs(edge_r)))
    buf = r(attr_s * agg_a + rep_s * r(agg_r + scale_neg * agg_n))
    buf = buf - react_a - react_r
    dY = r(4.0 * r(buf))
    mag = 4.0 * (attr_s * (abs_a + mag_a)
                 + rep_s * (abs_r + scale_neg * abs_n + mag_r))

    vel0, gains0 = r(pre["vel"]), r(pre["gains"])
    same = np.sign(dY) == np.sign(vel0)
    gains = np.clip(np.where(same, gains0 + 0.2, gains0 * 0.8), 0.01, 10.0)
    lr, mom = float(hp["lr"]), float(hp["momentum"])
    vel = r(mom * vel0 + r(lr * r(gains * dY)))
    scale = np.abs(mom * vel0) + lr * gains * mag
    return {"vel": vel, "Y": r(Y + vel), "gains": r(gains), "zhat": zhat,
            "scale": scale}
