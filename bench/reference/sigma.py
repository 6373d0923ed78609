"""Plain NumPy reference of the bandwidths that calibrate the HD affinities.

t-SNE sets each row's bandwidth beta_i so that the entropy of
``p_{j|i} ~ exp(-beta_i d2_ij)`` over the row's HD list equals
log(perplexity) (paper Eq. 1).  Where the perplexity exceeds the list's
length that entropy is out of reach, and beta -> 0 (uniform weights)
comes nearest: the target is then log of the list's length.
"""
from __future__ import annotations

import numpy as np

from bench.reference import step as step_ref

# bisection steps on log(beta) over [LOG_LO, LOG_HI]
ITERATIONS = 50
LOG_LO, LOG_HI = -60.0, 60.0


def entropy(d2, beta, r=np.asarray) -> np.ndarray:
    """Entropy (nats) of each row's affinities at bandwidth ``beta``."""
    return _entropy(_shifted(d2, r), beta, r)


def _shifted(d2, r):
    """Each row's distances less the row's nearest."""
    return r(d2 - d2.min(axis=1, keepdims=True))


def _entropy(z, beta, r) -> np.ndarray:
    e = r(np.exp(r(-np.asarray(beta)[:, None] * z)))
    p = r(e / r(e.sum(axis=1, keepdims=True)))
    pos = p > 0
    logp = np.log(np.where(pos, p, 1.0))
    return -np.sum(r(np.where(pos, r(p * logp), 0.0)), axis=1)


def target(d2, perplexity) -> np.ndarray:
    """Each row's entropy target: log(perplexity), or log of the number
    of finite distances where that is less."""
    count = np.sum(np.isfinite(d2), axis=1)
    return np.minimum(np.log(float(np.float32(perplexity))), np.log(count))


def solve(d2, perplexity, r=np.asarray) -> np.ndarray:
    """beta of every row by bisection on log(beta); 0 where the target is
    log of the list's length.  Rows are independent, so each block of
    rows makes its own passes."""
    return np.concatenate(step_ref.by_rows(
        lambda s, e: _solve(d2[s:e], perplexity, r), d2.shape[0]))


def _solve(d2, perplexity, r) -> np.ndarray:
    t = target(d2, perplexity)
    z = _shifted(d2, r)
    lo = np.full(d2.shape[0], LOG_LO)
    hi = np.full(d2.shape[0], LOG_HI)
    for _ in range(ITERATIONS):
        mid = r(0.5 * (lo + hi))
        flat = _entropy(z, r(np.exp(mid)), r) > t
        lo, hi = np.where(flat, mid, lo), np.where(flat, hi, mid)
    beta = r(np.exp(r(0.5 * (lo + hi))))
    return np.where(t < np.log(np.sum(np.isfinite(d2), axis=1)), beta, 0.0)
