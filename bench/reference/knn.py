"""Exact neighbours of a fixed sample of rows, and the recall against them.

A frozen copy of the program's blocked ``exact_knn_rows`` and sampled
``knn_recall`` (``core/knn.py``, ``core/quality.py``): columns stream in
blocks with a running top-k, so no n x n matrix is built, and products
run at HIGHEST precision (a TPU rounds f32 matmul operands to bf16 by
default, which would reorder near neighbours).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sample_rows(n: int, count: int) -> np.ndarray:
    """A fixed, sorted sample of ``count`` rows (all rows when fewer)."""
    if n <= count:
        return np.arange(n, dtype=np.int32)
    rows = np.random.default_rng(0).choice(n, count, replace=False)
    return np.sort(rows).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def exact_knn_rows(X, rows, k: int, block: int = 4096):
    """((S, k) ids, (S, k) squared distances) of ``X[rows]`` over ``X``,
    self excluded, ties to the lower id."""
    X = X.astype(jnp.float32)
    n = X.shape[0]
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
        kept = jnp.take_along_axis(best_i, jnp.minimum(pos, k - 1), axis=1)
        idx = jnp.where(pos < k, kept, j * block + pos - k)
        return (-neg_top, idx), None

    s = rows.shape[0]
    init = (jnp.full((s, k), jnp.inf, jnp.float32),
            jnp.full((s, k), -1, jnp.int32))
    (d, idx), _ = jax.lax.scan(body, init,
                               jnp.arange(n_blocks, dtype=jnp.int32))
    return idx, d


@jax.jit
def recall(hd_idx, rows, true_idx):
    """Mean recall@K of the program's lists ``hd_idx[rows]`` against the
    exact lists of the same rows."""
    est = hd_idx[rows]
    hit = jnp.any(est[:, :, None] == true_idx[:, None, :], axis=-1)
    return jnp.mean(hit.astype(jnp.float32))
