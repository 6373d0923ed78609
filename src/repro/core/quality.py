"""Embedding / KNN quality criteria: R_NX(K) and its AUC (paper's metric).

R_NX(K) (Lee et al. 2015) rescales the K-ary neighbourhood agreement
Q_NX(K) = (1/NK) sum_i |est_i[:K] & true_i[:K]| so that 0 = random, 1 =
perfect:  R_NX(K) = ((N-1) Q_NX(K) - K) / (N - 1 - K).

The AUC uses 1/K weights (multi-scale overview, emphasising local scales):
AUC = sum_K R_NX(K)/K / sum_K 1/K.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.knn import exact_knn, exact_knn_rows

# query rows the sampled criteria score: enough for a stable AUC, and the
# blocked reference stays O(rows * block) memory at any n
N_SAMPLE = 2048


def _rank_in_true(est_idx, true_idx):
    """Position of each estimated neighbour inside the true order (or inf)."""
    match = est_idx[:, :, None] == true_idx[:, None, :]   # (N, Ke, Kt)
    pos = jnp.argmax(match, axis=-1)
    found = jnp.any(match, axis=-1)
    return jnp.where(found, pos, jnp.iinfo(jnp.int32).max)


def qnx_curve(est_idx, true_idx):
    """Q_NX(K) for K = 1..Kmax, Kmax = min(est K, true K).

    est_idx rows must be sorted by estimated distance; true_idx by true
    distance.  Overlap(K) counts pairs present in both prefixes; an est
    entry at position a with true-rank r joins every K > max(a, r).
    """
    kmax = min(est_idx.shape[1], true_idx.shape[1])
    est_idx = est_idx[:, :kmax]
    true_idx = true_idx[:, :kmax]
    n = est_idx.shape[0]
    rank = _rank_in_true(est_idx, true_idx)               # (N, K)
    a = jnp.arange(kmax)[None, :]
    m = jnp.maximum(a, rank)                              # joins at K = m+1
    m = jnp.where(m < kmax, m, kmax)                      # kmax bin = never
    hist = jnp.zeros((kmax + 1,)).at[m.reshape(-1)].add(1.0)
    overlap = jnp.cumsum(hist)[:kmax]                     # overlap(K=1..kmax)
    ks = jnp.arange(1, kmax + 1)
    return overlap / (n * ks)


def rnx_curve(est_idx, true_idx, n_total=None):
    if n_total is None:
        n_total = est_idx.shape[0]
    q = qnx_curve(est_idx, true_idx)
    ks = jnp.arange(1, q.shape[0] + 1)
    return ((n_total - 1) * q - ks) / jnp.maximum(n_total - 1 - ks, 1)


def rnx_auc(rnx):
    """1/K-weighted AUC of an R_NX curve."""
    ks = jnp.arange(1, rnx.shape[0] + 1, dtype=jnp.float32)
    w = 1.0 / ks
    return jnp.sum(rnx * w) / jnp.sum(w)


def knn_set_quality(est_idx, X, kmax: int = None):
    """AUC of R_NX comparing estimated HD KNN sets to the exact sets."""
    k = est_idx.shape[1] if kmax is None else kmax
    true_idx, _ = exact_knn(X, k)
    return rnx_auc(rnx_curve(est_idx[:, :k], true_idx, X.shape[0]))


def sample_rows(n: int):
    """Fixed sorted sample of N_SAMPLE query rows (all rows when fewer)."""
    if n <= N_SAMPLE:
        return jnp.arange(n, dtype=jnp.int32)
    rows = np.random.default_rng(0).choice(n, N_SAMPLE, replace=False)
    return jnp.asarray(np.sort(rows), jnp.int32)


def embedding_rnx_curve(X, Y, kmax: int = 64):
    """R_NX(K) of the LD neighbourhoods against the HD ones, over a fixed
    sample of query rows; exact KNN of both is computed in blocks, so no
    n x n matrix is built at any n."""
    n = X.shape[0]
    kmax = min(kmax, n - 2)
    rows = sample_rows(n)
    true_idx, _ = exact_knn_rows(X, rows, kmax)
    emb_idx, _ = exact_knn_rows(Y, rows, kmax)
    return rnx_curve(emb_idx, true_idx, n)


def embedding_quality(X, Y, kmax: int = 64):
    """AUC of R_NX comparing LD neighbourhoods to HD neighbourhoods (over
    the fixed sample of query rows of :func:`embedding_rnx_curve`)."""
    return rnx_auc(embedding_rnx_curve(X, Y, kmax))


def knn_recall(est_idx, X):
    """Mean recall@K of the estimated (n, K) HD lists against exact KNN,
    over the same fixed sample of query rows."""
    rows = sample_rows(X.shape[0])
    k = est_idx.shape[1]
    true_idx, _ = exact_knn_rows(X, rows, k)
    est = est_idx[rows]
    hit = jnp.any(est[:, :, None] == true_idx[:, None, :], axis=-1)
    return jnp.mean(hit.astype(jnp.float32))


def one_nn_accuracy(Z, labels, rng, n_trials: int = 1, one_shot: bool = False):
    """1-NN classification accuracy in representation Z (paper Table 2).

    one_shot: reveal one random labelled example per class per trial and
    classify the rest; otherwise leave-one-out 1-NN.
    """
    Z = jnp.asarray(Z, jnp.float32)
    labels = jnp.asarray(labels)
    n = Z.shape[0]
    if not one_shot:
        idx, _ = exact_knn(Z, 1)
        return jnp.mean(labels[idx[:, 0]] == labels)

    classes = jnp.unique(labels)
    accs = []
    for t in range(n_trials):
        r = jax.random.fold_in(rng, t)
        # pick one prototype per class
        protos = []
        for ci in range(classes.shape[0]):
            members = jnp.nonzero(labels == classes[ci], size=n,
                                  fill_value=0)[0]
            count = jnp.sum(labels == classes[ci])
            pick = jax.random.randint(jax.random.fold_in(r, ci), (), 0,
                                      jnp.maximum(count, 1))
            protos.append(members[pick])
        protos = jnp.stack(protos)
        d2 = jnp.sum((Z[:, None, :] - Z[protos][None, :, :]) ** 2, axis=-1)
        pred = classes[jnp.argmin(d2, axis=1)]
        mask = ~jnp.isin(jnp.arange(n), protos)
        accs.append(jnp.sum((pred == labels) & mask) / jnp.sum(mask))
    return jnp.mean(jnp.stack(accs))
