"""Perplexity calibration + iterative-KNN machinery unit/property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import affinities, knn as knn_lib
from repro.core.knn import SENTINEL
from repro.core.nnd import NNDConfig, nnd
from repro.data.synthetic import blobs, disjoint_blobs


def test_solve_beta_hits_target_entropy():
    rng = np.random.default_rng(0)
    d2 = jnp.asarray(np.sort(rng.random((64, 40)).astype(np.float32) * 10))
    for perp in (5.0, 15.0, 30.0):
        beta = affinities.solve_beta(d2, perp)
        h = affinities.entropy_of_beta(d2, beta, jnp.isfinite(d2))
        np.testing.assert_allclose(np.asarray(h), np.log(perp), atol=2e-3)


def test_solve_beta_warm_start_consistent():
    rng = np.random.default_rng(1)
    d2 = jnp.asarray(rng.random((32, 24)).astype(np.float32))
    cold = affinities.solve_beta(d2, 10.0)
    warm = affinities.solve_beta(d2, 10.0, beta0=cold, n_iter=8)
    np.testing.assert_allclose(np.asarray(warm), np.asarray(cold), rtol=0.05)


def test_entropy_monotone_in_beta():
    rng = np.random.default_rng(2)
    d2 = jnp.asarray(rng.random((8, 16)).astype(np.float32))
    valid = jnp.isfinite(d2)
    hs = [float(affinities.entropy_of_beta(d2, jnp.full((8,), b),
                                           valid).mean())
          for b in (0.1, 1.0, 10.0, 100.0)]
    assert hs == sorted(hs, reverse=True)


def test_p_rows_normalised_and_masked():
    d2 = jnp.asarray([[0.1, 0.2, jnp.inf, 0.3]])
    p = affinities.p_rows(d2, jnp.ones((1,)))
    assert float(p[0, 2]) == 0.0
    np.testing.assert_allclose(float(p.sum()), 1.0, rtol=1e-6)


@settings(max_examples=30, deadline=None)
@given(n=st.integers(12, 64), k=st.integers(2, 8), c=st.integers(1, 10),
       seed=st.integers(0, 10_000))
def test_merge_knn_invariants(n, k, c, seed):
    """Merged lists are sorted, self-free, duplicate-free, and no worse
    than before (distances can only shrink)."""
    rng = np.random.default_rng(seed)
    rows = np.arange(n, dtype=np.int32)
    # distinct-per-row current lists (the init_knn_idx invariant)
    cur_idx = np.stack([rng.permutation(np.delete(np.arange(n), i))[:k]
                        for i in range(n)]).astype(np.int32)
    cur_d = np.sort(rng.random((n, k)).astype(np.float32), axis=1)
    cand = rng.integers(0, n, (n, c)).astype(np.int32)
    cand_d = rng.random((n, c)).astype(np.float32)
    valid = knn_lib.dedup_candidates(jnp.asarray(rows), jnp.asarray(cur_idx),
                                     jnp.asarray(cand))
    new_idx, new_d, improved = knn_lib.merge_knn(
        jnp.asarray(cur_idx), jnp.asarray(cur_d), jnp.asarray(cand),
        jnp.asarray(cand_d), valid)
    new_idx, new_d = np.asarray(new_idx), np.asarray(new_d)
    assert (np.diff(new_d, axis=1) >= 0).all()          # sorted
    assert (new_d <= cur_d + 1e-7).all()                # monotone improvement
    assert not (new_idx == rows[:, None]).any()         # no self
    for i in range(n):                                  # no dupes among finite
        fin = new_idx[i][np.isfinite(new_d[i])]
        assert len(set(fin.tolist())) == len(fin)


def test_dedup_rejects_existing_and_self():
    rows = jnp.arange(4, dtype=jnp.int32)
    cur = jnp.asarray([[1, 2], [0, 2], [0, 1], [0, 1]], jnp.int32)
    cand = jnp.asarray([[0, 1, 3], [1, 3, 3], [2, 3, 0], [3, 2, 2]],
                       jnp.int32)
    valid = np.asarray(knn_lib.dedup_candidates(rows, cur, cand))
    assert not valid[0, 0]      # self
    assert not valid[0, 1]      # already a neighbour
    assert valid[0, 2]
    assert valid[1, 1] and not valid[1, 2]   # duplicate within candidates
    assert not valid[3, 0]      # self


def test_reverse_neighbors_contains_true_reverse_edges():
    idx = jnp.asarray([[1, 2], [2, 3], [3, 0], [0, 1]], jnp.int32)
    fill = knn_lib.counter_fill(jnp.int32(0), 4, 3)
    rev = np.asarray(knn_lib.reverse_neighbors(idx, 4, 3, fill=fill))
    # point 0 is listed by 2 and 3
    assert {2, 3} <= set(rev[0].tolist()) | {2, 3}
    for tgt in range(4):
        srcs = {s for s in range(4) if tgt in np.asarray(idx[s])}
        assert srcs & set(rev[tgt].tolist())


def test_exact_knn_correct():
    X, _ = blobs(n=100, dim=4, seed=3)
    idx, d = knn_lib.exact_knn(jnp.asarray(X), 5)
    d_full = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(d_full, np.inf)
    want = np.argsort(d_full, axis=1)[:, :5]
    got_sets = [set(r.tolist()) for r in np.asarray(idx)]
    want_sets = [set(r.tolist()) for r in want]
    same = sum(g == w for g, w in zip(got_sets, want_sets))
    assert same >= 97   # ties may permute a couple of sets


def test_nnd_converges_on_overlapping_blobs():
    X, _ = blobs(n=400, dim=16, n_centers=5, center_std=1.0, blob_std=1.0,
                 seed=0)
    idx, d, hist = nnd(X, NNDConfig(k=10, backend="xla"), max_iter=50)
    from repro.core.quality import knn_set_quality
    q = float(knn_set_quality(idx, jnp.asarray(X)))
    assert q > 0.95, q


def test_nnd_struggles_on_disjoint_blobs():
    """Paper Fig. 7: the greedy local join stalls on isolated clusters."""
    X, _ = disjoint_blobs(n=600, dim=16, n_centers=100, seed=0)
    idx, d, hist = nnd(X, NNDConfig(k=5, c_rev=0, backend="xla"),
                       max_iter=12)
    from repro.core.quality import knn_set_quality
    q = float(knn_set_quality(idx, jnp.asarray(X)))
    assert q < 0.9      # it should NOT fully solve this one quickly
