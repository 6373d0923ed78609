"""Merge-fused neighbour refinement: parity, invariants, HLO shape.

Contract layers:

  * kernel vs oracle -- the Pallas kernel (interpret mode) and the
    stable-rank XLA implementation must reproduce
    ``knn_lib.dedup_candidates`` + ``knn_lib.merge_knn`` EXACTLY on
    discrete outputs (indices, improved flags), not just to tolerance:
    test coordinates are quantised to quarter-integers so every squared
    distance is exactly representable and accumulation order cannot flip
    a merge decision.  Sweeps cover SENTINEL slots (current list and
    candidates), inactive rows, duplicate candidates, out-of-range ids,
    distance ties, ragged blocks and multi-M-chunk grids, in both modes
    (HD: stored distances ride in; LD ``rescore``: current rows re-scored
    in-kernel).
  * property suite -- hypothesis (when installed) walks randomized
    shapes/seeds over the same discrete-parity assertion plus the list
    invariants (sorted ascending, self-free, duplicate-free among finite,
    monotone improvement).
  * step level -- flipping ``cfg.merge_fused`` on the XLA backend is
    bit-neutral over 50 steps (the ref IS the legacy pipeline), and the
    interpret backend drives a full step through the kernel.
  * HLO -- the merge-fused step's compiled module contains NO top-k /
    sort (the ``merge_knn`` selection this PR removes) and NO full-size
    (n, C, K) / (n, C, C) dedup broadcast operand; the legacy flag is the
    positive control for both detectors.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import example, given, settings, st

from repro.core import funcsne
from repro.core import knn as knn_lib
from repro.core.knn import SENTINEL
from repro.kernels.knn_merge.kernel import knn_merge_pallas
from repro.kernels.knn_merge.ops import knn_merge
from repro.kernels.knn_merge.ref import knn_merge_ref, knn_merge_rank_ref


# --------------------------------------------------------------------------
# Quantised problem construction (exact distances -> discrete parity)


def _problem(n, m, b, k, c, seed, *, sentinel_frac=0.2, inactive_frac=0.15):
    rng = np.random.default_rng(seed)
    x = jnp.asarray((rng.integers(-8, 9, (n, m)) / 4.0).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    # K distinct ids per row: the merge's precondition on its current list
    cur_idx = np.argsort(rng.random((b, n)), axis=1)[:, :k].astype(np.int32)
    # invalid tail slots, as merge_knn leaves them (sorted -> inf at end)
    sent = np.sort(rng.random((b, k)) < sentinel_frac, axis=1)
    cur_idx[sent] = SENTINEL
    # out-of-range + SENTINEL + duplicate candidates
    cand = rng.integers(-2, n + 3, (b, c)).astype(np.int32)
    cand[rng.random((b, c)) < 0.1] = SENTINEL
    cand_active = jnp.asarray(rng.random((b, c)) >= inactive_frac)
    # HD-mode stored distances: the real (exact) distances, sorted, with
    # the invariant inf pattern
    d0 = np.array(jnp.sum(
        (x[jnp.clip(jnp.asarray(cur_idx), 0, n - 1)]
         - x[qid][:, None, :]) ** 2, axis=-1))
    d0[sent] = np.inf
    order = np.argsort(d0, axis=1, kind="stable")
    cur_idx_s = jnp.asarray(np.take_along_axis(cur_idx, order, axis=1))
    cur_d = jnp.asarray(np.take_along_axis(d0, order, axis=1))
    cur_valid = jnp.asarray((np.asarray(cur_idx_s) != SENTINEL)
                            & (rng.random((b, k)) < 0.9))
    return x, qid, cur_idx_s, cur_d, jnp.asarray(cand), cand_active, \
        cur_valid


def _assert_all_equal(got, want, what):
    for g, w, name in zip(got, want, ("idx", "d", "improved")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{what}:{name}")


def _assert_discrete_parity(n, m, b, k, c, seed, rescore, **pallas_kw):
    x, qid, cur_idx, cur_d, cand, cand_active, cur_valid = _problem(
        n, m, b, k, c, seed)
    if rescore:
        args = (x, qid, cur_idx, None, cand)
        kw = dict(cand_active=cand_active, cur_valid=cur_valid)
        cur_w = cur_valid
    else:
        args = (x, qid, cur_idx, cur_d, cand)
        kw = dict(cand_active=cand_active)
        cur_w = cur_d
    want = knn_merge_ref(*args, **kw)
    _assert_all_equal(knn_merge_rank_ref(*args, **kw), want, "rank_ref")
    got = knn_merge_pallas(x, qid, cur_idx, cur_w, cand, cand_active,
                           rescore=rescore, interpret=True, **pallas_kw)
    _assert_all_equal(got, want, "kernel")
    return want


# --------------------------------------------------------------------------
# Seeded deterministic sweeps (always run, hypothesis or not)


@pytest.mark.parametrize("n,m,b,k,c,bb,bm", [
    (50, 19, 37, 6, 5, 16, 8),     # everything ragged; 3 ragged M chunks
    (64, 128, 64, 8, 7, 32, 128),  # exact tiling, unpadded B
    (40, 300, 33, 4, 3, 8, 128),   # padded B + clamped+masked final M chunk
    (30, 2, 30, 8, 8, 16, 512),    # tiny M (the LD-space case)
])
@pytest.mark.parametrize("rescore", [False, True])
def test_knn_merge_kernel_vs_oracle_sweep(n, m, b, k, c, bb, bm, rescore):
    """Kernel (interpret) and rank ref == dedup_candidates+merge_knn,
    discrete-exact, across ragged/multi-chunk tilings and both modes."""
    _assert_discrete_parity(n, m, b, k, c, seed=n * 10 + m + c,
                            rescore=rescore, block_b=bb, block_m=bm)


@pytest.mark.parametrize("sub_b,persistent_q", [
    (8, False), (8, True), (16, None), (None, True),
])
def test_knn_merge_pipeline_variants(sub_b, persistent_q):
    """Double-buffered sub-blocks and the persistent-q slab are pure
    scheduling for the merge kernel too: every point must stay
    discrete-exact vs the oracle on a multi-M-chunk grid."""
    _assert_discrete_parity(45, 300, 37, 5, 4, seed=17, rescore=False,
                            block_b=16, block_m=64, sub_b=sub_b,
                            persistent_q=persistent_q)


def test_knn_merge_tie_breaking_matches_topk():
    """All-equal coordinates force maximal distance ties: the stable-rank
    merge must resolve them exactly like lax.top_k (current list first,
    then earlier candidates)."""
    n, b, k, c = 12, 9, 4, 6
    x = jnp.zeros((n, 3), jnp.float32)          # every distance == 0.0
    rng = np.random.default_rng(3)
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    cur_idx = jnp.asarray(rng.integers(0, n, (b, k)).astype(np.int32))
    cur_d = jnp.zeros((b, k), jnp.float32)
    cand = jnp.asarray(rng.integers(0, n, (b, c)).astype(np.int32))
    active = jnp.ones((b, c), bool)
    want = knn_merge_ref(x, qid, cur_idx, cur_d, cand, cand_active=active)
    got = knn_merge_pallas(x, qid, cur_idx, cur_d, cand, active,
                           rescore=False, interpret=True)
    _assert_all_equal(got, want, "ties")


def test_knn_merge_ops_dispatch():
    """ops.knn_merge: 'xla' is the oracle; 'interpret' runs the kernel;
    both modes agree with the direct ref call."""
    x, qid, cur_idx, cur_d, cand, cand_active, cur_valid = _problem(
        40, 7, 23, 5, 4, seed=5)
    want = knn_merge_ref(x, qid, cur_idx, cur_d, cand,
                         cand_active=cand_active)
    for backend in ("xla", "interpret"):
        got = knn_merge(x, qid, cur_idx, cur_d, cand,
                        cand_active=cand_active, backend=backend)
        _assert_all_equal(got, want, backend)
    with pytest.raises(ValueError):
        knn_merge(x, qid, cur_idx, cur_d, cand, backend="nope")


# --------------------------------------------------------------------------
# Property-based parity + invariants (hypothesis; skipped if missing)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(12, 60), m=st.integers(1, 40), b=st.integers(1, 48),
       k=st.integers(2, 10), c=st.integers(1, 10), rescore=st.booleans(),
       seed=st.integers(0, 10 ** 6))
@example(n=12, m=1, b=5, k=5, c=1, rescore=False, seed=0)
@example(n=12, m=1, b=1, k=5, c=1, rescore=False, seed=0)
def test_property_merge_fused_discrete_parity(n, m, b, k, c, rescore, seed):
    """Randomized shapes/seeds: kernel == rank ref == oracle exactly
    (dedup semantics incl. SENTINEL + inactive rows, improved flag), and
    the merged lists keep the merge_knn invariants."""
    new_idx, new_d, _ = _assert_discrete_parity(n, m, b, k, c, seed,
                                                rescore)
    new_idx, new_d = np.asarray(new_idx), np.asarray(new_d)
    assert (new_d[:, 1:] >= new_d[:, :-1]).all()         # sorted ascending
    for i in range(b):                                   # no finite dupes
        fin = new_idx[i][np.isfinite(new_d[i])]
        assert len(set(fin.tolist())) == len(fin)


# --------------------------------------------------------------------------
# Step level: flag parity and interpret-backend execution


@pytest.mark.slow
def test_merge_fused_step_bit_equivalent_on_xla():
    """cfg.merge_fused is bit-neutral on the XLA backend: the ref IS the
    legacy dedup/top_k pipeline, so 50 steps from the same seed must
    produce identical state (the gather_fused precedent)."""
    from repro.data.synthetic import blobs
    X, _ = blobs(n=257, dim=13, n_centers=4, center_std=5.0, seed=0)
    Xj = jnp.asarray(X)
    cfg_m = funcsne.FuncSNEConfig(n_points=257, dim_hd=13, backend="xla",
                                  merge_fused=True)
    cfg_l = dataclasses.replace(cfg_m, merge_fused=False)
    st0 = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg_m)
    hp = funcsne.default_hparams(257)

    def run(cfg, st):
        step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
        for _ in range(50):
            st = step(st, Xj, hp)
        return st

    st_m = run(cfg_m, st0)
    st_l = run(cfg_l, st0)
    for name in funcsne.FuncSNEState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(st_m, name)),
                                      np.asarray(getattr(st_l, name)),
                                      err_msg=name)


@pytest.mark.slow
def test_merge_fused_step_interpret_trajectory():
    """A few steps with the merge kernel (interpret) vs the XLA selection
    epilogue, same interpret distance kernels: fp32-tolerance parity of
    the embedding (the kernels reassociate distance sums, so bit equality
    is not the contract here)."""
    from repro.data.synthetic import blobs
    X, _ = blobs(n=96, dim=10, n_centers=3, center_std=5.0, seed=1)
    Xj = jnp.asarray(X)
    kw = dict(n_points=96, dim_hd=10, k_hd=8, k_ld=6, n_negatives=5,
              backend="interpret")
    cfg_m = funcsne.FuncSNEConfig(merge_fused=True, **kw)
    cfg_l = funcsne.FuncSNEConfig(merge_fused=False, **kw)
    st_m = funcsne.init_state(jax.random.PRNGKey(3), Xj, cfg_m)
    st_l = funcsne.init_state(jax.random.PRNGKey(3), Xj, cfg_l)
    hp = funcsne.default_hparams(96)
    for _ in range(3):
        st_m = funcsne.funcsne_step(cfg_m, st_m, Xj, hp)
        st_l = funcsne.funcsne_step(cfg_l, st_l, Xj, hp)
    np.testing.assert_allclose(np.asarray(st_m.Y), np.asarray(st_l.Y),
                               rtol=1e-4, atol=1e-5)


def test_nnd_merge_fused_bit_equivalent():
    """nnd.py's port onto knn_merge is bit-neutral on the XLA backend."""
    from repro.core.nnd import NNDConfig, nnd_init, nnd_step
    from repro.data.synthetic import blobs
    X, _ = blobs(n=150, dim=12, n_centers=4, seed=9)
    Xj = jnp.asarray(X)
    cfg_m = NNDConfig(k=8, c_fwd=4, c_rev=2, backend="xla",
                      merge_fused=True)
    cfg_l = dataclasses.replace(cfg_m, merge_fused=False)
    rng = jax.random.PRNGKey(0)

    def run(cfg):
        idx, d = nnd_init(rng, Xj, cfg)
        fracs = []
        for it in range(5):
            idx, d, frac = nnd_step(jax.random.fold_in(rng, it), Xj, idx,
                                    d, cfg)
            fracs.append(float(frac))
        return np.asarray(idx), np.asarray(d), fracs

    idx_m, d_m, f_m = run(cfg_m)
    idx_l, d_l, f_l = run(cfg_l)
    np.testing.assert_array_equal(idx_m, idx_l)
    np.testing.assert_array_equal(d_m, d_l)
    assert f_m == f_l


# --------------------------------------------------------------------------
# HLO: the selection epilogue is structurally gone


def _step_hlo_text(cfg, n):
    X = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(n, cfg.dim_hd)).astype(np.float32))
    st_ = funcsne.init_state(jax.random.PRNGKey(0), X, cfg)
    hp = funcsne.default_hparams(n)
    step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
    return step.lower(st_, X, hp).compile().as_text()


def _topk_or_sort_lines(text):
    return [l for l in text.splitlines()
            if "TopK" in l or " sort(" in l or "= sort" in l]


def _dedup_broadcast_shapes(text, cfg, n):
    from repro.launch.hlo_analysis import module_array_shapes
    tails = {(cfg.c_hd, cfg.k_hd), (cfg.c_ld, cfg.k_ld),
             (cfg.c_hd, cfg.c_hd), (cfg.c_ld, cfg.c_ld)}
    return [dims for dtype, dims in module_array_shapes(text)
            if dtype == "pred" and len(dims) == 3
            and dims[1:] in tails and dims[0] >= n]


def test_merge_fused_step_hlo_has_no_topk_and_no_dedup_broadcast():
    """Acceptance: with cfg.merge_fused=True (interpret backend = the
    Pallas data path lowered on CPU) the compiled step contains no top-k
    / sort anywhere and no full-size (n, C, K) or (n, C, C) dedup
    broadcast tensor.  The legacy flag is the positive control for both
    detectors."""
    n = 257
    kw = dict(n_points=n, dim_hd=7, backend="interpret")
    cfg_m = funcsne.FuncSNEConfig(merge_fused=True, **kw)
    text_m = _step_hlo_text(cfg_m, n)
    assert _topk_or_sort_lines(text_m) == [], \
        "top_k/sort back in the merge-fused step"
    assert _dedup_broadcast_shapes(text_m, cfg_m, n) == [], \
        "full-size dedup broadcast back in the merge-fused step"

    cfg_l = funcsne.FuncSNEConfig(merge_fused=False, **kw)
    text_l = _step_hlo_text(cfg_l, n)
    assert _topk_or_sort_lines(text_l), \
        "detector is blind: legacy path shows no top_k/sort"
    assert _dedup_broadcast_shapes(text_l, cfg_l, n), \
        "detector is blind: legacy path shows no dedup broadcast"


def test_merge_fused_chunked_step_hlo_clean():
    """The scan-chunked driver compounds the win (the epilogue would run
    T times per dispatch): the whole chunk module must be top_k/sort-free
    too."""
    n = 96
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=5, backend="interpret")
    X = jnp.asarray(np.random.default_rng(1)
                    .normal(size=(n, 5)).astype(np.float32))
    st_ = funcsne.init_state(jax.random.PRNGKey(0), X, cfg)
    hp = funcsne.default_hparams(n)
    chunk = funcsne.make_chunked_step(cfg, 4)
    text = chunk.lower(st_, X, hp).compile().as_text()
    assert _topk_or_sort_lines(text) == []
