"""FUnc-SNE behaviour: force correctness vs the exact gradient, joint KNN
convergence, dynamic datasets, interactive hyperparameters."""
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import affinities, baselines, funcsne
from repro.core.knn import exact_knn
from repro.core.quality import embedding_quality, knn_set_quality
from repro.data.synthetic import blobs


def _full_state(X, alpha=1.0, k=None, seed=0):
    n, m = X.shape
    k = k or n - 1
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=m, dim_ld=2, k_hd=k,
                                k_ld=k, n_negatives=4)
    st = funcsne.init_state(jax.random.PRNGKey(seed), X, cfg, init="random")
    hd_idx, hd_d = exact_knn(X, k)
    st = st._replace(hd_idx=hd_idx, hd_d=hd_d,
                     beta=affinities.solve_beta(hd_d, 30.0),
                     new_flag=jnp.zeros((n,), bool))
    ld_idx, ld_d = exact_knn(st.Y, k)
    return cfg, st._replace(ld_idx=ld_idx, ld_d=ld_d)


def test_forces_match_exact_gradient_direction():
    """With full neighbour sets, one FUnc-SNE force step must align with
    the exact Eq. 5 gradient (validates the three-term decomposition)."""
    X = jnp.asarray(np.random.default_rng(0).normal(size=(48, 6))
                    .astype(np.float32)) * 2.0
    cfg, st = _full_state(X)
    hp = funcsne.default_hparams(48, lr=1.0, momentum=0.0)
    st2 = funcsne._forces_update(cfg, st, hp, jax.random.PRNGKey(1),
                                 funcsne.AxisCtx())
    dY = np.asarray(st2.Y - st.Y).ravel()
    P = baselines.exact_p_matrix(X, 30.0)
    g = np.asarray(baselines.exact_tsne_grad(st.Y, P, 1.0)).ravel()
    cos = dY @ (-g) / (np.linalg.norm(dY) * np.linalg.norm(g))
    assert cos > 0.9, cos


@pytest.mark.parametrize("alpha", [0.5, 1.0, 2.0])
def test_z_estimator_unbiased(alpha):
    from repro.core.ld_kernels import pairwise_sqdists_full, w_tail
    X = jnp.asarray(np.random.default_rng(1).normal(size=(64, 6))
                    .astype(np.float32))
    cfg, st = _full_state(X)
    st = st._replace(Y=jax.random.normal(jax.random.PRNGKey(2), (64, 2)))
    ld_idx, ld_d = exact_knn(st.Y, 63)
    st = st._replace(ld_idx=ld_idx, ld_d=ld_d)
    hp = funcsne.default_hparams(64)._replace(alpha=jnp.float32(alpha))
    st2 = funcsne._forces_update(cfg, st, hp, jax.random.PRNGKey(3),
                                 funcsne.AxisCtx())
    d2 = pairwise_sqdists_full(st.Y)
    z_true = float(jnp.sum(w_tail(d2, alpha) * (1 - jnp.eye(64))))
    assert abs(float(st2.zhat) - z_true) / z_true < 0.25


def test_fit_blobs_quality_and_knn():
    X, labels = blobs(n=600, dim=16, n_centers=5, center_std=6.0, seed=0)
    hp = funcsne.default_hparams(600, perplexity=10.0)
    st, _ = funcsne.fit(X, n_iter=350, hparams=hp)
    assert float(knn_set_quality(st.hd_idx, jnp.asarray(X))) > 0.9
    assert float(embedding_quality(jnp.asarray(X), st.Y)) > 0.15
    assert bool(jnp.isfinite(st.Y).all())


def test_feedback_loop_beats_frozen_embedding():
    """Paper Fig. 4: co-optimised embedding accelerates HD KNN discovery."""
    X, _ = blobs(n=500, dim=24, n_centers=8, center_std=6.0, seed=1)
    Xj = jnp.asarray(X)
    cfg = funcsne.FuncSNEConfig(n_points=500, dim_hd=24, c_hd_rand=1,
                                c_hd_non=2)
    hp = funcsne.default_hparams(500, perplexity=10.0)

    def run(frozen):
        st = funcsne.init_state(jax.random.PRNGKey(2), Xj, cfg)
        step = funcsne.make_step(cfg)
        y0 = jnp.array(st.Y, copy=True)    # step donates the state
        for it in range(120):
            st = step(st, Xj, hp)
            if frozen:
                st = st._replace(Y=jnp.array(y0, copy=True),
                                 vel=jnp.zeros_like(st.vel))
        return float(knn_set_quality(st.hd_idx, Xj))

    q_live = run(frozen=False)
    q_frozen = run(frozen=True)
    assert q_live >= q_frozen - 0.02, (q_live, q_frozen)


def test_dynamic_add_points():
    X, _ = blobs(n=300, dim=8, n_centers=3, center_std=5.0, seed=2)
    Xj = jnp.asarray(X)
    cfg = funcsne.FuncSNEConfig(n_points=300, dim_hd=8)
    active0 = jnp.arange(300) < 200
    st = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg, active=active0)
    step = funcsne.make_step(cfg)
    hp = funcsne.default_hparams(300)
    for it in range(60):
        st = step(st, Xj, hp)
    # activate the held-out 100 points mid-run: no recompile, no stall
    st = funcsne.add_points(st, jnp.arange(200, 300), jax.random.PRNGKey(5))
    for it in range(120):
        st = step(st, Xj, hp)
    # new points must have found real HD neighbours
    assert bool(jnp.isfinite(st.Y).all())
    assert float(st.hd_d[200:][jnp.isfinite(st.hd_d[200:])].mean()) > 0
    new_deg = np.asarray(jnp.isfinite(st.hd_d[200:]).sum(1))
    assert (new_deg >= cfg.k_hd // 2).all()


def test_remove_points_stops_their_influence():
    X, _ = blobs(n=200, dim=8, seed=3)
    cfg = funcsne.FuncSNEConfig(n_points=200, dim_hd=8)
    st = funcsne.init_state(jax.random.PRNGKey(0), jnp.asarray(X), cfg)
    st = funcsne.remove_points(st, jnp.arange(100, 200))
    step = funcsne.make_step(cfg)
    hp = funcsne.default_hparams(200)
    y_before = st.Y[100:]
    for it in range(30):
        st = step(st, jnp.asarray(X), hp)
    np.testing.assert_array_equal(np.asarray(st.Y[100:]),
                                  np.asarray(y_before))


def test_interactive_hparams_no_recompile():
    """alpha/perplexity/ratios are traced: changing them reuses the same
    compiled step (the paper's instant-feedback property)."""
    X, _ = blobs(n=256, dim=8, seed=4)
    cfg = funcsne.FuncSNEConfig(n_points=256, dim_hd=8)
    st = funcsne.init_state(jax.random.PRNGKey(0), jnp.asarray(X), cfg)
    step = funcsne.make_step(cfg)
    hp = funcsne.default_hparams(256)
    st = step(st, jnp.asarray(X), hp)          # compile once
    with jax.log_compiles():
        import logging
        records = []
        handler = logging.Handler()
        handler.emit = lambda r: records.append(r)
        logging.getLogger("jax._src.dispatch").addHandler(handler)
        for alpha in (0.4, 0.7, 1.5, 3.0):
            st = step(st, jnp.asarray(X),
                      hp._replace(alpha=jnp.float32(alpha),
                                  perplexity=jnp.float32(5 + alpha)))
        logging.getLogger("jax._src.dispatch").removeHandler(handler)
    assert not any("Compiling" in str(r.getMessage()) for r in records)
    assert bool(jnp.isfinite(st.Y).all())


@pytest.mark.slow
def test_scatter_fused_step_trajectory_equivalent():
    """50 steps with the scatter-fused epilogue vs the legacy edge +
    ``.at[].add`` epilogue, same seed.  Positions cannot stay bit-equal
    (the epilogue reassociates fp32 sums, and the LD-KNN merge / gains
    sign logic amplify any ulp difference into discrete divergence), so
    this pins what must survive 50 steps: a statistically equivalent
    trajectory -- same Z estimator, same embedding scale, same quality.
    Per-step displacement parity to fp32 tolerance is asserted separately
    in test_scatter_fused.py."""
    from repro.data.synthetic import blobs as _blobs
    X, _ = _blobs(n=257, dim=13, n_centers=4, center_std=5.0, seed=0)
    Xj = jnp.asarray(X)
    cfg_s = funcsne.FuncSNEConfig(n_points=257, dim_hd=13, backend="xla",
                                  scatter_fused=True)
    cfg_l = dataclasses.replace(cfg_s, scatter_fused=False)
    st0 = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg_s)
    hp = funcsne.default_hparams(257)

    def run(cfg, st):
        step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
        for _ in range(50):
            st = step(st, Xj, hp)
        return st

    st_s = run(cfg_s, st0)
    st_l = run(cfg_l, st0)
    assert bool(jnp.isfinite(st_s.Y).all())
    np.testing.assert_allclose(float(st_s.zhat), float(st_l.zhat),
                               rtol=0.02)
    np.testing.assert_allclose(float(jnp.std(st_s.Y)),
                               float(jnp.std(st_l.Y)), rtol=0.1)
    q_s = float(embedding_quality(Xj, st_s.Y))
    q_l = float(embedding_quality(Xj, st_l.Y))
    assert abs(q_s - q_l) < 0.05, (q_s, q_l)


def test_dynamic_dataset_fused_parity():
    """add_points / remove_points under the Pallas kernels (interpret
    mode): activating and deactivating rows mid-run must follow the same
    trajectory as the pure-jnp references -- the kernels' index clipping,
    coef masking and packed activity flags, not dense gathers, carry the
    inactive-row semantics.  Neighbour lists, membership and gate state
    match exactly; floats to the kernels' fp32 summation order."""
    X, _ = blobs(n=240, dim=8, n_centers=3, center_std=5.0, seed=9)
    Xj = jnp.asarray(X)
    kw = dict(n_points=240, dim_hd=8, scatter_fused=False)
    cfg_kernel = funcsne.FuncSNEConfig(backend="interpret", **kw)
    cfg_ref = funcsne.FuncSNEConfig(backend="xla", **kw)
    active0 = jnp.arange(240) < 160

    def run(cfg):
        st = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg,
                                active=active0)
        step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
        hp = funcsne.default_hparams(240)
        for _ in range(15):
            st = step(st, Xj, hp)
        st = funcsne.add_points(st, jnp.arange(160, 240),
                                jax.random.PRNGKey(5))
        for _ in range(15):
            st = step(st, Xj, hp)
        st = funcsne.remove_points(st, jnp.arange(0, 40))
        for _ in range(15):
            st = step(st, Xj, hp)
        return st

    st_k, st_r = run(cfg_kernel), run(cfg_ref)
    for name in ("hd_idx", "ld_idx", "active", "new_flag", "gains"):
        np.testing.assert_array_equal(np.asarray(getattr(st_k, name)),
                                      np.asarray(getattr(st_r, name)),
                                      err_msg=name)
    for name in ("Y", "vel", "hd_d", "ld_d", "beta", "zhat",
                 "ema_new_frac"):
        np.testing.assert_allclose(np.asarray(getattr(st_k, name)),
                                   np.asarray(getattr(st_r, name)),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    # removed rows must have frozen in place on both paths
    assert not bool(st_k.active[:40].any())


def test_dynamic_dataset_scatter_fused_respects_membership():
    """Same add/remove sequence under the scatter-fused epilogue (fp32
    reassociation-level path, so no bit contract): inactive rows stay
    frozen, re-activated rows move, everything stays finite."""
    X, _ = blobs(n=200, dim=8, n_centers=3, center_std=5.0, seed=10)
    Xj = jnp.asarray(X)
    cfg = funcsne.FuncSNEConfig(n_points=200, dim_hd=8, backend="xla",
                                scatter_fused=True)
    st = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg,
                            active=jnp.arange(200) < 140)
    step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
    hp = funcsne.default_hparams(200)
    for _ in range(20):
        st = step(st, Xj, hp)
    frozen_before = np.asarray(st.Y[140:])
    st = funcsne.add_points(st, jnp.arange(140, 200), jax.random.PRNGKey(3))
    y_at_activation = np.asarray(st.Y[140:])
    for _ in range(30):
        st = step(st, Xj, hp)
    np.testing.assert_array_equal(frozen_before, y_at_activation)
    assert float(np.abs(np.asarray(st.Y[140:]) - y_at_activation).max()) > 0
    st = funcsne.remove_points(st, jnp.arange(0, 50))
    y_removed = np.asarray(st.Y[:50])
    for _ in range(20):
        st = step(st, Xj, hp)
    np.testing.assert_array_equal(np.asarray(st.Y[:50]), y_removed)
    assert bool(jnp.isfinite(st.Y).all())


def test_init_state_honors_perplexity():
    """The initial sigma solve must target the requested perplexity, not a
    hardcoded 30.0 (paper: perplexity is a live hyperparameter)."""
    from repro.data.synthetic import blobs as _blobs
    X, _ = _blobs(n=150, dim=12, n_centers=3, center_std=6.0, seed=7)
    Xj = jnp.asarray(X)
    # perplexity must stay well below k_hd: row entropy over k neighbours
    # is capped at log(k)
    cfg = funcsne.FuncSNEConfig(n_points=150, dim_hd=12, backend="xla")
    for perp in (5.0, 20.0):
        st = funcsne.init_state(jax.random.PRNGKey(1), Xj, cfg,
                                perplexity=perp)
        valid = jnp.isfinite(st.hd_d)
        h = affinities.entropy_of_beta(st.hd_d, st.beta, valid)
        np.testing.assert_allclose(np.asarray(h).mean(), np.log(perp),
                                   atol=0.2)


def test_fused_step_interpret_backend_matches_xla():
    """The Pallas gather kernels (interpret mode) drive a full step to the
    same embedding as the pure-jnp fallback."""
    from repro.data.synthetic import blobs as _blobs
    X, _ = _blobs(n=96, dim=10, n_centers=3, center_std=5.0, seed=1)
    Xj = jnp.asarray(X)
    kw = dict(n_points=96, dim_hd=10, k_hd=8, k_ld=6, n_negatives=5)
    cfg_i = funcsne.FuncSNEConfig(backend="interpret", **kw)
    cfg_x = funcsne.FuncSNEConfig(backend="xla", **kw)
    st_i = funcsne.init_state(jax.random.PRNGKey(3), Xj, cfg_i)
    st_x = funcsne.init_state(jax.random.PRNGKey(3), Xj, cfg_x)
    hp = funcsne.default_hparams(96)
    for _ in range(3):
        st_i = funcsne.funcsne_step(cfg_i, st_i, Xj, hp)
        st_x = funcsne.funcsne_step(cfg_x, st_x, Xj, hp)
    np.testing.assert_allclose(np.asarray(st_i.Y), np.asarray(st_x.Y),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("field", ["gather_fused", "merge_fused",
                                   "cand_fused"])
def test_removed_step_paths_are_refused(field):
    """The three locked fields accept only True: the paths their False
    value selected were removed, so asking for one is an error, not a
    silent switch to the one wiring."""
    with pytest.raises(ValueError, match="removed"):
        funcsne.FuncSNEConfig(n_points=64, dim_hd=8, **{field: False})


@pytest.mark.parametrize("name", ["atlas-262k", "mnist-70k"])
def test_bench_config_builds_the_default_config(name):
    """A benchmark configuration's ``funcsne`` fields still build a
    config, and it is the default one: the cells run the program's one
    wiring with every field at its default."""
    root = Path(__file__).resolve().parents[1]
    c = json.loads((root / "bench" / "configs" / f"{name}.json")
                   .read_text())
    shape = dict(n_points=c["n"], dim_hd=c["dim_hd"], dim_ld=c["dim_ld"])
    cfg = funcsne.FuncSNEConfig(**shape, **c["funcsne"])
    assert cfg == funcsne.FuncSNEConfig(**shape)


def test_rescale_embedding():
    X, _ = blobs(n=128, dim=8, seed=5)
    cfg = funcsne.FuncSNEConfig(n_points=128, dim_hd=8)
    st = funcsne.init_state(jax.random.PRNGKey(0), jnp.asarray(X), cfg)
    st = st._replace(Y=st.Y * 1e4)
    st2 = funcsne.rescale_embedding(st, 1e-2)
    np.testing.assert_allclose(np.asarray(st2.Y), np.asarray(st.Y) * 1e-2)
    assert float(jnp.abs(st2.vel).max()) == 0.0
