"""Scatter-fused force epilogue: parity, physics invariants, HLO shape.

Three layers pin the epilogue down:

  * parity -- from the *same* state, the scatter-fused displacement field
    must match the legacy edge-emitting + ``.at[].add`` path to fp32
    reassociation tolerance (randomized shapes, SENTINEL slots, inactive
    rows), and the Pallas scatter kernel must match the segment-sum ref;
  * physics -- with no negative sampling every directed edge acts on both
    endpoints, so the symmetrised field must conserve momentum (sum ~ 0).
    An equally-wrong reference would still pass parity; this catches
    sign/indexing bugs in the epilogue absolutely;
  * HLO -- the scatter-fused step's compiled module must not contain a
    full-size (n, K, d) per-edge force tensor (the buffers this PR
    removes), asserted via the hlo_analysis shape inventory.

Property tests run under hypothesis when installed (tests/_hypothesis_compat
skips them otherwise); seeded parametrized sweeps cover the same ground
unconditionally.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import funcsne
from repro.core.knn import SENTINEL
from repro.kernels.ne_forces.kernel import ne_forces_scatter_pallas
from repro.kernels.ne_forces.ref import (ne_forces_gather_ref,
                                         ne_forces_scatter_ref)


# --------------------------------------------------------------------------
# Randomized state construction (SENTINEL slots, inactive rows)


def _random_forces_state(n, k_hd, k_ld, n_neg, d, seed, *,
                         sentinel_frac=0.15, inactive_frac=0.2):
    rng = np.random.default_rng(seed)
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=4, dim_ld=d, k_hd=k_hd,
                                k_ld=k_ld, n_negatives=n_neg, backend="xla",
                                scatter_fused=True)
    hd_idx = rng.integers(0, n, (n, k_hd)).astype(np.int32)
    hd_d = np.sort(rng.random((n, k_hd)).astype(np.float32) * 5.0, axis=1)
    # invalid slots in all the ways _forces_update must mask: SENTINEL
    # index, inf distance, and both
    hd_idx[rng.random((n, k_hd)) < sentinel_frac] = SENTINEL
    hd_d[rng.random((n, k_hd)) < sentinel_frac] = np.inf
    ld_idx = rng.integers(0, n, (n, k_ld)).astype(np.int32)
    ld_idx[rng.random((n, k_ld)) < sentinel_frac] = SENTINEL
    active = rng.random(n) >= inactive_frac
    active[0] = True                      # keep n_act >= 1 row meaningful
    st_ = funcsne.FuncSNEState(
        Y=jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
        vel=jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)) * 0.1,
        gains=jnp.asarray(0.5 + rng.random((n, d)).astype(np.float32)),
        hd_idx=jnp.asarray(hd_idx), hd_d=jnp.asarray(hd_d),
        ld_idx=jnp.asarray(ld_idx),
        ld_d=jnp.zeros((n, k_ld), jnp.float32),
        beta=jnp.asarray(0.2 + rng.random(n).astype(np.float32) * 3.0),
        new_flag=jnp.zeros((n,), bool), active=jnp.asarray(active),
        ema_new_frac=jnp.float32(0.5), zhat=jnp.float32(1.7),
        step=jnp.int32(3), rng=jax.random.PRNGKey(seed))
    return cfg, st_


def _assert_forces_update_parity(n, k_hd, k_ld, n_neg, d, alpha, seed):
    cfg_s, st_ = _random_forces_state(n, k_hd, k_ld, n_neg, d, seed)
    cfg_l = dataclasses.replace(cfg_s, scatter_fused=False)
    hp = funcsne.default_hparams(n)._replace(alpha=jnp.float32(alpha))
    key = jax.random.PRNGKey(seed + 1)
    a = funcsne._forces_update(cfg_s, st_, hp, key, funcsne.AxisCtx())
    b = funcsne._forces_update(cfg_l, st_, hp, key, funcsne.AxisCtx())
    # scale-aware fp32 reassociation tolerance on the displacement field
    scale = float(jnp.max(jnp.abs(b.vel))) + 1e-6
    np.testing.assert_allclose(np.asarray(a.vel), np.asarray(b.vel),
                               rtol=5e-5, atol=5e-5 * scale)
    np.testing.assert_allclose(np.asarray(a.Y), np.asarray(b.Y),
                               rtol=5e-5,
                               atol=5e-5 * float(jnp.max(jnp.abs(b.Y))))
    np.testing.assert_allclose(float(a.zhat), float(b.zhat), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(a.gains), np.asarray(b.gains),
                               atol=1e-6)


def _assert_kernel_vs_ref(n, b, d, segments, scatter_back, alpha, seed,
                          block_b):
    rng = np.random.default_rng(seed)
    k = sum(s for _, s in segments)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    # out-of-range ids: the kernel must clip exactly like the ref
    nbr = jnp.asarray(rng.integers(-2, n + 3, (b, k)).astype(np.int32))
    coef = jnp.asarray(rng.random((b, k)).astype(np.float32))
    scats_p, wsums_p = ne_forces_scatter_pallas(
        x, qid, nbr, coef, alpha, segments=segments,
        scatter_back=scatter_back, block_b=block_b, interpret=True)
    scats_r, wsums_r = ne_forces_scatter_ref(
        x, qid, nbr, coef, alpha, segments=segments,
        scatter_back=scatter_back)
    for s in range(len(segments)):
        np.testing.assert_allclose(np.asarray(scats_p[s]),
                                   np.asarray(scats_r[s]),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"scat[{s}]")
        np.testing.assert_allclose(np.asarray(wsums_p[s]),
                                   np.asarray(wsums_r[s]),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"wsum[{s}]")


# --------------------------------------------------------------------------
# Property-based parity (hypothesis; skipped when it is not installed)


@settings(max_examples=12, deadline=None)
@given(n=st.integers(12, 48), k_hd=st.integers(2, 8),
       k_ld=st.integers(2, 6), n_neg=st.integers(0, 5),
       d=st.integers(2, 4), alpha=st.floats(0.4, 3.0),
       seed=st.integers(0, 10 ** 6))
def test_property_forces_update_parity(n, k_hd, k_ld, n_neg, d, alpha, seed):
    """scatter-fused _forces_update == legacy displacement field, under
    randomized shapes with SENTINEL slots and inactive rows."""
    _assert_forces_update_parity(n, k_hd, k_ld, n_neg, d, alpha, seed)


@settings(max_examples=12, deadline=None)
@given(n=st.integers(8, 60), b=st.integers(1, 50),
       s1=st.integers(1, 6), s2=st.integers(1, 5), d=st.integers(2, 5),
       back2=st.booleans(), alpha=st.floats(0.4, 3.0),
       block_b=st.sampled_from([8, 16, 32]), seed=st.integers(0, 10 ** 6))
def test_property_scatter_kernel_vs_segment_sum_ref(n, b, s1, s2, d, back2,
                                                    alpha, block_b, seed):
    """Pallas scatter kernel (interpret) == jax.ops.segment_sum reference."""
    segments = (("attraction", s1), ("repulsion", s2))
    _assert_kernel_vs_ref(n, b, d, segments, (True, back2), alpha, seed,
                          block_b)


# --------------------------------------------------------------------------
# Seeded deterministic sweeps (always run, hypothesis or not)


@pytest.mark.parametrize("n,k_hd,k_ld,n_neg,d,alpha,seed", [
    (30, 4, 3, 4, 2, 1.0, 0),
    (48, 8, 6, 0, 2, 0.5, 1),     # no negatives: pure symmetrised field
    (17, 2, 2, 2, 3, 2.5, 2),     # ragged small shapes
    (64, 6, 4, 8, 4, 1.3, 3),     # d > 2
])
def test_forces_update_parity_sweep(n, k_hd, k_ld, n_neg, d, alpha, seed):
    _assert_forces_update_parity(n, k_hd, k_ld, n_neg, d, alpha, seed)


@pytest.mark.parametrize("segments,scatter_back", [
    ((("attraction", 5),), (True,)),
    ((("repulsion", 4),), (True,)),
    ((("attraction", 4), ("repulsion", 3), ("repulsion", 2)),
     (True, True, False)),
])
@pytest.mark.parametrize("n,b,d,block_b", [(50, 37, 2, 16),   # padded B
                                           (64, 64, 4, 32),   # exact tiling
                                           (23, 11, 3, 8)])
def test_scatter_kernel_vs_ref_sweep(segments, scatter_back, n, b, d,
                                     block_b):
    _assert_kernel_vs_ref(n, b, d, segments, scatter_back, 1.3,
                          n * 10 + b, block_b)


# --------------------------------------------------------------------------
# N-chunked binning: the resident slab is (chunk_n, d), not (N, d)


@pytest.mark.parametrize("chunk_n", [8, 16, 48, 50])   # ragged + exact + N
@pytest.mark.parametrize("n,b,d,block_b", [(50, 37, 2, 16), (64, 23, 3, 32)])
def test_scatter_kernel_chunked_bins_vs_ref(chunk_n, n, b, d, block_b):
    """Any chunk_n (ragged final chunk included) must reproduce the
    single-chunk answer: the chunk guard bins every edge exactly once and
    the staged rows survive the block's chunk sweep."""
    segments = (("attraction", 4), ("repulsion", 3), ("repulsion", 2))
    rng = np.random.default_rng(n + chunk_n)
    k = 9
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    nbr = jnp.asarray(rng.integers(-2, n + 3, (b, k)).astype(np.int32))
    coef = jnp.asarray(rng.random((b, k)).astype(np.float32))
    got = ne_forces_scatter_pallas(x, qid, nbr, coef, 1.3,
                                   segments=segments,
                                   scatter_back=(True, True, False),
                                   block_b=block_b, chunk_n=chunk_n,
                                   interpret=True)
    want = ne_forces_scatter_ref(x, qid, nbr, coef, 1.3, segments=segments,
                                 scatter_back=(True, True, False))
    for s in range(len(segments)):
        np.testing.assert_allclose(np.asarray(got[0][s]),
                                   np.asarray(want[0][s]),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"scat[{s}]@chunk_n={chunk_n}")
        np.testing.assert_allclose(np.asarray(got[1][s]),
                                   np.asarray(want[1][s]),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"wsum[{s}]@chunk_n={chunk_n}")


def test_scatter_chunk_plan_lifts_large_n_vmem_cap():
    """Acceptance: n=65536 at d=2 with the step's 3 segments does not
    fall back to the XLA segment-sum ref -- the plan chunks the bins so
    the resident slabs fit the VMEM budget."""
    from repro.kernels.ne_forces import ops

    chunk_n = ops.scatter_chunk_plan(65536, 2, 3)
    assert chunk_n is not None, "fused epilogue fell back at n=65536/d=2"
    n_chunks = -(-65536 // chunk_n)
    assert n_chunks > 1, "plan claims a whole-(N,d) slab fits; it cannot"
    lane_padded = 128                       # d=2 pads to one 128-lane tile
    assert 3 * chunk_n * lane_padded * 4 <= ops._SCATTER_VMEM_BUDGET
    assert chunk_n % 8 == 0                 # sublane-tile aligned
    # small problems stay single-chunk; huge ones only take more chunks
    # (no shape is sent to the XLA ref)
    assert ops.scatter_chunk_plan(2048, 2, 3) == 2048
    big = ops.scatter_chunk_plan(10 ** 7, 2, 3)
    assert big == chunk_n and -(-10 ** 7 // big) > 64


def test_scatter_ops_dispatch_uses_chunked_kernel_past_old_cap(monkeypatch):
    """End-to-end through ops.ne_forces_gather: when the budget forces
    multiple chunks (budget shrunk so a small n crosses it), the interpret
    dispatch must still produce the ref answer via the chunked kernel
    rather than falling back to XLA."""
    from repro.kernels.ne_forces import ops

    rng = np.random.default_rng(2)
    n, b, d, k = 96, 41, 2, 7
    segments = (("attraction", 4), ("repulsion", 3))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    nbr = jnp.asarray(rng.integers(-1, n + 2, (b, k)).astype(np.int32))
    coef = jnp.asarray(rng.random((b, k)).astype(np.float32))

    monkeypatch.setattr(ops, "_SCATTER_VMEM_BUDGET", 2 * 128 * 4 * 32)
    assert ops.scatter_chunk_plan(n, d, len(segments)) == 32   # 3 chunks
    got = ops.ne_forces_gather(x, qid, nbr, coef, 1.1, segments=segments,
                               scatter_fused=True,
                               scatter_back=(True, True),
                               backend="interpret")
    want = ne_forces_scatter_ref(x, qid, nbr, coef, 1.1, segments=segments,
                                 scatter_back=(True, True))
    for s in range(len(segments)):
        np.testing.assert_allclose(np.asarray(got[0][s]),
                                   np.asarray(want[0][s]),
                                   rtol=2e-5, atol=2e-5)


def test_scatter_ref_matches_manual_edge_scatters():
    """segment-sum ref == edge-emitting ref + explicit .at[].add scatters
    (the exact construction _forces_update used before this PR)."""
    rng = np.random.default_rng(5)
    n, b, d = 40, 33, 2
    segments = (("attraction", 6), ("repulsion", 4))
    back = (True, False)
    k = 10
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    nbr = jnp.asarray(rng.integers(-1, n + 2, (b, k)).astype(np.int32))
    coef = jnp.asarray(rng.random((b, k)).astype(np.float32))
    scats, wsums = ne_forces_scatter_ref(x, qid, nbr, coef, 0.9,
                                         segments=segments,
                                         scatter_back=back)
    aggs, edges, wsums_e = ne_forces_gather_ref(x, qid, nbr, coef, 0.9,
                                                segments=segments)
    k0 = 0
    for s, (_, size) in enumerate(segments):
        want = jnp.zeros((n, d)).at[jnp.clip(qid, 0, n - 1)].add(aggs[s])
        if back[s]:
            tgt = jnp.clip(nbr[:, k0:k0 + size], 0, n - 1).reshape(-1)
            want = want.at[tgt].add(-edges[s].reshape(-1, d))
        np.testing.assert_allclose(np.asarray(scats[s]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(wsums[s]),
                                   np.asarray(wsums_e[s]), rtol=1e-6)
        k0 += size


# --------------------------------------------------------------------------
# Physics invariant: momentum conservation without negative sampling


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_symmetrised_field_conserves_momentum(backend):
    """Every scatter_back segment pairs +edge (query) with -edge
    (neighbour), so each per-segment field must sum to ~0 -- a sign or
    indexing bug in the epilogue breaks this even if kernel and ref agree.
    """
    rng = np.random.default_rng(7)
    n, b, d = 45, 45, 2
    segments = (("attraction", 5), ("repulsion", 4))
    k = 9
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = jnp.arange(b, dtype=jnp.int32)
    nbr = jnp.asarray(rng.integers(0, n, (b, k)).astype(np.int32))
    coef = jnp.asarray(rng.random((b, k)).astype(np.float32))
    from repro.kernels.ne_forces.ops import ne_forces_gather
    scats, _ = ne_forces_gather(x, qid, nbr, coef, 1.0, segments=segments,
                                scatter_fused=True,
                                scatter_back=(True, True), backend=backend)
    for s, scat in enumerate(scats):
        total = np.asarray(jnp.sum(scat, axis=0))
        np.testing.assert_allclose(total, 0.0, atol=1e-4,
                                   err_msg=f"segment {s}")


@pytest.mark.parametrize("scatter_fused", [True, False])
def test_forces_update_conserves_momentum_without_negatives(scatter_fused):
    """n_negatives=0 + all rows active: the full symmetrised displacement
    field must sum to ~0 (momentum conservation)."""
    n, d = 52, 2
    cfg, st_ = _random_forces_state(n, 6, 4, 0, d, seed=11,
                                    sentinel_frac=0.1, inactive_frac=0.0)
    cfg = dataclasses.replace(cfg, scatter_fused=scatter_fused)
    # zero velocity + unit gains so Y2 - Y == lr * dY exactly
    st_ = st_._replace(vel=jnp.zeros((n, d), jnp.float32),
                       gains=jnp.ones((n, d), jnp.float32))
    hp = funcsne.default_hparams(n)
    out = funcsne._forces_update(cfg, st_, hp, jax.random.PRNGKey(0),
                                 funcsne.AxisCtx())
    dY = np.asarray(out.Y - st_.Y)
    # conservation to fp32 accumulation tolerance, relative to the total
    # unsigned momentum actually exchanged
    budget = np.abs(dY).sum() + 1e-6
    assert np.abs(dY.sum(axis=0)).max() < 1e-5 * budget, (
        dY.sum(axis=0), budget)


def test_negative_sampling_breaks_momentum_conservation():
    """Sanity check on the invariant's power: with negatives (whose edges
    are deliberately not symmetrised) the field does NOT sum to zero."""
    n, d = 52, 2
    cfg, st_ = _random_forces_state(n, 6, 4, 16, d, seed=11,
                                    sentinel_frac=0.1, inactive_frac=0.0)
    st_ = st_._replace(vel=jnp.zeros((n, d), jnp.float32),
                       gains=jnp.ones((n, d), jnp.float32))
    hp = funcsne.default_hparams(n)
    out = funcsne._forces_update(cfg, st_, hp, jax.random.PRNGKey(0),
                                 funcsne.AxisCtx())
    dY = np.asarray(out.Y - st_.Y)
    budget = np.abs(dY).sum() + 1e-6
    assert np.abs(dY.sum(axis=0)).max() > 1e-4 * budget


# --------------------------------------------------------------------------
# HLO: the (n, K, d) per-edge force tensors are gone


def _edge_shapes_in_step_hlo(cfg, n):
    X = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(n, cfg.dim_hd)).astype(np.float32))
    st_ = funcsne.init_state(jax.random.PRNGKey(0), X, cfg)
    hp = funcsne.default_hparams(n)
    step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
    text = step.lower(st_, X, hp).compile().as_text()
    from repro.launch.hlo_analysis import module_array_shapes
    shapes = module_array_shapes(text)
    edge_tails = {(cfg.k_hd, cfg.dim_ld), (cfg.k_ld, cfg.dim_ld)}
    return [dims for dtype, dims in shapes
            if dtype == "f32" and len(dims) == 3
            and dims[1:] in edge_tails and dims[0] >= n]


def test_scatter_fused_step_hlo_has_no_edge_tensor():
    """Acceptance: no full-size (n, K, d) per-edge force buffer may appear
    anywhere in the scatter-fused step's compiled module (interpret
    backend = the Pallas kernel data path, lowered on CPU).  The legacy
    edge-emitting path is the positive control for the detector."""
    n = 257
    kw = dict(n_points=n, dim_hd=7, backend="interpret")
    fused = _edge_shapes_in_step_hlo(
        funcsne.FuncSNEConfig(scatter_fused=True, **kw), n)
    assert fused == [], f"per-edge tensors back in the hot path: {fused}"
    legacy = _edge_shapes_in_step_hlo(
        funcsne.FuncSNEConfig(scatter_fused=False, **kw), n)
    assert legacy, "detector is blind: legacy path shows no edge tensor"
