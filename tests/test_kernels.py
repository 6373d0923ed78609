"""Per-kernel interpret-mode validation vs the pure-jnp oracles:
shape/dtype sweeps + hypothesis property checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ne_forces.kernel import (ne_forces_gather_pallas,
                                            ne_forces_pallas)
from repro.kernels.ne_forces.ref import ne_forces_gather_ref, ne_forces_ref
from repro.kernels.pairwise_sqdist.kernel import (
    pairwise_sqdist_gather_pallas, pairwise_sqdist_pallas)
from repro.kernels.pairwise_sqdist.ref import (pairwise_sqdist_gather_ref,
                                               pairwise_sqdist_ref)


@pytest.mark.parametrize("b,c,m", [(8, 4, 16), (37, 11, 19), (64, 16, 128),
                                   (130, 3, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_sqdist_sweep(b, c, m, dtype):
    rng = np.random.default_rng(b * 100 + c)
    q = jnp.asarray(rng.normal(size=(b, m)), dtype)
    cands = jnp.asarray(rng.normal(size=(b, c, m)), dtype)
    got = pairwise_sqdist_pallas(q, cands, interpret=True)
    want = pairwise_sqdist_ref(q, cands)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * m)


@pytest.mark.parametrize("b,k,d", [(8, 4, 2), (33, 9, 4), (64, 32, 16)])
@pytest.mark.parametrize("mode", ["attraction", "repulsion"])
@pytest.mark.parametrize("alpha", [0.4, 1.0, 3.0])
def test_ne_forces_sweep(b, k, d, mode, alpha):
    rng = np.random.default_rng(b + k)
    y = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    nbr = jnp.asarray(rng.normal(size=(b, k, d)).astype(np.float32))
    coef = jnp.asarray(rng.random((b, k)).astype(np.float32))
    got = ne_forces_pallas(y, nbr, coef, alpha, mode=mode, interpret=True)
    want = ne_forces_ref(y, nbr, coef, alpha, mode=mode)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# Gather-fused (index-taking) kernel variants


@pytest.mark.parametrize("n,m,b,c,bb,bm", [
    (50, 19, 37, 5, 16, 8),      # everything ragged; M not a mult of bm
    (64, 128, 64, 7, 32, 128),   # exact tiling, unpadded B
    (40, 300, 33, 3, 8, 128),    # padded B + clamped+masked final M chunk
    (30, 2, 30, 9, 16, 512),     # tiny M (the LD-space case)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_sqdist_gather_sweep(n, m, b, c, bb, bm, dtype):
    rng = np.random.default_rng(n + m + b)
    x = jnp.asarray(rng.normal(size=(n, m)), dtype)
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    # include out-of-range ids: the kernel must clip exactly like the ref
    cand = jnp.asarray(rng.integers(-2, n + 3, (b, c)).astype(np.int32))
    got = pairwise_sqdist_gather_pallas(x, qid, cand, block_b=bb,
                                        block_m=bm, interpret=True)
    want = pairwise_sqdist_gather_ref(x, qid, cand)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * m)


@pytest.mark.parametrize("sub_b,persistent_q", [
    (8, False),      # 2-slot double buffer, per-chunk q staging
    (8, True),       # double buffer + persistent q slab
    (16, None),      # monolithic sub-block (no pipelining), auto q
    (None, True),    # auto sub_b, forced persistent q
])
def test_pairwise_sqdist_gather_pipeline_variants(sub_b, persistent_q):
    """The double-buffered b loop and the persistent-q slab are pure
    scheduling: every (sub_b, persistent_q) point must agree with the
    ref, including multi-M-chunk grids with a ragged final chunk."""
    rng = np.random.default_rng(17)
    n, m, b, c = 45, 300, 37, 5            # 5 ragged M-chunks at bm=64
    x = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    cand = jnp.asarray(rng.integers(-2, n + 3, (b, c)).astype(np.int32))
    got = pairwise_sqdist_gather_pallas(x, qid, cand, block_b=16,
                                        block_m=64, sub_b=sub_b,
                                        persistent_q=persistent_q,
                                        interpret=True)
    want = pairwise_sqdist_gather_ref(x, qid, cand)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("sub_b", [8, 16, 32])
def test_ne_forces_gather_double_buffer_sub_blocks(sub_b):
    """Sub-block size is pure scheduling for the force kernel too."""
    rng = np.random.default_rng(23)
    n, b, d = 50, 37, 3
    segments = (("attraction", 4), ("repulsion", 3), ("repulsion", 2))
    k = 9
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    nbr = jnp.asarray(rng.integers(-1, n + 2, (b, k)).astype(np.int32))
    coef = jnp.asarray(rng.random((b, k)).astype(np.float32))
    got = ne_forces_gather_pallas(x, qid, nbr, coef, 1.3, segments=segments,
                                  block_b=32, sub_b=sub_b, interpret=True)
    want = ne_forces_gather_ref(x, qid, nbr, coef, 1.3, segments=segments)
    for gs, ws, name in zip(got, want, ("agg", "edge", "wsum")):
        for s, (g, w) in enumerate(zip(gs, ws)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"{name}[{s}]@sub_b={sub_b}")


def test_dimension_semantics_annotated_kernels_parity():
    """The gather kernels carry grid ``dimension_semantics`` annotations
    ('parallel' row blocks, 'arbitrary' accumulation axes) for real-TPU
    tuning.  The annotation must be a pure scheduling hint: interpret-
    mode parity with the refs on multi-block grids (several row blocks
    AND several M chunks, so both axes actually iterate) pins that, and
    pins that the compat shim (TPUCompilerParams vs CompilerParams)
    resolves on this jax version."""
    from repro.compat import tpu_compiler_params

    params = tpu_compiler_params(dimension_semantics=("parallel",))
    assert params is not None

    rng = np.random.default_rng(31)
    n, m, b, c = 60, 200, 53, 5            # 4 ragged M chunks at bm=64
    x = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    cand = jnp.asarray(rng.integers(-2, n + 3, (b, c)).astype(np.int32))
    got = pairwise_sqdist_gather_pallas(x, qid, cand, block_b=16,
                                        block_m=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(pairwise_sqdist_gather_ref(
                                   x, qid, cand)),
                               rtol=1e-5, atol=1e-4)

    d = 3
    segments = (("attraction", 4), ("repulsion", 3))
    y = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(-1, n + 2, (b, 7)).astype(np.int32))
    coef = jnp.asarray(rng.random((b, 7)).astype(np.float32))
    got = ne_forces_gather_pallas(y, qid, nbr, coef, 1.1, segments=segments,
                                  block_b=16, interpret=True)
    want = ne_forces_gather_ref(y, qid, nbr, coef, 1.1, segments=segments)
    for gs, ws in zip(got, want):
        for g, w in zip(gs, ws):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)

    from repro.kernels.knn_merge.kernel import knn_merge_pallas
    from repro.kernels.knn_merge.ref import knn_merge_ref
    xq = jnp.asarray((rng.integers(-8, 9, (n, m)) / 4.0).astype(np.float32))
    k = 6
    cur_idx = jnp.asarray(rng.integers(0, n, (b, k)).astype(np.int32))
    d0 = jnp.sort(jnp.sum((xq[cur_idx] - xq[qid][:, None, :]) ** 2, -1), 1)
    order = jnp.argsort(jnp.sum((xq[cur_idx] - xq[qid][:, None, :]) ** 2,
                                -1), 1)
    cur_idx = jnp.take_along_axis(cur_idx, order, 1)
    active = jnp.ones((b, c), bool)
    got = knn_merge_pallas(xq, qid, cur_idx, d0, cand, active,
                           rescore=False, block_b=16, block_m=64,
                           interpret=True)
    want = knn_merge_ref(xq, qid, cur_idx, d0, cand, cand_active=active)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_pairwise_sqdist_gather_matches_pregather():
    """Same answer as the pre-gather kernel fed the explicit X[cand]."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(60, 23)).astype(np.float32))
    qid = jnp.arange(41, dtype=jnp.int32)
    cand = jnp.asarray(rng.integers(0, 60, (41, 6)).astype(np.int32))
    got = pairwise_sqdist_gather_pallas(x, qid, cand, block_b=16,
                                        block_m=16, interpret=True)
    want = pairwise_sqdist_pallas(x[qid], x[cand], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("segments", [
    (("attraction", 5),),
    (("repulsion", 4),),
    (("attraction", 4), ("repulsion", 3), ("repulsion", 2)),
])
@pytest.mark.parametrize("b,d,bb", [(37, 2, 16),    # padded B, vis-scale d
                                    (64, 8, 32),    # unpadded B
                                    (21, 16, 8)])
@pytest.mark.parametrize("alpha", [0.4, 1.0, 3.0])
def test_ne_forces_gather_sweep(segments, b, d, bb, alpha):
    k = sum(s for _, s in segments)
    rng = np.random.default_rng(b * 10 + d)
    n = 50
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    nbr = jnp.asarray(rng.integers(-1, n + 2, (b, k)).astype(np.int32))
    coef = jnp.asarray(rng.random((b, k)).astype(np.float32))
    got = ne_forces_gather_pallas(x, qid, nbr, coef, alpha,
                                  segments=segments, block_b=bb,
                                  interpret=True)
    want = ne_forces_gather_ref(x, qid, nbr, coef, alpha, segments=segments)
    for gs, ws, name in zip(got, want, ("agg", "edge", "wsum")):
        for s, (g, w) in enumerate(zip(gs, ws)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"{name}[{s}]")


def test_ne_forces_gather_matches_per_mode_launches():
    """One segmented launch == three independent pre-gather launches."""
    rng = np.random.default_rng(9)
    n, b, d = 48, 30, 4
    sizes, modes = (6, 5, 3), ("attraction", "repulsion", "repulsion")
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    nbr = jnp.asarray(rng.integers(0, n, (b, sum(sizes))).astype(np.int32))
    coef = jnp.asarray(rng.random((b, sum(sizes))).astype(np.float32))
    aggs, edges, wsums = ne_forces_gather_pallas(
        x, qid, nbr, coef, 1.3, segments=tuple(zip(modes, sizes)),
        block_b=16, interpret=True)
    k0 = 0
    for s, (mode, size) in enumerate(zip(modes, sizes)):
        sl = slice(k0, k0 + size)
        agg_s, edge_s, wsum_s = ne_forces_pallas(
            x[qid], x[nbr[:, sl]], coef[:, sl], 1.3, mode=mode,
            block_b=16, interpret=True)
        np.testing.assert_allclose(np.asarray(aggs[s]), np.asarray(agg_s),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(edges[s]),
                                   np.asarray(edge_s), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(wsums[s]),
                                   np.asarray(wsum_s), rtol=2e-5, atol=2e-5)
        k0 += size


def test_ne_forces_gather_emit_edges_skips_output():
    """emit_edges=False segments return None edges; everything else is
    unchanged vs the all-edges launch."""
    rng = np.random.default_rng(11)
    n, b, d = 40, 24, 3
    seg = (("attraction", 5), ("repulsion", 4))
    k = 9
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    nbr = jnp.asarray(rng.integers(0, n, (b, k)).astype(np.int32))
    coef = jnp.asarray(rng.random((b, k)).astype(np.float32))
    full = ne_forces_gather_pallas(x, qid, nbr, coef, 0.9, segments=seg,
                                   block_b=8, interpret=True)
    part = ne_forces_gather_pallas(x, qid, nbr, coef, 0.9, segments=seg,
                                   emit_edges=(True, False), block_b=8,
                                   interpret=True)
    assert part[1][1] is None
    np.testing.assert_allclose(np.asarray(part[1][0]),
                               np.asarray(full[1][0]), rtol=1e-6)
    for which in (0, 2):    # aggs, wsums identical
        for s in range(2):
            np.testing.assert_allclose(np.asarray(part[which][s]),
                                       np.asarray(full[which][s]),
                                       rtol=1e-6)


@pytest.mark.parametrize("d", [1, 2, 3, 4])
@pytest.mark.parametrize("n,b,bb", [(1100, 37, 16),   # N % 128, B % bb
                                    (70, 64, 32)])    # one packed row
def test_ne_forces_gather_vmem_rows_parity(d, n, b, bb):
    """The resident packed-table row source matches the reference, and the
    DMA row source bit for bit, on the step's three segments with the
    negatives' edges elided: repeated ids, ids past both ends and
    SENTINEL (all clipped), N and B off every tile."""
    from repro.core.knn import SENTINEL
    segments = (("attraction", 5), ("repulsion", 4), ("repulsion", 3))
    emit = (True, True, False)
    rng = np.random.default_rng(n + 10 * d)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qid = rng.integers(0, n, b).astype(np.int32)
    qid[1] = qid[0]
    nbr = rng.integers(-2, n + 2, (b, 12)).astype(np.int32)
    nbr[:, 1] = nbr[:, 0]
    nbr[0, 3:6] = SENTINEL
    nbr[2, :] = n - 1
    coef = jnp.asarray(rng.random((b, 12)).astype(np.float32))
    args = (x, jnp.asarray(qid), jnp.asarray(nbr), coef, 0.8)
    kw = dict(segments=segments, emit_edges=emit, block_b=bb,
              interpret=True)
    got = ne_forces_gather_pallas(*args, row_source="vmem", **kw)
    dma = ne_forces_gather_pallas(*args, row_source="dma", **kw)
    want = ne_forces_gather_ref(*args, segments=segments, emit_edges=emit)
    for gs, ds, ws, name in zip(got, dma, want, ("agg", "edge", "wsum")):
        for s, (g, dd, w) in enumerate(zip(gs, ds, ws)):
            if w is None:
                assert g is None and dd is None
                continue
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"{name}[{s}]")
            np.testing.assert_array_equal(np.asarray(g), np.asarray(dd),
                                          err_msg=f"{name}[{s}] vs dma")


@pytest.mark.parametrize("n,d,want", [
    (262144, 2, "vmem"),            # atlas-262k
    (70000, 2, "vmem"),             # mnist-70k
    (2 ** 21, 2, "vmem"),           # the budget's d=2 edge
    (2 ** 21 + 1, 2, "dma"),        # one tile over it
    (2 ** 20, 4, "vmem"),
    (2 ** 20 + 1024, 4, "dma"),
    (16384, 8, "dma"),              # wider than the packing takes
    (16384, 32, "dma"),             # backbone features
    (2 ** 20, 32, "dma"),
])
def test_ne_forces_row_source(n, d, want):
    from repro.kernels.ne_forces.ops import row_source
    assert row_source(n, d) == want


def _cand_merge_problem(n, d, b, k, seed, *, quantised):
    """A candidate-fused merge problem with every id kind: repeated
    queries and current ids, SENTINEL in the current list, the one-hop
    table and the extra slots, extra ids past both ends, inactive rows."""
    from repro.core.knn import SENTINEL
    rng = np.random.default_rng(seed)
    x = rng.integers(-8, 9, (n, d)) / 4.0 if quantised \
        else rng.normal(size=(n, d))
    x = jnp.asarray(x.astype(np.float32))
    qid = rng.integers(0, n, b).astype(np.int32)
    qid[1] = qid[0]
    cur_idx = rng.integers(0, n, (b, k)).astype(np.int32)
    cur_idx[:, 1] = cur_idx[:, 0]
    sent = np.sort(rng.random((b, k)) < 0.2, axis=1)
    cur_idx[sent] = SENTINEL
    d0 = np.array(jnp.sum((x[jnp.clip(jnp.asarray(cur_idx), 0, n - 1)]
                           - x[qid][:, None, :]) ** 2, axis=-1))
    d0[sent] = np.inf
    order = np.argsort(d0, axis=1, kind="stable")
    cur_idx = np.take_along_axis(cur_idx, order, axis=1)
    oth = rng.integers(0, n, (b, 5)).astype(np.int32)
    oth[rng.random((b, 5)) < 0.15] = SENTINEL
    sec = rng.integers(0, n, (n, 6)).astype(np.int32)
    extra = rng.integers(-2, n + 3, (b, 2)).astype(np.int32)
    extra[0] = SENTINEL
    return dict(
        x=x, qid=jnp.asarray(qid), cur_idx=jnp.asarray(cur_idx),
        cur_d=jnp.asarray(np.take_along_axis(d0, order, axis=1)),
        cur_valid=jnp.asarray((cur_idx != SENTINEL)
                              & (rng.random((b, k)) < 0.9)),
        firsts=(jnp.asarray(cur_idx), jnp.asarray(oth)),
        seconds=(jnp.asarray(sec),), extra=jnp.asarray(extra),
        active=jnp.asarray(rng.random(n) >= 0.15))


MERGE_SOURCES = (("two_hop", 0, 0, 3), ("one_hop", 1, 2), ("uniform", 2),
                 ("extra", 2))


def _cand_merge(p, rescore, use_active, **kw):
    from repro.kernels.knn_merge.kernel import knn_merge_cand_pallas
    return knn_merge_cand_pallas(
        p["x"], p["qid"], p["cur_idx"],
        p["cur_valid"] if rescore else p["cur_d"], jnp.int32(77),
        p["firsts"], p["seconds"], p["extra"],
        p["active"] if use_active else None, sources=MERGE_SOURCES,
        rescore=rescore, interpret=True, **kw)


@pytest.mark.parametrize("d,n,b,bb,rescore,use_active,rows,flags", [
    (d, n, b, bb, rescore, True, "vmem", "vmem")
    for d in (1, 2, 3, 4)
    for n, b, bb in ((1100, 37, 16),     # N % 128, B % bb
                     (70, 64, 32))       # one packed row
    for rescore in (True, False)
] + [
    (2, 1100, 37, 16, True, True, "vmem", "dma"),    # flags gave way
    (2, 1100, 37, 16, False, True, "dma", "vmem"),   # the HD launch's
    (4, 70, 64, 32, True, True, "vmem", "dma"),
    (3, 70, 64, 32, False, True, "dma", "vmem"),
    (2, 1100, 37, 16, True, False, "vmem", "vmem"),  # active=None
    (3, 70, 64, 32, False, False, "vmem", "vmem"),
])
def test_knn_merge_cand_vmem_sources_parity(d, n, b, bb, rescore,
                                            use_active, rows, flags):
    """Resident packed row and flag tables give the DMA sources' outputs
    bit for bit, and the reference's, in both modes: the LD rescore
    launch and the HD launch."""
    from repro.kernels.knn_merge.ref import knn_merge_cand_ref
    kw = dict(block_b=bb, row_source=rows, flag_source=flags)
    p = _cand_merge_problem(n, d, b, 6, seed=n + 10 * d + rescore,
                            quantised=True)
    got = _cand_merge(p, rescore, use_active, **kw)
    dma = _cand_merge(p, rescore, use_active, block_b=bb)
    want = knn_merge_cand_ref(
        p["x"], p["qid"], p["cur_idx"], None if rescore else p["cur_d"],
        salt=jnp.int32(77), sources=MERGE_SOURCES, first_tables=p["firsts"],
        second_tables=p["seconds"], extra=p["extra"],
        active=p["active"] if use_active else None,
        cur_valid=p["cur_valid"] if rescore else None)
    for g, dd, w, name in zip(got, dma, want, ("idx", "d", "improved")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(dd),
                                      err_msg=f"vs dma: {name}")
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"vs ref: {name}")
    # unquantised coordinates: distance sums that round, the same bits
    p = _cand_merge_problem(n, d, b, 6, seed=n + d, quantised=False)
    got = _cand_merge(p, rescore, use_active, **kw)
    dma = _cand_merge(p, rescore, use_active, block_b=bb)
    for g, dd, name in zip(got, dma, ("idx", "d", "improved")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(dd),
                                      err_msg=f"unquantised: {name}")


@pytest.mark.parametrize("n,d,want", [
    (262144, 2, ("vmem", "vmem")),      # atlas-262k, LD launch
    (70000, 2, ("vmem", "vmem")),       # mnist-70k, LD launch
    (1306112, 2, ("vmem", "vmem")),     # published atlas: 15.7 of 16.8 MB
    (1397760, 2, ("vmem", "vmem")),     # both tables at the budget's edge
    (1397761, 2, ("vmem", "dma")),      # one tile past it: flags give way
    (2 ** 21, 2, ("vmem", "dma")),      # the row table alone at the edge
    (2 ** 21 + 1, 2, ("dma", "vmem")),
    (16384, 32, ("dma", "vmem")),       # backbone features
    (262144, 50, ("dma", "vmem")),      # the HD launch
    (2 ** 22, 50, ("dma", "vmem")),     # the flag table alone at the edge
    (2 ** 22 + 1, 50, ("dma", "dma")),
])
def test_knn_merge_sources(n, d, want):
    from repro.kernels.knn_merge.ops import merge_sources
    assert merge_sources(n, d) == want


def test_ne_forces_action_reaction():
    """Aggregated force equals the sum of edge forces (Newton pairs)."""
    rng = np.random.default_rng(3)
    y = jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32))
    nbr = jnp.asarray(rng.normal(size=(16, 5, 3)).astype(np.float32))
    coef = jnp.ones((16, 5), jnp.float32)
    agg, edge, _ = ne_forces_ref(y, nbr, coef, 0.8, mode="repulsion")
    np.testing.assert_allclose(np.asarray(agg),
                               np.asarray(jnp.sum(edge, axis=1)), rtol=1e-6)


@pytest.mark.parametrize("s,d,hq,hkv", [(64, 32, 4, 2), (96, 64, 8, 8),
                                        (128, 32, 6, 1)])
@pytest.mark.parametrize("opts", [{}, {"softcap": 10.0}, {"window": 23},
                                  {"softcap": 5.0, "window": 17}])
def test_flash_attention_sweep(s, d, hq, hkv, opts):
    rng = np.random.default_rng(s + hq)
    q = jnp.asarray(rng.normal(size=(2, hq, s, d)).astype(np.float32)) * 0.4
    k = jnp.asarray(rng.normal(size=(2, hkv, s, d)).astype(np.float32)) * 0.4
    v = jnp.asarray(rng.normal(size=(2, hkv, s, d)).astype(np.float32))
    got = flash_attention_pallas(q, k, v, block_q=32, block_k=32,
                                 interpret=True, **opts)
    want = flash_attention_ref(q, k, v, **opts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, block_q=32, block_k=32,
                                 interpret=True)
    want = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@settings(max_examples=25, deadline=None)
@given(b=st.integers(1, 40), c=st.integers(1, 12), m=st.integers(1, 48),
       scale=st.floats(0.1, 10.0))
def test_sqdist_properties(b, c, m, scale):
    """Non-negativity, exact zero on identical points, scale law."""
    rng = np.random.default_rng(b * 7 + c)
    q = jnp.asarray(rng.normal(size=(b, m)).astype(np.float32)) * scale
    cands = jnp.repeat(q[:, None, :], c, axis=1)
    d = pairwise_sqdist_pallas(q, cands, interpret=True)
    np.testing.assert_allclose(np.asarray(d), 0.0, atol=1e-4 * scale ** 2)
    other = jnp.asarray(rng.normal(size=(b, c, m)).astype(np.float32))
    d2 = pairwise_sqdist_pallas(q, other, interpret=True)
    assert bool(jnp.all(d2 >= 0.0))
