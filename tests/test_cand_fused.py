"""Candidate-fused sampling (§Perf H17): counter RNG, kernel parity, HLO.

Contract layers:

  * RNG statistics -- chi-square uniformity of the counter hash (it is a
    deterministic function, so the test is exactly reproducible) and a
    distribution match of the fused two-hop sampler against a NumPy
    statement of the two-hop distribution (same tables, aggregated
    marginals).
  * kernel vs oracle -- the candidate-generating Pallas kernel
    (interpret mode) must reproduce the pure-jnp counter sampler
    (``knn_lib.counter_candidates``) feeding the reference selection
    pipeline EXACTLY on discrete outputs: quantised coordinates make
    every distance representable, so generation, chained two-hop DMAs,
    per-candidate active DMAs, dedup and merge are all pinned bitwise.
  * step level -- a 50-step trajectory with in-kernel generation is
    bit-equal to the same 50 steps where the jnp reference sampler
    generates the candidates and feeds them to the operand-taking merge
    kernel (the acceptance anchor).
  * HLO -- the compiled step's instructions hold NO threefry ops and NO
    (n, s, K2) two-hop gather broadcast; a jitted ``jax.random`` draw
    and a hand-built two-hop gather are the positive controls for the
    two detectors.
  * satellites -- cached reverse-edge table (cache-corruption
    invariance at ``rev_refresh=1``, cadence negative control, the
    ``nnd`` driver's cache vs in-step parity) and the
    ``fit(auto_rescale=)`` ChunkMetrics consumer.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import funcsne
from repro.core import knn as knn_lib
from repro.core.knn import SENTINEL
from repro.data.synthetic import blobs
from repro.kernels.knn_merge import ops as knn_merge_ops
from repro.kernels.knn_merge.kernel import knn_merge_cand_pallas
from repro.kernels.knn_merge.ops import knn_merge
from repro.kernels.knn_merge.ref import knn_merge_cand_ref


# --------------------------------------------------------------------------
# Counter-RNG statistics


def test_counter_randint_chi_square_uniform():
    """40k draws into 64 bins: chi-square must sit below the p=0.001
    critical value (103.4 at df=63).  Deterministic -- no flaky seeds."""
    n_rows, n_draws, bins = 200, 200, 64
    rows = jnp.arange(n_rows, dtype=jnp.int32)[:, None]
    draws = jnp.arange(n_draws, dtype=jnp.int32)[None, :]
    for salt in (0, 1, 12345, -77):
        v = np.asarray(knn_lib.counter_randint(jnp.int32(salt), rows,
                                               draws, bins)).ravel()
        counts = np.bincount(v, minlength=bins)
        expect = v.size / bins
        chi2 = float(((counts - expect) ** 2 / expect).sum())
        assert chi2 < 103.4, (salt, chi2)


def test_counter_uniform01_range_and_mean():
    h = knn_lib.hash3(jnp.int32(7), jnp.arange(50000, dtype=jnp.int32), 0)
    u = np.asarray(knn_lib.counter_uniform01(h))
    assert (u >= 0.0).all() and (u < 1.0).all()
    assert abs(u.mean() - 0.5) < 0.01
    assert abs(u.var() - 1.0 / 12.0) < 0.01


def test_counter_stream_shard_invariance():
    """Draws are keyed on global row ids: sampling rows [0, n) in one
    block equals sampling any row slice separately (the property that
    lets the distributed path drop the per-shard fold_in)."""
    sources = (("uniform", 3),)
    salt = jnp.int32(42)
    rows = jnp.arange(64, dtype=jnp.int32)
    full = knn_lib.counter_candidates(salt, rows, sources, n_total=101)
    lo = knn_lib.counter_candidates(salt, rows[:32], sources, n_total=101)
    hi = knn_lib.counter_candidates(salt, rows[32:], sources, n_total=101)
    np.testing.assert_array_equal(np.asarray(full),
                                  np.vstack([np.asarray(lo),
                                             np.asarray(hi)]))


def test_two_hop_marginal_matches_legacy_sampler():
    """The fused two-hop source must draw from the two-hop distribution
    ``second[first[r, a], b]`` with a uniform, SENTINEL mids falling back
    to the row id, and b uniform: aggregate marginals over many trials
    agree with a NumPy sampler of that distribution within a small TV
    distance."""
    rng = np.random.default_rng(0)
    n, k1, k2, s, trials = 50, 6, 5, 4, 300
    first = rng.integers(0, n, (n, k1)).astype(np.int32)
    first[rng.random((n, k1)) < 0.2] = SENTINEL
    second = rng.integers(0, n, (n, k2)).astype(np.int32)
    rows = np.arange(n, dtype=np.int32)

    ref = []
    for _ in range(trials):
        a = rng.integers(0, k1, (n, s))
        mid = np.take_along_axis(first, a, axis=1)
        mid = np.where(mid == SENTINEL, rows[:, None] % n, mid)
        b = rng.integers(0, k2, (n, s))
        ref.append(second[mid, b])
    fused = []
    for t in range(trials):
        fused.append(np.asarray(knn_lib.counter_candidates(
            jnp.int32(t), jnp.asarray(rows), (("two_hop", 0, 0, s),),
            (jnp.asarray(first),), (jnp.asarray(second),))))
    h_ref = np.bincount(np.concatenate(ref).ravel(), minlength=n)
    h_fus = np.bincount(np.concatenate(fused).ravel(), minlength=n)
    tv = 0.5 * np.abs(h_ref / h_ref.sum() - h_fus / h_fus.sum()).sum()
    assert tv < 0.03, tv


# --------------------------------------------------------------------------
# Kernel vs jnp reference sampler: discrete-exact parity


def _problem(n, m, b, k, seed, *, k_oth=5, k2a=6, k2b=4):
    rng = np.random.default_rng(seed)
    x = jnp.asarray((rng.integers(-8, 9, (n, m)) / 4.0).astype(np.float32))
    qid = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    cur_idx = rng.integers(0, n, (b, k)).astype(np.int32)
    sent = np.sort(rng.random((b, k)) < 0.2, axis=1)
    cur_idx[sent] = SENTINEL
    d0 = np.array(jnp.sum((x[jnp.clip(jnp.asarray(cur_idx), 0, n - 1)]
                           - x[qid][:, None, :]) ** 2, axis=-1))
    d0[sent] = np.inf
    order = np.argsort(d0, axis=1, kind="stable")
    cur_idx = jnp.asarray(np.take_along_axis(cur_idx, order, axis=1))
    cur_d = jnp.asarray(np.take_along_axis(d0, order, axis=1))
    oth = rng.integers(0, n, (b, k_oth)).astype(np.int32)
    oth[rng.random((b, k_oth)) < 0.15] = SENTINEL
    sec_a = rng.integers(0, n, (n, k2a)).astype(np.int32)
    sec_a[rng.random((n, k2a)) < 0.1] = SENTINEL
    sec_b = rng.integers(0, n, (n, k2b)).astype(np.int32)
    active = jnp.asarray(rng.random(n) >= 0.15)
    extra = jnp.asarray(rng.integers(-2, n + 3, (b, 2)).astype(np.int32))
    cur_valid = jnp.asarray((np.asarray(cur_idx) != SENTINEL)
                            & (rng.random((b, k)) < 0.9))
    return (x, qid, cur_idx, cur_d, jnp.asarray(oth), jnp.asarray(sec_a),
            jnp.asarray(sec_b), active, extra, cur_valid)


def _assert_cand_parity(n, m, b, k, seed, *, rescore, use_active,
                        use_extra, **pallas_kw):
    (x, qid, cur_idx, cur_d, oth, sec_a, sec_b, active, extra,
     cur_valid) = _problem(n, m, b, k, seed)
    sources = (("two_hop", 0, 0, 3), ("one_hop", 1, 2),
               ("two_hop", 1, 1, 2), ("uniform", 2)) \
        + ((("extra", 2),) if use_extra else ())
    salt = jnp.int32(seed * 7 + 3)
    kw = dict(salt=salt, sources=sources, first_tables=(cur_idx, oth),
              second_tables=(sec_a, sec_b),
              extra=extra if use_extra else None,
              active=active if use_active else None)
    cd, cv = (None, cur_valid) if rescore else (cur_d, None)
    want = knn_merge_cand_ref(x, qid, cur_idx, cd, cur_valid=cv, **kw)
    want_rank = knn_merge_cand_ref(x, qid, cur_idx, cd, cur_valid=cv,
                                   rank=True, **kw)
    got = knn_merge_cand_pallas(
        x, qid, cur_idx, cv if rescore else cur_d, salt, (cur_idx, oth),
        (sec_a, sec_b), extra if use_extra else None,
        active if use_active else None, sources=sources, rescore=rescore,
        interpret=True, **pallas_kw)
    for g, w, name in zip(want_rank, want, ("idx", "d", "improved")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"rank:{name}")
    for g, w, name in zip(got, want, ("idx", "d", "improved")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"kernel:{name}")


@pytest.mark.parametrize("n,m,b,k,bb,bm", [
    (50, 19, 37, 6, 16, 8),     # everything ragged; 3 ragged M chunks
    (64, 128, 64, 8, 32, 128),  # exact tiling, unpadded B
    (40, 300, 33, 4, 8, 128),   # padded B + clamped+masked final M chunk
    (30, 2, 30, 8, 16, 512),    # tiny M (the LD-space case)
])
@pytest.mark.parametrize("rescore", [False, True])
def test_cand_kernel_vs_ref_sweep(n, m, b, k, bb, bm, rescore):
    """In-kernel generation (hash draws, chained two-hop element DMAs,
    active DMAs) == jnp counter sampler + reference selection, exactly."""
    _assert_cand_parity(n, m, b, k, seed=n + m + k, rescore=rescore,
                        use_active=True, use_extra=True, block_b=bb,
                        block_m=bm)


@pytest.mark.parametrize("use_active,use_extra", [
    (False, False), (True, False), (False, True),
])
def test_cand_kernel_optional_channels(use_active, use_extra):
    """The active-DMA channel and the extra (cached reverse-edge) slab
    are independently optional."""
    _assert_cand_parity(45, 33, 29, 5, seed=11, rescore=False,
                        use_active=use_active, use_extra=use_extra,
                        block_b=16, block_m=16)


@pytest.mark.parametrize("sub_b,persistent_q", [
    (8, False), (8, True), (16, None), (None, True),
])
def test_cand_kernel_pipeline_variants(sub_b, persistent_q):
    """Double-buffering and the persistent-q slab stay pure scheduling
    for the candidate-generating kernel too."""
    _assert_cand_parity(45, 300, 37, 5, seed=17, rescore=False,
                        use_active=True, use_extra=True, block_b=16,
                        block_m=64, sub_b=sub_b, persistent_q=persistent_q)


def test_cand_ops_dispatch():
    """ops.knn_merge in candidate-fused mode: 'xla' is the jnp-sampler
    oracle, 'interpret' runs the generating kernel, both agree; explicit
    ``cand_active`` is rejected (activity is derived in-op)."""
    (x, qid, cur_idx, cur_d, oth, sec_a, sec_b, active, extra,
     cur_valid) = _problem(40, 7, 23, 5, seed=5)
    sources = (("two_hop", 0, 0, 2), ("uniform", 2), ("extra", 2))
    kw = dict(sources=sources, salt=jnp.int32(3),
              first_tables=(cur_idx,), second_tables=(sec_a,),
              active=active)
    want = knn_merge(x, qid, cur_idx, cur_d, extra, backend="xla", **kw)
    got = knn_merge(x, qid, cur_idx, cur_d, extra, backend="interpret",
                    **kw)
    for g, w, name in zip(got, want, ("idx", "d", "improved")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    with pytest.raises(AssertionError):
        knn_merge(x, qid, cur_idx, cur_d, extra, backend="xla",
                  cand_active=jnp.ones((23, 2), bool), **kw)


# --------------------------------------------------------------------------
# Step level


def _run_steps(cfg, st, Xj, hp, n_steps):
    step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
    for _ in range(n_steps):
        st = step(st, Xj, hp)
    return st


def _assert_states_equal(a, b, skip=()):
    for name in funcsne.FuncSNEState._fields:
        if name in skip:
            continue
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)


@pytest.mark.slow
def test_cand_fused_step_bit_equal_to_jnp_sampler_feed(monkeypatch):
    """Acceptance: a 50-step trajectory with candidates generated
    *inside* the kernel is bit-equal to the jnp reference sampler
    generating them and feeding the operand-taking merge kernel (same
    interpret backend, so scoring arithmetic is identical and the only
    varying piece is the generation)."""
    X, _ = blobs(n=64, dim=8, n_centers=3, center_std=5.0, seed=1)
    Xj = jnp.asarray(X)
    cfg = funcsne.FuncSNEConfig(n_points=64, dim_hd=8, k_hd=6, k_ld=4,
                                c_hd_non=2, c_hd_ld=1, c_hd_ld_non=1,
                                c_hd_rand=1, c_ld_non=2, c_ld_hd=1,
                                c_ld_rand=1, n_negatives=4,
                                backend="interpret")
    st0 = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg)
    hp = funcsne.default_hparams(64)

    st_kernel = _run_steps(cfg, st0, Xj, hp, 50)

    real = knn_merge_ops.knn_merge

    def feed(x, qid, cur_idx, cur_d, cand=None, *, cand_active=None,
             cur_valid=None, backend="auto", sources=None, salt=None,
             first_tables=(), second_tables=(), active=None):
        if sources is None:
            return real(x, qid, cur_idx, cur_d, cand,
                        cand_active=cand_active, cur_valid=cur_valid,
                        backend=backend)
        gen = knn_lib.counter_candidates(
            salt, qid, tuple(s for s in sources if s[-1] > 0),
            first_tables, second_tables, n_total=x.shape[0], extra=cand)
        act = None
        if active is not None:
            act = active[jnp.clip(gen, 0, active.shape[0] - 1)]
        return real(x, qid, cur_idx, cur_d, gen, cand_active=act,
                    cur_valid=cur_valid, backend=backend)

    monkeypatch.setattr(funcsne, "knn_merge", feed)
    st_feed = _run_steps(cfg, st0, Xj, hp, 50)
    _assert_states_equal(st_kernel, st_feed)


# --------------------------------------------------------------------------
# HLO: threefry and the two-hop broadcast are structurally gone


def _step_hlo_text(cfg, n):
    X = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(n, cfg.dim_hd)).astype(np.float32))
    st_ = funcsne.init_state(jax.random.PRNGKey(0), X, cfg)
    hp = funcsne.default_hparams(n)
    step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
    return step.lower(st_, X, hp).compile().as_text()


def _instruction_lines(text):
    """The instruction lines (``%name = ...``) of an HLO module's text.
    The module also carries stack-frame tables of file and function
    names -- this test's own name among them -- which are not program."""
    return [l for l in text.splitlines()
            if re.match(r"\s*(ROOT\s+)?%[\w.\-]+ = ", l)]


def _random_op_lines(text):
    return [l for l in _instruction_lines(text)
            if "threefry" in l.lower() or "rng-bit-generator" in l.lower()]


def _twohop_broadcast_shapes(text, cfg, n):
    from repro.launch.hlo_analysis import module_array_shapes
    tails = {(cfg.c_hd_non, cfg.k_hd), (cfg.c_hd_ld_non, cfg.k_ld),
             (cfg.c_ld_non, cfg.k_ld)}
    return [dims for dtype, dims in module_array_shapes(text)
            if dtype == "s32" and len(dims) == 3
            and tuple(dims[1:]) in tails and dims[0] >= n]


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_cand_fused_step_hlo_no_threefry_no_twohop_broadcast(backend):
    """Acceptance: the compiled step contains no threefry/random-bits
    ops anywhere (gate, candidates, negatives all run on the counter
    RNG) and no (n, s, K2) two-hop gather broadcast (in-kernel chains /
    flat gathers).  A jitted ``jax.random`` split + randint and a
    hand-built (n, s, K2) two-hop gather are the positive controls."""
    n = 257
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=7, backend=backend)
    text = _step_hlo_text(cfg, n)
    assert _random_op_lines(text) == [], "threefry back in the step"
    assert _twohop_broadcast_shapes(text, cfg, n) == [], \
        "(n, s, K2) two-hop broadcast back in the step"

    def draw(key):
        ka, kb = jax.random.split(key)
        return (jax.random.randint(ka, (n, cfg.c_hd_rand), 0, n)
                + jax.random.randint(kb, (n, cfg.c_hd_rand), 0, n))

    text_rng = jax.jit(draw).lower(jax.random.PRNGKey(0)).compile() \
        .as_text()
    assert _random_op_lines(text_rng), \
        "detector is blind: jax.random shows no threefry"

    def two_hop(first, second, a, b):
        mid = jnp.take_along_axis(first, a, axis=1)       # (n, s)
        cand = second[mid]                                 # (n, s, K2)
        return jnp.take_along_axis(cand, b[..., None], axis=2)[..., 0]

    s_, k2 = cfg.c_hd_non, cfg.k_hd
    args = (jnp.zeros((n, cfg.k_hd), jnp.int32),
            jnp.zeros((n, k2), jnp.int32),
            jnp.zeros((n, s_), jnp.int32), jnp.zeros((n, s_), jnp.int32))
    text_hop = jax.jit(two_hop).lower(*args).compile().as_text()
    assert _twohop_broadcast_shapes(text_hop, cfg, n), \
        "detector is blind: a two-hop gather shows no (n, s, K2) operand"


def test_cand_fused_chunked_hlo_no_threefry():
    """The scan-chunked driver compounds the win (T random phases per
    dispatch): the whole chunk module must be threefry-free too."""
    n = 96
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=5, backend="interpret")
    X = jnp.asarray(np.random.default_rng(1)
                    .normal(size=(n, 5)).astype(np.float32))
    st_ = funcsne.init_state(jax.random.PRNGKey(0), X, cfg)
    hp = funcsne.default_hparams(n)
    chunk = funcsne.make_chunked_step(cfg, 4)
    text = chunk.lower(st_, X, hp).compile().as_text()
    assert _instruction_lines(text), "no instruction lines parsed"
    assert _random_op_lines(text) == []


# --------------------------------------------------------------------------
# Satellite: cached reverse-edge table


def test_rev_cache_never_read_at_refresh_1():
    """At rev_refresh=1 the cache is rebuilt before every use, so
    corrupting it between steps must not change the trajectory -- the
    bit-parity argument that refresh=1 IS a rebuild at every
    refinement."""
    n = 96
    X, _ = blobs(n=n, dim=8, n_centers=3, seed=4)
    Xj = jnp.asarray(X)
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=8, c_hd_rev=2,
                                rev_refresh=1, backend="xla")
    st_a = funcsne.init_state(jax.random.PRNGKey(1), Xj, cfg)
    st_b = jax.tree.map(lambda x: jnp.array(x, copy=True), st_a)
    hp = funcsne.default_hparams(n)
    step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
    garbage = jnp.full((n, 2), 17, jnp.int32)
    for _ in range(10):
        st_a = step(st_a, Xj, hp)
        st_b = step(st_b._replace(rev_idx=garbage), Xj, hp)
    _assert_states_equal(st_a, st_b, skip=("rev_idx",))


def test_rev_cache_cadence_is_since_last_refresh():
    """Refinement runs behind a stochastic gate, so the cadence counts
    steps since the last *actual* refresh: a refinement at step
    rev_step + k refreshes iff k >= rev_refresh, regardless of absolute
    step alignment (an absolute step % k schedule would lose every
    refresh whose step the gate happened to skip)."""
    n = 64
    X, _ = blobs(n=n, dim=6, n_centers=2, seed=8)
    Xj = jnp.asarray(X)
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=6, c_hd_rev=2,
                                rev_refresh=3, backend="xla")
    st = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg)
    salt = jnp.int32(11)
    marker = jnp.full((n, 2), 23, jnp.int32)

    # 2 steps after the last refresh: the gate fired but the cache is
    # young -- no rebuild, the (marked) table is served as-is
    st_young = st._replace(step=jnp.int32(2), rev_step=jnp.int32(0),
                           rev_idx=marker)
    out = funcsne._hd_refine(cfg, st_young, Xj, salt, funcsne.AxisCtx())
    assert int(out.rev_step) == 0
    np.testing.assert_array_equal(np.asarray(out.rev_idx),
                                  np.asarray(marker))

    # 5 steps after (the step-3 refresh fell on a gate-skipped step):
    # the refresh is NOT lost -- it fires now and restamps rev_step
    st_stale = st._replace(step=jnp.int32(5), rev_step=jnp.int32(0),
                           rev_idx=marker)
    out = funcsne._hd_refine(cfg, st_stale, Xj, salt, funcsne.AxisCtx())
    assert int(out.rev_step) == 5
    assert not np.array_equal(np.asarray(out.rev_idx), np.asarray(marker))


def test_cand_kernel_accepts_zero_width_sources():
    """The grammar allows c == 0 entries; the kernel entry point must
    drop them instead of tripping over the static slot plan."""
    (x, qid, cur_idx, cur_d, oth, sec_a, _, _, _, _) = _problem(
        40, 7, 23, 5, seed=13)
    sources = (("two_hop", 0, 0, 0), ("uniform", 2), ("extra", 0))
    salt = jnp.int32(1)
    want = knn_merge_cand_ref(x, qid, cur_idx, cur_d, salt=salt,
                              sources=sources, first_tables=(cur_idx,),
                              second_tables=(sec_a,))
    got = knn_merge_cand_pallas(x, qid, cur_idx, cur_d, salt, (cur_idx,),
                                (sec_a,), None, None, sources=sources,
                                rescore=False, interpret=True)
    for g, w, name in zip(got, want, ("idx", "d", "improved")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_rev_cache_cadence_changes_candidates():
    """Negative control: rev_refresh=5 really serves stale tables (the
    trajectory departs from the rebuild-every-step one)."""
    n = 96
    X, _ = blobs(n=n, dim=8, n_centers=3, seed=5)
    Xj = jnp.asarray(X)
    kw = dict(n_points=n, dim_hd=8, c_hd_rev=4, min_refresh_prob=1.0,
              backend="xla")
    cfg1 = funcsne.FuncSNEConfig(rev_refresh=1, **kw)
    cfg5 = funcsne.FuncSNEConfig(rev_refresh=5, **kw)
    st0 = funcsne.init_state(jax.random.PRNGKey(2), Xj, cfg1)
    hp = funcsne.default_hparams(n)
    st1 = _run_steps(cfg1, st0, Xj, hp, 12)
    st5 = _run_steps(cfg5, st0, Xj, hp, 12)
    assert not np.array_equal(np.asarray(st1.hd_idx),
                              np.asarray(st5.hd_idx))


def test_nnd_rev_cache_refresh1_bit_equals_legacy():
    """The nnd driver's cached reverse table, rebuilt every iteration, is
    bit-identical to the in-step rebuild (rev=None): both pad with the
    same counter fill.  A coarser cadence is a real behaviour change."""
    from repro.core import nnd as nnd_lib
    from repro.core.nnd import NNDConfig, nnd, nnd_init, nnd_step
    X, _ = blobs(n=150, dim=12, n_centers=4, seed=9)
    Xj = jnp.asarray(X)
    cfg = NNDConfig(k=8, c_fwd=4, c_rev=2, backend="xla", rev_refresh=1)
    rng = jax.random.PRNGKey(0)

    idx_s, d_s, hist_s = nnd(Xj, cfg, rng=rng, max_iter=6, tol=-1.0)

    idx, d = nnd_init(rng, Xj, cfg)
    step = jax.jit(lambda r, i, dd, rv: nnd_step(r, Xj, i, dd, cfg,
                                                 rev=rv))
    rebuild = jax.jit(lambda i, r: nnd_lib._reverse_table(i, r, cfg))
    hist = []
    for it in range(6):
        r_it = jax.random.fold_in(rng, it)
        idx, d, frac = step(r_it, idx, d, rebuild(idx, r_it))
        hist.append(float(frac))
    np.testing.assert_array_equal(np.asarray(idx_s), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(d_s), np.asarray(d))
    assert hist_s == hist

    cfg3 = dataclasses.replace(cfg, rev_refresh=3)
    idx_3, _, _ = nnd(Xj, cfg3, rng=rng, max_iter=6, tol=-1.0)
    assert not np.array_equal(np.asarray(idx_3), np.asarray(idx))


def test_nnd_cand_fused_backends_agree():
    """NND's candidate-generating merge: the jnp sampler ('xla') and the
    generating kernel ('interpret') produce identical refinements."""
    from repro.core.nnd import NNDConfig, nnd_init, nnd_step
    rng = np.random.default_rng(2)
    X = jnp.asarray((rng.integers(-8, 9, (80, 10)) / 4.0)
                    .astype(np.float32))
    key = jax.random.PRNGKey(4)
    outs = {}
    for backend in ("xla", "interpret"):
        cfg = NNDConfig(k=6, c_fwd=3, c_rev=2, backend=backend)
        idx, d = nnd_init(key, X, cfg)
        for it in range(3):
            idx, d, _ = nnd_step(jax.random.fold_in(key, it), X, idx, d,
                                 cfg)
        outs[backend] = (np.asarray(idx), np.asarray(d))
    np.testing.assert_array_equal(outs["xla"][0], outs["interpret"][0])
    np.testing.assert_array_equal(outs["xla"][1], outs["interpret"][1])


# --------------------------------------------------------------------------
# Satellite: auto-rescale (ChunkMetrics consumer)


def test_fit_auto_rescale_triggers_and_matches_manual_loop():
    """auto_rescale with an always-firing threshold must equal a manual
    chunk loop that applies rescale_embedding after every chunk."""
    X, _ = blobs(n=120, dim=6, n_centers=3, seed=6)
    cfg = funcsne.FuncSNEConfig(n_points=120, dim_hd=6)
    hp = funcsne.default_hparams(120)
    st_f, _ = funcsne.fit(X, cfg=cfg, n_iter=30, hparams=hp,
                          schedule=lambda it, n, h: h, chunk_size=10,
                          auto_rescale=1e9)
    chunk = funcsne.make_chunked_step(cfg, 10)
    st = funcsne.init_state(jax.random.PRNGKey(0), jnp.asarray(X), cfg,
                            perplexity=hp.perplexity)
    for i in range(3):
        st, _, _ = chunk(st, jnp.asarray(X), hp)
        if i < 2:    # fit skips the rescale after the final chunk
            st = funcsne.rescale_embedding(st)
    _assert_states_equal(st_f, st)


def test_fit_auto_rescale_off_by_default_and_no_trigger():
    """auto_rescale=None (default) and a never-firing threshold are both
    bit-identical to the plain run."""
    X, _ = blobs(n=120, dim=6, n_centers=3, seed=6)
    cfg = funcsne.FuncSNEConfig(n_points=120, dim_hd=6)
    hp = funcsne.default_hparams(120)
    kw = dict(cfg=cfg, n_iter=20, hparams=hp,
              schedule=lambda it, n, h: h, chunk_size=10)
    st_plain, _ = funcsne.fit(X, **kw)
    st_zero, _ = funcsne.fit(X, auto_rescale=0.0, **kw)
    _assert_states_equal(st_plain, st_zero)


def test_fit_auto_rescale_host_loop_fallback():
    """A host-only schedule routes through _fit_host_loop; the same
    always-firing threshold rescales after every step (except the
    last), matching a manual per-step loop."""
    X, _ = blobs(n=80, dim=5, n_centers=2, seed=7)
    cfg = funcsne.FuncSNEConfig(n_points=80, dim_hd=5)
    hp = funcsne.default_hparams(80)

    def host_schedule(it, n_iter, h):     # Python control flow on it
        return h if int(it) < n_iter else h

    st_f, _ = funcsne.fit(X, cfg=cfg, n_iter=4, hparams=hp,
                          schedule=host_schedule, auto_rescale=1e9)
    st = funcsne.init_state(jax.random.PRNGKey(0), jnp.asarray(X), cfg,
                            perplexity=hp.perplexity)
    step = funcsne.make_step(cfg)
    for it in range(4):
        st = step(st, jnp.asarray(X), hp)
        if it < 3:
            st = funcsne.rescale_embedding(st)
    _assert_states_equal(st_f, st)
