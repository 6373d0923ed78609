"""Scan-chunked on-device driver: parity, composition, schedule, HLO.

Contract layers (tests/test_scatter_fused.py precedent for what XLA does
and does not guarantee bit-wise):

  * scan vs sequential -- a chunk of T steps runs the *same traced*
    ``funcsne_step`` as T ``make_step`` dispatches, but XLA compiles a
    while-loop body in a different codegen context than straight-line
    code (scatter-add application order, fused-reduction tails), so
    1-ulp differences per step are unavoidable and the KNN merge / gains
    sign logic eventually amplify them.  What must hold over a short
    horizon: every discrete field bit-equal (indices, flags, rng, the
    do_hd/do_sigma cond outcomes they encode) and every float field
    equal to fp32 tolerance.
  * within the chunked stack the driver IS bit-exact: chunk(T1) then
    chunk(T2) == chunk(T1+T2) including the snapshot ring and metrics,
    rerunning a chunk is deterministic, and ``fit`` is invariant to
    ``chunk_size`` bit-for-bit.
  * the device-side schedule evaluates bit-identically traced (from the
    carried ``st.step``) and on host (Python ``it``).
  * HLO: the compiled chunk contains exactly ONE top-level loop, its
    trip count is T, and no host transfer (infeed/outfeed/send/recv)
    exists anywhere in the module -- the per-step host round-trips this
    driver removes cannot silently come back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import funcsne
from repro.data.synthetic import blobs


def _setup(n=96, dim=9, seed=0, **cfg_kw):
    X, _ = blobs(n=n, dim=dim, n_centers=3, center_std=5.0, seed=seed)
    Xj = jnp.asarray(X)
    kw = dict(n_points=n, dim_hd=dim, backend="xla")
    kw.update(cfg_kw)
    cfg = funcsne.FuncSNEConfig(**kw)
    hp = funcsne.default_hparams(n)
    st0 = funcsne.init_state(jax.random.PRNGKey(seed), Xj, cfg)
    return cfg, st0, Xj, hp


def _copy(st):
    return jax.tree.map(lambda a: jnp.array(a, copy=True), st)


def _assert_states_match(a, b, *, bitwise):
    for name in funcsne.FuncSNEState._fields:
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        if bitwise or x.dtype.kind != "f":
            np.testing.assert_array_equal(x, y, err_msg=name)
        else:
            finite = np.isfinite(y)
            np.testing.assert_array_equal(finite, np.isfinite(x),
                                          err_msg=name)
            scale = float(np.max(np.abs(y[finite]))) + 1e-9
            np.testing.assert_allclose(x[finite], y[finite], rtol=1e-4,
                                       atol=1e-5 * scale, err_msg=name)


def test_chunked_matches_sequential_with_conds_and_ring():
    """scan-of-T == T sequential make_step calls: discrete state (incl.
    both do_hd/do_sigma cond branches -- sigma_refresh_every=2 fires the
    refresh several times in T=8) bit-equal, float state to fp32
    tolerance, snapshot ring slots == the host loop's device_get points."""
    cfg, st0, Xj, hp = _setup(sigma_refresh_every=2)
    T, every = 8, 3
    step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
    st_seq = _copy(st0)
    host_snaps = []
    for it in range(T):
        st_seq = step(st_seq, Xj, hp)
        if (it + 1) % every == 0:
            host_snaps.append(np.asarray(jax.device_get(st_seq.Y)))

    chunk = funcsne.make_chunked_step(cfg, T, snapshot_every=every)
    st_c, snaps, metrics = chunk(_copy(st0), Xj, hp)
    _assert_states_match(st_c, st_seq, bitwise=False)
    assert int(metrics.step) == T
    k = int(metrics.n_snapshots)
    assert k == len(host_snaps), (k, len(host_snaps))
    for i in range(k):
        scale = float(np.max(np.abs(host_snaps[i]))) + 1e-9
        np.testing.assert_allclose(np.asarray(snaps[i]), host_snaps[i],
                                   rtol=1e-4, atol=1e-5 * scale)


@pytest.mark.parametrize("c_hd_rev", [0, 2])
def test_chunked_kernels_match_reference_backend(c_hd_rev):
    """20 steps of one chunk through the Pallas kernels (interpret mode)
    against the same chunk on the pure-jnp references ('xla'): the
    generated candidates, merges, gate draws and reverse-edge cache agree
    exactly; floats to the kernels' fp32 summation order."""
    kw = dict(c_hd_rev=c_hd_rev, min_refresh_prob=0.5)
    cfg_k, st_k, Xj, hp = _setup(seed=5, backend="interpret", **kw)
    cfg_r, st_r, _, _ = _setup(seed=5, backend="xla", **kw)
    _assert_states_match(st_k, st_r, bitwise=False)
    st_k, _, m_k = funcsne.make_chunked_step(cfg_k, 20)(st_k, Xj, hp)
    st_r, _, m_r = funcsne.make_chunked_step(cfg_r, 20)(st_r, Xj, hp)
    assert int(m_k.hd_fires) == int(m_r.hd_fires) > 0
    assert int(m_k.sigma_fires) == int(m_r.sigma_fires)
    _assert_states_match(st_k, st_r, bitwise=False)


def test_chunk_composition_and_determinism_bit_exact():
    """chunk(6) then chunk(7) == chunk(13) bit-for-bit -- state, snapshot
    ring and metrics -- and rerunning is deterministic: chunk boundaries
    are a pure dispatch-granularity knob, never a numerics knob."""
    cfg, st0, Xj, hp = _setup()
    every = 4
    c6 = funcsne.make_chunked_step(cfg, 6, snapshot_every=every)
    c7 = funcsne.make_chunked_step(cfg, 7, snapshot_every=every)
    c13 = funcsne.make_chunked_step(cfg, 13, snapshot_every=every)

    s, sn_a, m_a = c6(_copy(st0), Xj, hp)
    s, sn_b, m_b = c7(s, Xj, hp)
    s13, sn_c, m_c = c13(_copy(st0), Xj, hp)
    _assert_states_match(s, s13, bitwise=True)
    ring_split = (list(np.asarray(sn_a[:int(m_a.n_snapshots)]))
                  + list(np.asarray(sn_b[:int(m_b.n_snapshots)])))
    ring_whole = list(np.asarray(sn_c[:int(m_c.n_snapshots)]))
    assert len(ring_split) == len(ring_whole) == 3
    for a, b in zip(ring_split, ring_whole):
        np.testing.assert_array_equal(a, b)
    assert int(m_b.step) == int(m_c.step) == 13

    s13_again, _, _ = c13(_copy(st0), Xj, hp)
    _assert_states_match(s13_again, s13, bitwise=True)


def test_fit_invariant_to_chunk_size_bit_exact():
    """fit(chunk_size=a) == fit(chunk_size=b) bit-for-bit, snapshots
    included (exercises the ragged final chunk: 29 % 8 != 0)."""
    X, _ = blobs(n=80, dim=7, n_centers=3, center_std=5.0, seed=1)
    kw = dict(n_iter=29, snapshot_every=10,
              cfg=funcsne.FuncSNEConfig(n_points=80, dim_hd=7,
                                        backend="xla"))
    st_a, snaps_a = funcsne.fit(X, chunk_size=8, **kw)
    st_b, snaps_b = funcsne.fit(X, chunk_size=29, **kw)
    _assert_states_match(st_a, st_b, bitwise=True)
    assert len(snaps_a) == len(snaps_b) == 2
    for a, b in zip(snaps_a, snaps_b):
        np.testing.assert_array_equal(a, b)


def test_device_schedule_bit_matches_host_schedule():
    """default_schedule(traced it) == default_schedule(python it): the
    on-device schedule uploads nothing and changes nothing."""
    hp = funcsne.default_hparams(500)
    n_iter = 750
    traced = jax.jit(lambda it: funcsne.default_schedule(it, n_iter, hp))
    for it in (0, 1, 187, 188, 300, 749):
        host = funcsne.default_schedule(it, n_iter, hp)
        dev = traced(jnp.int32(it))
        for f in funcsne.HParams._fields:
            np.testing.assert_array_equal(np.asarray(getattr(dev, f)),
                                          np.asarray(getattr(host, f)),
                                          err_msg=f"{f}@it={it}")


def test_chunked_with_schedule_matches_host_scheduled_loop():
    """Chunk with the traced schedule == host loop feeding per-step
    schedule(it) hparams into make_step (discrete bit-equal + fp32)."""
    cfg, st0, Xj, hp = _setup(seed=2)
    T, n_iter = 8, 40
    step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
    st_seq = _copy(st0)
    for it in range(T):
        st_seq = step(st_seq, Xj, funcsne.default_schedule(it, n_iter, hp))
    chunk = funcsne.make_chunked_step(cfg, T,
                                      schedule=funcsne.default_schedule,
                                      n_iter=n_iter)
    st_c, _, _ = chunk(_copy(st0), Xj, hp)
    _assert_states_match(st_c, st_seq, bitwise=False)


def test_schedule_requires_horizon():
    cfg, _, _, _ = _setup()
    with pytest.raises(ValueError):
        funcsne.make_chunked_step(cfg, 4,
                                  schedule=funcsne.default_schedule)


def test_chunked_hlo_one_scan_no_host_transfers():
    """The compiled chunk is ONE device program: exactly one top-level
    while whose trip count is T (the scan), and no infeed / outfeed /
    send / recv anywhere -- the per-step host dispatches and device_get
    round-trips the driver removes are structurally absent."""
    from repro.launch.hlo_analysis import analyze

    cfg, st0, Xj, hp = _setup()
    T = 17
    fn = funcsne._chunk_fn(cfg, T, schedule=funcsne.default_schedule,
                           n_iter=100, snapshot_every=5)
    text = jax.jit(fn).lower(st0, Xj, hp).compile().as_text()
    top = [l for l in analyze(text).loops if l["depth"] == 0]
    assert len(top) == 1, top
    assert top[0]["trip"] == T, top
    for marker in ("infeed", "outfeed", " send(", " recv("):
        assert not any(marker in line for line in text.splitlines()), marker


def test_chunk_metrics_sync_once_per_chunk():
    """ChunkMetrics carries everything a driver/GUI needs from one sync:
    global step, ring occupancy, EMA'd displacement, zhat, refresh EMA."""
    cfg, st0, Xj, hp = _setup()
    chunk = funcsne.make_chunked_step(cfg, 10, snapshot_every=4)
    st, snaps, m = chunk(_copy(st0), Xj, hp)
    assert int(m.step) == 10 and int(st.step) == 10
    assert int(m.n_snapshots) == 2 and snaps.shape[0] == 10 // 4 + 1
    assert np.isfinite(float(m.disp_ema)) and float(m.disp_ema) > 0.0
    np.testing.assert_array_equal(np.asarray(m.zhat), np.asarray(st.zhat))
    np.testing.assert_array_equal(np.asarray(m.ema_new_frac),
                                  np.asarray(st.ema_new_frac))

    st2, _, m2 = chunk(st, Xj, hp)
    assert int(m2.step) == 20


def test_fit_early_stop_halts_converged_run():
    """First ChunkMetrics consumer: with lr=0 the embedding cannot move
    (vel stays 0 -> disp_ema == 0), so fit(early_stop=...) must stop
    after the first chunk instead of burning the remaining dispatches."""
    X, _ = blobs(n=64, dim=6, n_centers=2, center_std=5.0, seed=4)
    cfg = funcsne.FuncSNEConfig(n_points=64, dim_hd=6, backend="xla")
    hp = funcsne.default_hparams(64)._replace(lr=jnp.float32(0.0))
    st, _ = funcsne.fit(X, cfg=cfg, n_iter=60, hparams=hp,
                        schedule=lambda it, n, h: h,   # keep lr pinned at 0
                        chunk_size=10, early_stop=1e-9)
    assert int(st.step) == 10, int(st.step)     # stopped after one chunk


def test_fit_early_stop_lets_moving_run_finish():
    """A run that is still moving must never trip an (absurdly small)
    threshold -- and early_stop=None must not change behaviour at all."""
    X, _ = blobs(n=64, dim=6, n_centers=2, center_std=5.0, seed=4)
    cfg = funcsne.FuncSNEConfig(n_points=64, dim_hd=6, backend="xla")
    st, _ = funcsne.fit(X, cfg=cfg, n_iter=40, chunk_size=10,
                        early_stop=1e-30)
    assert int(st.step) == 40, int(st.step)
    st_none, _ = funcsne.fit(X, cfg=cfg, n_iter=40, chunk_size=10)
    _assert_states_match(st_none, st, bitwise=True)


def test_fit_early_stop_host_loop_fallback():
    """Host-only schedules (Python control flow on ``it``) route through
    the per-step host loop; early_stop must work there too via the
    mirrored displacement EMA."""
    X, _ = blobs(n=48, dim=5, n_centers=2, center_std=5.0, seed=5)
    cfg = funcsne.FuncSNEConfig(n_points=48, dim_hd=5, backend="xla")
    hp = funcsne.default_hparams(48)._replace(lr=jnp.float32(0.0))

    def host_schedule(it, n, h):          # int(it): host loop required
        return h if int(it) >= 0 else h

    st, _ = funcsne.fit(X, cfg=cfg, n_iter=30, hparams=hp,
                        schedule=host_schedule, early_stop=1e-9)
    assert int(st.step) < 30, int(st.step)


@pytest.mark.slow
def test_chunked_trajectory_statistically_equivalent_long_horizon():
    """Over 60 steps the ulp-level codegen differences fork discrete KNN
    choices (see module docstring), so the long-horizon contract is the
    trajectory-equivalence one: same Z estimator, same embedding scale,
    finite everywhere."""
    cfg, st0, Xj, hp = _setup(n=128, seed=3)
    T = 60
    step = jax.jit(lambda s, x, h: funcsne.funcsne_step(cfg, s, x, h))
    st_seq = _copy(st0)
    for _ in range(T):
        st_seq = step(st_seq, Xj, hp)
    st_c, _, _ = funcsne.make_chunked_step(cfg, T)(_copy(st0), Xj, hp)
    assert bool(jnp.isfinite(st_c.Y).all())
    np.testing.assert_allclose(float(st_c.zhat), float(st_seq.zhat),
                               rtol=0.02)
    np.testing.assert_allclose(float(jnp.std(st_c.Y)),
                               float(jnp.std(st_seq.Y)), rtol=0.1)
