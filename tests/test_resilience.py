"""Resilient runtime: health telemetry, rollback/retry, checkpoint-resume,
sticky kernel fallback, fault injection, and input validation.

The recovery contracts pinned here are the ones ISSUE 6 promises:
  * injected NaN chunk -> telemetry trip -> rollback + backoff -> a fully
    finite final embedding (and a structured event log saying so);
  * persistent divergence -> bounded retries -> EmbeddingDiverged;
  * kill-and-resume through the Checkpointer is bit-deterministic;
  * injected Pallas launch failure -> sticky XLA demotion whose output is
    bit-identical to a run with the family demoted up front;
  * a clean run under a ResiliencePolicy is bit-identical to one without.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import funcsne
from repro.core.funcsne import FuncSNEConfig
from repro.core.resilience import EmbeddingDiverged, ResiliencePolicy
from repro.kernels import fallback
from repro.runtime import faults
from repro.runtime.faults import (FaultScript, KernelLaunchFault, NaNChunk,
                                  Preempted, Preemption)

N, DIM = 48, 5


def _data(n=N, dim=DIM, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(2, dim)) * 5.0
    X = centers[rng.integers(0, 2, size=n)] + rng.normal(size=(n, dim))
    return jnp.asarray(X, jnp.float32)


def _cfg(n=N, dim=DIM, **kw):
    kw.setdefault("backend", "xla")
    kw.setdefault("n_negatives", 4)
    kw.setdefault("k_hd", min(32, n // 2))
    kw.setdefault("k_ld", min(16, n // 4))
    return FuncSNEConfig(n_points=n, dim_hd=dim, **kw)


def _assert_state_equal(a, b):
    for name in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"state field {name!r} differs")


# ---------------------------------------------------------------------------
# On-device health telemetry (tentpole part 1)


def test_health_metrics_healthy_run():
    X, cfg = _data(), _cfg()
    hp = funcsne.default_hparams(N)
    st = funcsne.init_state(jax.random.PRNGKey(0), X, cfg)
    _, _, m = funcsne.make_chunked_step(cfg, 4)(st, X, hp)
    assert float(m.finite_frac) == 1.0
    assert float(m.y_max_abs) > 0.0
    assert int(m.bad_step) == -1


def test_health_metrics_flag_nan_and_first_bad_step():
    X, cfg = _data(), _cfg()
    hp = funcsne.default_hparams(N)
    st = funcsne.init_state(jax.random.PRNGKey(0), X, cfg)
    st = st._replace(Y=st.Y.at[0].set(jnp.nan))
    _, _, m = funcsne.make_chunked_step(cfg, 4)(st, X, hp)
    assert float(m.finite_frac) < 1.0
    assert int(m.bad_step) == 0          # poisoned before the first step
    # the max-|Y| probe must ignore the non-finite entries it reports
    assert np.isfinite(float(m.y_max_abs))


def test_policy_check_trips_and_fails_closed():
    p = ResiliencePolicy()
    healthy = {"finite_frac": 1.0, "y_max_abs": 3.0, "bad_step": -1}

    class M:
        def __init__(self, **kw):
            self.__dict__.update(kw)

    assert p.check(M(**healthy)) is None
    assert "non-finite" in p.check(M(**{**healthy, "finite_frac": 0.9,
                                        "bad_step": 7}))
    assert "explosion" in p.check(M(**{**healthy, "y_max_abs": 1e12}))
    # NaN telemetry must trip, not pass, every comparison
    assert p.check(M(**{**healthy, "finite_frac": float("nan")})) is not None
    assert p.check(M(**{**healthy, "y_max_abs": float("nan")})) is not None


# ---------------------------------------------------------------------------
# Rollback-and-retry (tentpole part 2)


def test_nan_fault_rollback_recovers():
    X, cfg = _data(), _cfg()
    policy = ResiliencePolicy(max_retries=2)
    with faults.active(FaultScript(NaNChunk(at_step=4))):
        st, _ = funcsne.fit(X, cfg=cfg, n_iter=12, chunk_size=4,
                            resilience=policy)
    assert bool(jnp.isfinite(st.Y).all())
    assert int(st.step) == 12
    rollbacks = [e for e in policy.events if e["kind"] == "rollback"]
    assert len(rollbacks) == 1
    assert rollbacks[0]["lr_scale"] == pytest.approx(0.5)
    assert "non-finite" in rollbacks[0]["reason"]


def test_persistent_divergence_exhausts_retries():
    X, cfg = _data(), _cfg()
    policy = ResiliencePolicy(max_retries=2)
    with faults.active(FaultScript(NaNChunk(at_step=0, once=False))):
        with pytest.raises(EmbeddingDiverged) as ei:
            funcsne.fit(X, cfg=cfg, n_iter=8, chunk_size=4,
                        resilience=policy)
    assert ei.value.retries == 2
    assert ei.value.step == 0
    kinds = [e["kind"] for e in policy.events]
    assert kinds.count("rollback") == 2 and "giving_up" in kinds


def test_clean_run_under_policy_is_bit_identical():
    X, cfg = _data(), _cfg()
    kw = dict(cfg=cfg, n_iter=8, chunk_size=4)
    st_plain, _ = funcsne.fit(X, **kw)
    policy = ResiliencePolicy()
    st_pol, _ = funcsne.fit(X, resilience=policy, **kw)
    _assert_state_equal(st_plain, st_pol)
    assert policy.events == []


# ---------------------------------------------------------------------------
# Checkpoint / preemption / resume (tentpole part 2, satellite d)


def test_preempt_and_resume_is_bit_identical(tmp_path):
    X, cfg = _data(), _cfg()
    kw = dict(cfg=cfg, n_iter=12, chunk_size=4)
    st_ref, _ = funcsne.fit(X, **kw)

    ckdir = str(tmp_path / "ck")
    with faults.active(FaultScript(Preemption(at_step=8))):
        with pytest.raises(Preempted) as ei:
            funcsne.fit(X, resilience=ResiliencePolicy(
                checkpoint_dir=ckdir), **kw)
    assert ei.value.step == 8
    st_res, _ = funcsne.fit(X, resume_from=ckdir, resilience=ResiliencePolicy(
        checkpoint_dir=ckdir), **kw)
    assert int(st_res.step) == 12
    _assert_state_equal(st_ref, st_res)


def test_resume_restores_backoff_scales(tmp_path):
    """lr/exaggeration backoff survives a kill: the scales ride in the
    checkpoint metadata, so a resumed run keeps the demoted trust."""
    X, cfg = _data(), _cfg()
    ckdir = str(tmp_path / "ck")
    policy = ResiliencePolicy(checkpoint_dir=ckdir, max_retries=2)
    with faults.active(FaultScript(NaNChunk(at_step=4),
                                   Preemption(at_step=8))):
        with pytest.raises(Preempted):
            funcsne.fit(X, cfg=cfg, n_iter=12, chunk_size=4,
                        resilience=policy)
    from repro.checkpoint import Checkpointer
    _, meta = Checkpointer(ckdir).restore(
        funcsne.init_state(jax.random.PRNGKey(0), X, cfg))
    assert meta["lr_scale"] == pytest.approx(0.5)


def test_fit_surfaces_async_checkpoint_failure(tmp_path, monkeypatch):
    X, cfg = _data(), _cfg()
    import repro.checkpoint.checkpointer as ckm

    def boom(*a, **kw):
        raise OSError("disk full (injected)")

    monkeypatch.setattr(ckm.np, "savez", boom)
    with pytest.raises(OSError, match="disk full"):
        funcsne.fit(X, cfg=cfg, n_iter=8, chunk_size=4,
                    resilience=ResiliencePolicy(
                        checkpoint_dir=str(tmp_path / "ck")))


# ---------------------------------------------------------------------------
# Sticky kernel fallback (tentpole part 3)


def test_guarded_passthrough_when_disabled():
    fallback.reset()

    def boom():
        raise RuntimeError("lowering failed")

    with pytest.raises(RuntimeError, match="lowering failed"):
        fallback.guarded("fam_test", boom, lambda: "ref")
    assert not fallback.is_demoted("fam_test")


def test_guarded_demotes_sticky_when_enabled():
    fallback.reset()
    calls = {"pallas": 0}

    def boom():
        calls["pallas"] += 1
        raise RuntimeError("lowering failed")

    try:
        with fallback.enabled():
            assert fallback.guarded("fam_test", boom, lambda: "ref") == "ref"
            assert fallback.guarded("fam_test", boom, lambda: "ref") == "ref"
        assert calls["pallas"] == 1          # sticky: no second launch try
        assert fallback.is_demoted("fam_test")
        (ev,) = fallback.events()
        assert ev["kind"] == "kernel_demoted" and ev["family"] == "fam_test"
    finally:
        fallback.reset()


def test_kernel_fault_demotes_and_matches_predemoted_run():
    n = 32
    X, cfg = _data(n=n), _cfg(n=n, backend="interpret")
    kw = dict(cfg=cfg, n_iter=4, chunk_size=2)
    try:
        fallback.reset()
        policy = ResiliencePolicy(sticky_fallback=True)
        with faults.active(FaultScript(KernelLaunchFault("knn_merge"))):
            st_fault, _ = funcsne.fit(X, resilience=policy, **kw)
        assert "knn_merge" in fallback.demotions()
        assert any(e["kind"] == "kernel_demoted" for e in policy.events)

        fallback.reset()
        with pytest.warns(RuntimeWarning):
            fallback.demote("knn_merge", "pre-demoted (parity reference)")
        with fallback.enabled():
            st_ref, _ = funcsne.fit(
                X, resilience=ResiliencePolicy(sticky_fallback=True), **kw)
        _assert_state_equal(st_fault, st_ref)
    finally:
        fallback.reset()


def test_fallback_registry_is_thread_safe_under_churn():
    """Two threads hammer the registry -- one demoting fresh families,
    one reading events()/demotions()/is_demoted() -- while the
    readers iterate snapshots.  Before the lock fix the readers copied
    the shared dict/list WHILE the writer appended (a genuine race:
    `dict(_DEMOTED)` and `list(_EVENTS[...])` iterate the live
    containers outside _LOCK); this drives it hard enough to blow up
    with RuntimeError('dictionary changed size during iteration') under
    the old code."""
    import threading
    import warnings as _w

    fallback.reset()
    stop = threading.Event()
    errors = []

    def writer():
        try:
            with _w.catch_warnings():
                _w.simplefilter("ignore", RuntimeWarning)
                i = 0
                while not stop.is_set():
                    fallback.demote(f"fam_{i}", "stress")
                    i += 1
        except Exception as e:          # pragma: no cover - fail surface
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                for ev in fallback.events():
                    assert "kind" in ev
                d = fallback.demotions()
                assert all(isinstance(r, str) for r in d.values())
                fallback.is_demoted("fam_0")
                fallback.n_events()
                fallback.is_enabled()
        except Exception as e:          # pragma: no cover - fail surface
            errors.append(e)

    threads = [threading.Thread(target=writer),
               threading.Thread(target=reader)]
    try:
        for t in threads:
            t.start()
        time.sleep(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join()
        fallback.reset()
    assert not errors, errors


# ---------------------------------------------------------------------------
# Threshold semantics parity (satellite c)


def test_early_stop_units_match_host_loop():
    """The chunked driver's normalised disp_ema at T=1 IS the host loop's
    per-step displacement: thresholds read in the same units on both."""
    X, cfg = _data(), _cfg()
    hp = funcsne.default_hparams(N)
    st = funcsne.init_state(jax.random.PRNGKey(0), X, cfg)
    st1, _, m = funcsne.make_chunked_step(cfg, 1)(st, X, hp)
    disp_norm = float(m.disp_ema) / (1.0 - funcsne._METRICS_DECAY)
    n_act = max(float(jnp.sum(st1.active.astype(jnp.float32))), 1.0)
    act_disp = float(jnp.sum(
        jnp.abs(st1.vel) * st1.active[:, None].astype(jnp.float32))) \
        / (n_act * cfg.dim_ld)
    assert disp_norm == pytest.approx(act_disp, rel=1e-5)


def test_early_stop_threshold_is_chunk_size_invariant():
    """A converged run (lr=0 -> zero displacement) stops at the first
    chunk whatever the chunk size; a live run never trips a 0 threshold."""
    X, cfg = _data(), _cfg()
    hp = funcsne.default_hparams(N)._replace(lr=jnp.float32(0.0))
    for cs in (2, 5):
        st, _ = funcsne.fit(X, cfg=cfg, n_iter=10, chunk_size=cs,
                            hparams=hp, early_stop=1e-9,
                            schedule=lambda it, n, h: h)
        assert int(st.step) == cs
    st, _ = funcsne.fit(X, cfg=cfg, n_iter=10, chunk_size=5,
                        early_stop=0.0)
    assert int(st.step) == 10


# ---------------------------------------------------------------------------
# Input validation (satellite b)


def test_validate_rejects_bad_ndim_dtype_shape():
    cfg = _cfg(n=16, dim=4)
    with pytest.raises(ValueError, match="2-D"):
        funcsne.validate_inputs(jnp.zeros((16,)), cfg)
    with pytest.raises(ValueError, match="real-numeric"):
        funcsne.validate_inputs(jnp.zeros((16, 4), jnp.complex64), cfg)
    with pytest.raises(ValueError, match="does not match cfg"):
        funcsne.validate_inputs(jnp.zeros((16, 5)), cfg)


def test_validate_rejects_k_ge_n():
    cfg = FuncSNEConfig(n_points=16, dim_hd=4, k_hd=16, backend="xla")
    with pytest.raises(ValueError, match="k_hd"):
        funcsne.validate_inputs(jnp.zeros((16, 4)), cfg)


def test_validate_counts_nonfinite_rows():
    cfg = _cfg(n=16, dim=4)
    X = np.zeros((16, 4), np.float32)
    X[3, 0] = np.nan
    X[7, 2] = np.inf
    with pytest.raises(ValueError, match="2 row"):
        funcsne.validate_inputs(jnp.asarray(X), cfg)
    with pytest.raises(ValueError, match="non-finite"):
        funcsne.fit(jnp.asarray(X), cfg=cfg, n_iter=1)
    # opt-out keeps the old behaviour for callers who sanitise upstream
    funcsne.validate_inputs(jnp.asarray(X), cfg, check_finite=False)


def test_init_state_validates_and_can_opt_out():
    cfg = _cfg(n=16, dim=4)
    with pytest.raises(ValueError, match="does not match cfg"):
        funcsne.init_state(jax.random.PRNGKey(0), jnp.zeros((16, 5)), cfg)
    st = funcsne.init_state(jax.random.PRNGKey(0),
                            jnp.zeros((16, 5))[:, :4], cfg, validate=False)
    assert st.Y.shape == (16, 2)


# ---------------------------------------------------------------------------
# fit() surface contracts


def test_host_only_schedule_rejects_resilience():
    X, cfg = _data(n=16, dim=4), _cfg(n=16, dim=4)

    def host_schedule(it, n_iter, hp):     # needs a Python int
        return hp if int(it) < 2 else hp._replace(lr=hp.lr * 0.5)

    with pytest.raises(ValueError, match="traceable schedule"):
        funcsne.fit(X, cfg=cfg, n_iter=4, schedule=host_schedule,
                    resilience=ResiliencePolicy())


def test_fit_state_continuation():
    X, cfg = _data(), _cfg()
    ident = lambda it, n, hp: hp
    kw = dict(cfg=cfg, chunk_size=4, schedule=ident)
    st_full, _ = funcsne.fit(X, n_iter=8, **kw)
    st_half, _ = funcsne.fit(X, n_iter=4, **kw)
    st_cont, _ = funcsne.fit(X, n_iter=4, state=st_half, **kw)
    _assert_state_equal(st_full, st_cont)


# ---------------------------------------------------------------------------
# Chunk-boundary state auditor in the fit loop (ISSUE 9)


def test_audit_trips_rollback_in_fit_and_control_misses():
    """Finite index corruption is invisible to the NaN probes; with
    audit_every it trips the EXISTING rollback path, without it the
    damage survives to the final state (the positive control)."""
    from repro.runtime.faults import IndexCorruption

    X, cfg = _data(), _cfg()
    kw = dict(cfg=cfg, n_iter=16, chunk_size=4)

    policy = ResiliencePolicy(max_retries=2, audit_every=1)
    with faults.active(FaultScript(IndexCorruption(at_step=8))):
        st, _ = funcsne.fit(X, resilience=policy, **kw)
    kinds = [e["kind"] for e in policy.events]
    assert "audit_violation" in kinds and "rollback" in kinds, kinds
    assert int(st.step) == 16
    res = jax.device_get(funcsne.audit_state(st, cfg, X))
    assert policy.audit_check(res) is None

    ctrl = ResiliencePolicy(max_retries=2, audit_every=0)
    with faults.active(FaultScript(IndexCorruption(at_step=8))):
        st0, _ = funcsne.fit(X, resilience=ctrl, **kw)
    assert "rollback" not in [e["kind"] for e in ctrl.events]
    res0 = jax.device_get(funcsne.audit_state(st0, cfg, X))
    assert ctrl.audit_check(res0) is not None


def test_clean_run_with_audit_is_bit_identical():
    """Auditing is read-only: a clean run with audit_every=1 matches the
    no-policy run bit for bit (same guarantee as the health probes)."""
    X, cfg = _data(), _cfg()
    kw = dict(cfg=cfg, n_iter=8, chunk_size=4)
    st_plain, _ = funcsne.fit(X, **kw)
    policy = ResiliencePolicy(audit_every=1)
    st_aud, _ = funcsne.fit(X, resilience=policy, **kw)
    _assert_state_equal(st_plain, st_aud)
    assert not [e for e in policy.events
                if e["kind"] in ("rollback", "audit_violation")]


# ---------------------------------------------------------------------------
# Straggler-alarm escalation: early checkpoint (ISSUE 9 satellite)


def test_straggler_alarm_triggers_early_checkpoint(tmp_path):
    """With the checkpoint cadence effectively off, every alarm must
    still commit the just-advanced boundary (straggler.py's contract:
    a kill after an alarm loses at most one chunk)."""
    from repro.checkpoint import Checkpointer

    X, cfg = _data(), _cfg()
    # hang_timeout=0 makes every chunk dispatch an alarm; cadence 1000
    # means every committed boundary below is escalation-only
    policy = ResiliencePolicy(checkpoint_dir=str(tmp_path),
                              checkpoint_every=1000,
                              hang_timeout=0.0, straggler_warmup=0)
    st, _ = funcsne.fit(X, cfg=cfg, n_iter=16, chunk_size=4,
                        resilience=policy)
    kinds = [e["kind"] for e in policy.events]
    assert kinds.count("early_checkpoint") == 4, kinds
    ck = Checkpointer(tmp_path)
    assert ck.latest_step() == 16
    # the escalated boundary is a real, verified, resumable checkpoint
    st_res, _ = funcsne.fit(X, cfg=cfg, n_iter=16, chunk_size=4,
                            resilience=ResiliencePolicy(),
                            resume_from=str(tmp_path))
    _assert_state_equal(st, st_res)
