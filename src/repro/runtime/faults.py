"""Deterministic fault injection for the resilient embedding runtime.

Every recovery path in ``funcsne.fit``'s resilience layer is exercised by
*scripted* faults rather than by hoping a real TPU misbehaves on cue:

  :class:`NaNChunk`          corrupts the state handed to one chunk
                             dispatch (the rollback copy stays clean), so
                             the in-scan health telemetry sees a chunk
                             whose optimisation blew up mid-flight;
  :class:`KernelLaunchFault` raises inside the guarded Pallas launch of
                             one kernel family (``repro.kernels.fallback``
                             consults this module right before calling the
                             Pallas builder), driving the sticky
                             demote-to-XLA path;
  :class:`Preemption`        raises :class:`Preempted` at a chunk
                             boundary -- the SIGTERM-between-dispatches
                             case; a subsequent ``fit(resume_from=dir)``
                             must reproduce the uninterrupted run
                             bit-for-bit.
  :class:`HostLoss`          raises :class:`HostLost` at a chunk
                             boundary -- one simulated host (its block
                             of devices) drops out of the pod; the
                             elastic coordinator
                             (``repro.runtime.coordinator.fit_elastic``)
                             quiesces the survivors, re-forms the mesh
                             over the remaining devices and resumes
                             from the last committed chunk boundary.
  :class:`ProcessKill`       SIGKILLs the worker process itself at a
                             chunk boundary -- the REAL death
                             :class:`HostLoss` only simulates; nothing
                             in-process survives it, so the test
                             payload is the supervisor/worker control
                             plane (``repro.runtime.control``): the
                             supervisor must detect the lost heartbeat,
                             kill the generation, re-form the pod over
                             the survivors and relaunch from the last
                             committed generation-tagged checkpoint.
  :class:`CorruptShard`      damages the newest COMMITTED checkpoint on
                             disk (truncate / bit-flip / delete one
                             shard file) at a chunk boundary -- the
                             torn-write / bad-disk case; the verified
                             restore chain must detect it and fall back
                             to the previous intact boundary.
  :class:`IndexCorruption`   poisons a state index table (``hd_idx`` /
                             ``rev_idx``) with out-of-range but
                             perfectly FINITE values -- corruption the
                             NaN health probes cannot see; only the
                             chunk-boundary state auditor
                             (``funcsne.audit_state`` via
                             ``ResiliencePolicy(audit_every=)``) trips.

Faults are one-shot by default (``fired`` latches), so a rolled-back
retry of the same steps does not re-trip: the script models a transient
fault, which is exactly what rollback-and-retry is for.  Persistent
faults (``once=False``) model real divergence and exhaust the retry
budget instead.

Usage::

    script = FaultScript(NaNChunk(at_step=40))
    with faults.active(script):
        st, _ = funcsne.fit(X, resilience=ResiliencePolicy(), ...)

``python -m repro.runtime.faults --smoke`` runs every recovery scenario
end-to-end on tiny data with the kernels in interpret mode -- the CI
gate that keeps every path green in minutes.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import List, Optional

_SENTINEL_NOT_ACTIVE = None


class Preempted(RuntimeError):
    """Simulated preemption: the run was killed between chunk dispatches."""

    def __init__(self, step: int):
        super().__init__(f"simulated preemption at step {step}")
        self.step = step


class InjectedKernelFault(RuntimeError):
    """Raised in place of a Pallas launch by :class:`KernelLaunchFault`."""


class HostLost(RuntimeError):
    """Simulated host loss: one host's devices dropped out of the mesh."""

    def __init__(self, step: int, host: int):
        super().__init__(f"simulated loss of host {host} at step {step}")
        self.step = step
        self.host = host


def _poison_one_replica(arr, shard: int, rows: int, value=None):
    """Rebuild a *replicated* mesh array with poison written into ONE
    device's buffer only -- rows ``[shard*n_loc, shard*n_loc+rows)`` of
    device ``shard``'s replica (its own row slice in the phase
    decomposition).  ``value=None`` writes NaN (float corruption);
    an int ``value`` poisons integer index tables.  This models a
    device-local corruption (bad HBM row, miscompiled kernel on one
    core): the replication invariant is broken but every collective
    still runs, which is exactly the fault a shard-blind health probe
    commits silently."""
    import numpy as np

    import jax

    sharding = arr.sharding
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or mesh.devices.size < 2:
        raise ValueError(
            "per-shard poisoning needs a state replicated over a >=2 "
            "device mesh (NamedSharding); got " + repr(sharding))
    devs = list(mesh.devices.flat)
    if not (0 <= shard < len(devs)):
        raise ValueError(f"shard {shard} out of range for {len(devs)} "
                         f"devices")
    host = np.asarray(arr)
    n_loc = max(1, host.shape[0] // len(devs))
    lo = shard * n_loc
    bad = host.copy()
    bad[lo:lo + min(rows, n_loc)] = np.nan if value is None else value
    bufs = [jax.device_put(bad if i == shard else host, d)
            for i, d in enumerate(devs)]
    return jax.make_array_from_single_device_arrays(
        arr.shape, sharding, bufs)


@dataclasses.dataclass
class NaNChunk:
    """Poison the state entering the first chunk whose start step is
    ``>= at_step``: the first ``rows`` rows of ``field`` become NaN, as
    if the optimiser diverged mid-chunk.  The caller's rollback copy
    (taken before injection) stays clean, so rollback + retry recovers.

    ``shard=None`` (default) poisons the logical state -- every replica
    sees it.  ``shard=s`` poisons ONLY device ``s``'s replica (rows of
    that shard's own slice), breaking the replication invariant the way
    a device-local fault does; combined with ``field='vel'`` the NaN
    reaches that device's copy of ``Y`` through the purely local
    momentum update -- no collective touches it within the step -- so a
    shard-blind probe that reads shard 0's telemetry misses it entirely
    while the mesh-reduced probe trips.  (Poisoning ``Y`` directly
    propagates to every replica through the force psum within one step,
    which is why the shard-confined scenario pairs with ``vel``.)"""
    at_step: int
    rows: int = 8
    once: bool = True
    fired: bool = False
    shard: Optional[int] = None
    field: str = "Y"

    def apply(self, st, it: int):
        if (self.fired and self.once) or it < self.at_step:
            return st
        self.fired = True
        arr = getattr(st, self.field)
        if self.shard is None:
            import jax.numpy as jnp
            rows = min(self.rows, arr.shape[0])
            arr = arr.at[:rows].set(jnp.nan)
        else:
            arr = _poison_one_replica(arr, self.shard, self.rows)
        return st._replace(**{self.field: arr})


@dataclasses.dataclass
class IndexCorruption:
    """Poison an index table of the state entering the first chunk whose
    start step is ``>= at_step``: the first ``rows`` rows of ``field``
    (``hd_idx`` / ``ld_idx`` / ``rev_idx``) are overwritten with an
    out-of-range but perfectly FINITE value (``n + 12345`` -- in-range
    for int32, below the SENTINEL).  The finite-fraction / max-|Y|
    health probes cannot see it (nothing is NaN and the embedding drifts
    only slowly), which is exactly the corruption class
    ``funcsne.audit_state`` exists for.  ``shard=s`` confines the poison
    to device ``s``'s replica on a mesh (the audit reductions AllReduce,
    so the mesh-global audit still trips)."""
    at_step: int
    field: str = "hd_idx"
    rows: int = 8
    once: bool = True
    fired: bool = False
    shard: Optional[int] = None

    def apply(self, st, it: int):
        if (self.fired and self.once) or it < self.at_step:
            return st
        self.fired = True
        arr = getattr(st, self.field)
        bad_val = st.active.shape[0] + 12345
        if self.shard is None:
            rows = min(self.rows, arr.shape[0])
            arr = arr.at[:rows].set(bad_val)
        else:
            arr = _poison_one_replica(arr, self.shard, self.rows,
                                      value=bad_val)
        return st._replace(**{self.field: arr})


@dataclasses.dataclass
class CorruptShard:
    """Damage the NEWEST committed checkpoint on disk at the first chunk
    boundary ``>= at_step`` -- after the in-flight write lands, so the
    damage hits a fully committed step the way a torn write, a flipped
    bit in cold storage or a lost object does.  ``shard`` indexes the
    sorted ``shard*-of-*.npz`` set (default -1: the last shard;
    single-host checkpoints damage ``arrays.npz``).  ``damaged`` records
    the file actually hit, for assertions."""
    at_step: int
    mode: str = "bitflip"       # "truncate" | "bitflip" | "delete"
    shard: int = -1
    once: bool = True
    fired: bool = False
    damaged: Optional[str] = None

    def check(self, it: int, ck):
        if ck is None or (self.fired and self.once) or it < self.at_step:
            return
        ck.wait()       # the in-flight write must COMMIT before damage:
        #                 this models corruption of a good checkpoint,
        #                 not a crash mid-write (the tmp-dir rename
        #                 already covers that)
        step = ck.latest_step()
        if step is None:
            return
        self.fired = True
        d = ck.dir / f"step_{step:010d}"
        files = sorted(d.glob("shard*-of-*.npz")) or [d / "arrays.npz"]
        target = files[self.shard % len(files)]
        if self.mode == "delete":
            target.unlink()
        elif self.mode == "truncate":
            blob = target.read_bytes()
            target.write_bytes(blob[:max(1, len(blob) // 2)])
        elif self.mode == "bitflip":
            blob = bytearray(target.read_bytes())
            blob[len(blob) // 2] ^= 0x01
            target.write_bytes(bytes(blob))
        else:
            raise ValueError(f"unknown CorruptShard mode {self.mode!r}")
        self.damaged = str(target)


@dataclasses.dataclass
class KernelLaunchFault:
    """Raise :class:`InjectedKernelFault` in place of the ``at_launch``-th
    guarded Pallas launch of ``family`` (see ``repro.kernels.fallback``)."""
    family: str
    at_launch: int = 0
    once: bool = True
    fired: bool = False
    _count: int = 0

    def check(self, family: str):
        if family != self.family or (self.fired and self.once):
            return
        launch, self._count = self._count, self._count + 1
        if launch >= self.at_launch:
            self.fired = True
            raise InjectedKernelFault(
                f"injected launch failure: {self.family} "
                f"(launch {launch})")


@dataclasses.dataclass
class Preemption:
    """Raise :class:`Preempted` at the first chunk boundary ``>= at_step``
    -- AFTER the state advanced past the chunk, like a kill signal landing
    between dispatches."""
    at_step: int
    once: bool = True
    fired: bool = False

    def check(self, it: int):
        if (self.fired and self.once) or it < self.at_step:
            return
        self.fired = True
        raise Preempted(it)


@dataclasses.dataclass
class ProcessKill:
    """SIGKILL THIS process at the first chunk boundary ``>= at_chunk``,
    iff it is running as pod ``pod`` -- the real-death analogue of
    :class:`HostLoss`.  ``os.kill(getpid(), SIGKILL)`` is deliberate:
    no atexit, no flushes, no JAX teardown, exactly what ``kill -9`` on
    a worker looks like.  The in-process runtime cannot survive this by
    construction; recovery is the supervisor's job
    (``repro.runtime.control``: kill the generation, re-form the pod
    over the survivors, relaunch from the last committed boundary).
    Checked from the worker's ``on_boundary`` hook via
    :func:`maybe_process_kill` -- after the boundary's checkpoint save
    has been *dispatched*, so the kill races a possibly-in-flight write
    the way a real signal does (generation-tagged shards make the torn
    leftovers harmless)."""
    at_chunk: int
    pod: int = 1
    once: bool = True
    fired: bool = False

    def check(self, it: int, pod: int):
        if pod != self.pod or (self.fired and self.once) \
                or it < self.at_chunk:
            return
        self.fired = True
        import os
        import signal
        os.kill(os.getpid(), signal.SIGKILL)


@dataclasses.dataclass
class HostLoss:
    """Raise :class:`HostLost` at the first chunk boundary ``>= at_step``:
    simulated death of host ``host`` (its whole device block).  Unlike
    :class:`Preemption` the process survives -- the elastic coordinator
    catches it, drops the host's devices, remeshes and resumes from the
    last committed checkpoint on the shrunken mesh."""
    at_step: int
    host: int = 1
    once: bool = True
    fired: bool = False

    def check(self, it: int):
        if (self.fired and self.once) or it < self.at_step:
            return
        self.fired = True
        raise HostLost(it, self.host)


class FaultScript:
    """An ordered bag of fault objects consulted by the runtime hooks."""

    def __init__(self, *faults):
        self.faults: List = list(faults)

    def corrupt_state(self, st, it: int):
        for f in self.faults:
            if isinstance(f, (NaNChunk, IndexCorruption)):
                st = f.apply(st, it)
        return st

    def maybe_preempt(self, it: int):
        for f in self.faults:
            if isinstance(f, Preemption):
                f.check(it)

    def maybe_corrupt_checkpoint(self, it: int, ck):
        for f in self.faults:
            if isinstance(f, CorruptShard):
                f.check(it, ck)

    def maybe_host_loss(self, it: int):
        for f in self.faults:
            if isinstance(f, HostLoss):
                f.check(it)

    def maybe_process_kill(self, it: int, pod: int):
        for f in self.faults:
            if isinstance(f, ProcessKill):
                f.check(it, pod)

    def check_kernel(self, family: str):
        for f in self.faults:
            if isinstance(f, KernelLaunchFault):
                f.check(family)


_ACTIVE: Optional[FaultScript] = _SENTINEL_NOT_ACTIVE


@contextlib.contextmanager
def active(script: FaultScript):
    """Install ``script`` as the process-wide fault source."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, script
    try:
        yield script
    finally:
        _ACTIVE = prev


def current() -> Optional[FaultScript]:
    return _ACTIVE


# -- hooks the runtime calls (all no-ops when no script is active) ---------


def corrupt_state(st, it: int):
    return _ACTIVE.corrupt_state(st, it) if _ACTIVE is not None else st


def maybe_preempt(it: int):
    if _ACTIVE is not None:
        _ACTIVE.maybe_preempt(it)


def maybe_corrupt_checkpoint(it: int, ck):
    if _ACTIVE is not None and ck is not None:
        _ACTIVE.maybe_corrupt_checkpoint(it, ck)


def maybe_host_loss(it: int):
    if _ACTIVE is not None:
        _ACTIVE.maybe_host_loss(it)


def maybe_process_kill(it: int, pod: int):
    if _ACTIVE is not None:
        _ACTIVE.maybe_process_kill(it, pod)


def check_kernel(family: str):
    if _ACTIVE is not None:
        _ACTIVE.check_kernel(family)


# --------------------------------------------------------------------------
# Smoke scenarios: the CI gate (`python -m repro.runtime.faults --smoke`)


def _smoke_setup(n=64, dim=6, backend="interpret", seed=0):
    import jax.numpy as jnp

    from repro.core import funcsne
    from repro.data.synthetic import blobs

    X, _ = blobs(n=n, dim=dim, n_centers=2, center_std=5.0, seed=seed)
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=dim, backend=backend,
                                n_negatives=4)
    return jnp.asarray(X), cfg


def scenario_nan_rollback(backend="interpret") -> dict:
    """Injected NaN chunk -> telemetry trip -> rollback + backoff ->
    finite final embedding."""
    import jax.numpy as jnp

    from repro.core import funcsne
    from repro.core.resilience import ResiliencePolicy

    X, cfg = _smoke_setup(backend=backend)
    policy = ResiliencePolicy(max_retries=2)
    with active(FaultScript(NaNChunk(at_step=8))):
        st, _ = funcsne.fit(X, cfg=cfg, n_iter=16, chunk_size=4,
                            resilience=policy)
    assert bool(jnp.isfinite(st.Y).all()), "embedding not finite"
    kinds = [e["kind"] for e in policy.events]
    assert "rollback" in kinds, kinds
    assert int(st.step) == 16, int(st.step)
    return {"events": len(policy.events), "retries": kinds.count("rollback")}


def scenario_kernel_fallback(backend="interpret") -> dict:
    """Injected Pallas launch failure -> sticky XLA demotion -> run
    completes, bit-identical to a run with the family pre-demoted."""
    import numpy as np

    from repro.core import funcsne
    from repro.core.resilience import ResiliencePolicy
    from repro.kernels import fallback

    X, cfg = _smoke_setup(backend=backend)

    fallback.reset()
    with active(FaultScript(KernelLaunchFault("knn_merge"))):
        policy = ResiliencePolicy(sticky_fallback=True)
        st_fault, _ = funcsne.fit(X, cfg=cfg, n_iter=8, chunk_size=4,
                                  resilience=policy)
    assert "knn_merge" in fallback.demotions(), fallback.demotions()

    fallback.reset()
    fallback.demote("knn_merge", "pre-demoted (smoke parity reference)")
    with fallback.enabled():
        st_ref, _ = funcsne.fit(X, cfg=cfg, n_iter=8, chunk_size=4,
                                resilience=ResiliencePolicy(
                                    sticky_fallback=True))
    fallback.reset()
    np.testing.assert_array_equal(np.asarray(st_fault.Y),
                                  np.asarray(st_ref.Y))
    return {"demoted": ["knn_merge"]}


def scenario_preempt_resume(backend="interpret", tmpdir=None) -> dict:
    """Kill between chunks, restore from disk: resumed run bit-identical
    to the uninterrupted one."""
    import tempfile

    import numpy as np

    from repro.core import funcsne
    from repro.core.resilience import ResiliencePolicy

    X, cfg = _smoke_setup(backend=backend)
    if tmpdir is None:
        tmpdir = tempfile.mkdtemp(prefix="funcsne-faults-")
    kw = dict(cfg=cfg, n_iter=16, chunk_size=4)

    st_ref, _ = funcsne.fit(X, resilience=ResiliencePolicy(), **kw)

    policy = ResiliencePolicy(checkpoint_dir=tmpdir, checkpoint_every=1)
    try:
        with active(FaultScript(Preemption(at_step=8))):
            funcsne.fit(X, resilience=policy, **kw)
        raise AssertionError("preemption did not fire")
    except Preempted as e:
        killed_at = e.step
    st_res, _ = funcsne.fit(X, resilience=ResiliencePolicy(
        checkpoint_dir=tmpdir, checkpoint_every=1),
        resume_from=tmpdir, **kw)
    np.testing.assert_array_equal(np.asarray(st_res.Y),
                                  np.asarray(st_ref.Y))
    assert int(st_res.step) == 16
    return {"killed_at": killed_at}


def scenario_host_loss(backend="interpret", tmpdir=None) -> dict:
    """One simulated host's device block dies mid-run; the elastic
    coordinator quiesces, remeshes over the survivors and resumes from
    the last committed chunk boundary.  The run finishes every
    iteration on the shrunken mesh with an embedding whose spread
    matches the uninterrupted run (exact bitwise parity is not expected:
    the smaller mesh regroups the force psum)."""
    import jax

    if jax.device_count() < 2:
        # plain `--smoke` runs single-device; the dedicated CI gate sets
        # XLA_FLAGS=--xla_force_host_platform_device_count=8
        return {"skipped": f"needs >=2 devices, have {jax.device_count()}"}

    import tempfile

    import numpy as np

    from repro.core.resilience import ResiliencePolicy
    from repro.runtime.coordinator import fit_elastic

    X, cfg = _smoke_setup(backend=backend)
    kw = dict(cfg=cfg, n_iter=16, chunk_size=4, n_hosts=2)

    st_ref = fit_elastic(X, resilience=ResiliencePolicy(), **kw)

    if tmpdir is None:
        tmpdir = tempfile.mkdtemp(prefix="funcsne-hostloss-")
    policy = ResiliencePolicy(checkpoint_dir=tmpdir, checkpoint_every=1)
    with active(FaultScript(HostLoss(at_step=8, host=1))):
        st = fit_elastic(X, resilience=policy, **kw)

    assert int(st.step) == 16, int(st.step)
    Y = np.asarray(st.Y)
    assert bool(np.isfinite(Y).all()), "embedding not finite after remesh"
    kinds = [e["kind"] for e in policy.events]
    assert "host_lost" in kinds and "remesh" in kinds, kinds
    # quality proxy robust at smoke scale: the layout kept optimising
    # after the remesh instead of resetting/ freezing -- its spread is
    # within 2x of the uninterrupted run's
    ref = float(np.std(np.asarray(st_ref.Y)))
    got = float(np.std(Y))
    assert 0.5 * ref <= got <= 2.0 * ref, (ref, got)
    return {"host_lost": 1, "resumed_at": next(
        e["step"] for e in policy.events if e["kind"] == "remesh"),
        "spread_ratio": round(got / max(ref, 1e-9), 3)}


def scenario_corrupt_restore(backend="interpret", tmpdir=None) -> dict:
    """Damage the newest COMMITTED checkpoint (truncate / bit-flip /
    delete a shard file) right after it lands, then kill the run: resume
    detects the damage at restore time, falls back to the previous
    verified boundary with a ``checkpoint_fallback`` event, and still
    reproduces the uninterrupted run bit-for-bit (chunk boundaries are
    bit-neutral, so replaying from one further back is exact).  With >=2
    devices the same story runs through ``fit_elastic``'s host-loss
    path: the lost host's per-shard checkpoint file is deleted and the
    remesh resumes from the previous verified boundary."""
    import shutil
    import tempfile

    import numpy as np

    from repro.core import funcsne
    from repro.core.resilience import ResiliencePolicy

    X, cfg = _smoke_setup(backend=backend)
    kw = dict(cfg=cfg, n_iter=16, chunk_size=4)
    st_ref, _ = funcsne.fit(X, resilience=ResiliencePolicy(), **kw)

    out = {}
    for mode in ("truncate", "bitflip", "delete"):
        tdir = tempfile.mkdtemp(prefix=f"funcsne-corrupt-{mode}-")
        fault = CorruptShard(at_step=8, mode=mode)
        try:
            with active(FaultScript(fault, Preemption(at_step=8))):
                funcsne.fit(X, resilience=ResiliencePolicy(
                    checkpoint_dir=tdir, checkpoint_every=1), **kw)
            raise AssertionError("preemption did not fire")
        except Preempted:
            pass
        assert fault.damaged is not None, "CorruptShard never fired"
        policy = ResiliencePolicy(checkpoint_dir=tdir, checkpoint_every=1)
        st_res, _ = funcsne.fit(X, resilience=policy, resume_from=tdir,
                                **kw)
        fbs = [e for e in policy.events
               if e["kind"] == "checkpoint_fallback"]
        assert fbs and fbs[0]["step"] == 8, policy.events
        np.testing.assert_array_equal(np.asarray(st_res.Y),
                                      np.asarray(st_ref.Y))
        assert int(st_res.step) == 16
        out[mode] = {"fell_back_from": fbs[0]["step"]}
        shutil.rmtree(tdir, ignore_errors=True)

    import jax
    if jax.device_count() < 2:
        out["elastic"] = {"skipped":
                          f"needs >=2 devices, have {jax.device_count()}"}
        return out

    from repro.runtime.coordinator import fit_elastic

    ekw = dict(cfg=cfg, n_iter=16, chunk_size=4, n_hosts=2)
    st_eref = fit_elastic(X, resilience=ResiliencePolicy(), **ekw)
    tdir = tempfile.mkdtemp(prefix="funcsne-corrupt-elastic-")
    policy = ResiliencePolicy(checkpoint_dir=tdir, checkpoint_every=1)
    with active(FaultScript(CorruptShard(at_step=8, mode="delete"),
                            HostLoss(at_step=8, host=1))):
        st = fit_elastic(X, resilience=policy, **ekw)
    kinds = [e["kind"] for e in policy.events]
    assert "host_lost" in kinds and "remesh" in kinds, kinds
    fbs = [e for e in policy.events if e["kind"] == "checkpoint_fallback"]
    assert fbs and fbs[0]["step"] == 8, policy.events
    assert int(st.step) == 16, int(st.step)
    Y = np.asarray(st.Y)
    assert bool(np.isfinite(Y).all()), "embedding not finite"
    ref = float(np.std(np.asarray(st_eref.Y)))
    got = float(np.std(Y))
    assert 0.5 * ref <= got <= 2.0 * ref, (ref, got)
    shutil.rmtree(tdir, ignore_errors=True)
    out["elastic"] = {"fell_back_from": fbs[0]["step"],
                      "spread_ratio": round(got / max(ref, 1e-9), 3)}
    return out


def scenario_index_audit(backend="interpret") -> dict:
    """Poisoned ``hd_idx`` (out-of-range but FINITE values, invisible to
    the NaN probes) trips the chunk-boundary auditor and the existing
    rollback path, and the run finishes with a clean state.  Positive
    control: with ``audit_every=0`` the same fault sails through -- no
    rollback, and the final state fails an offline audit."""
    import jax

    from repro.core import funcsne
    from repro.core.resilience import ResiliencePolicy

    X, cfg = _smoke_setup(backend=backend)
    kw = dict(cfg=cfg, n_iter=16, chunk_size=4)

    policy = ResiliencePolicy(max_retries=2, audit_every=1)
    with active(FaultScript(IndexCorruption(at_step=8, field="hd_idx"))):
        st, _ = funcsne.fit(X, resilience=policy, **kw)
    kinds = [e["kind"] for e in policy.events]
    assert "audit_violation" in kinds and "rollback" in kinds, kinds
    assert int(st.step) == 16, int(st.step)
    final = policy.audit_check(
        jax.device_get(funcsne.audit_state(st, cfg, X)))
    assert final is None, f"final state dirty after rollback: {final}"
    viol = next(e for e in policy.events
                if e["kind"] == "audit_violation")

    # positive control: auditor off -> nothing notices, the corruption
    # survives to the end of the run (this is the blind spot the
    # auditor closes; a regression that quietly stops auditing fails
    # the first assert above, a regression that trips on CLEAN states
    # fails this one)
    ctrl = ResiliencePolicy(max_retries=2, audit_every=0)
    with active(FaultScript(IndexCorruption(at_step=8, field="hd_idx"))):
        st0, _ = funcsne.fit(X, resilience=ctrl, **kw)
    kinds0 = [e["kind"] for e in ctrl.events]
    assert "rollback" not in kinds0 and "audit_violation" not in kinds0, \
        kinds0
    missed = ctrl.audit_check(
        jax.device_get(funcsne.audit_state(st0, cfg, X)))
    assert missed is not None, \
        "control run: the corruption disappeared without an audit"
    return {"tripped": viol["reason"][:48],
            "control_missed": missed[:48]}


def scenario_process_kill(backend="interpret", tmpdir=None) -> dict:
    """THE real-death gate: a 2-process CPU pod (gloo collectives under
    ``jax.distributed``), one worker SIGKILLs itself mid-run, and the
    supervisor must finish the embedding anyway -- heartbeat-loss
    detection, generation kill, remesh over the survivor, resume from
    the last committed generation-tagged boundary.  Asserts the
    structured event trail, the final committed step, no orphaned
    worker processes and no stale-generation shards on disk."""
    import os

    if os.environ.get("FUNCSNE_NO_MULTIPROCESS") == "1":
        return {"skipped": "FUNCSNE_NO_MULTIPROCESS=1"}

    from repro.runtime import control

    if not control.gloo_available():
        return {"skipped": "no gloo CPU collectives in this jaxlib"}

    import shutil
    import tempfile

    if tmpdir is None:
        tmpdir = tempfile.mkdtemp(prefix="funcsne-prockill-")
    n_iter, chunk = 16, 4
    sup = control.Supervisor(
        tmpdir, n_pods=2, n_iter=n_iter, chunk_size=chunk, n=64, dim=6,
        backend=backend, kill_pod=1, kill_at_chunk=8,
        heartbeat_timeout=20.0, total_timeout=480.0,
        # the drill tests the control plane, not the chip: workers run
        # on the CPU (this process may hold the accelerator), one local
        # device each even under --xla_force_host_platform_device_count
        extra_env={"XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"})
    report = sup.run()

    # the survivor finished every iteration and committed the boundary
    assert report["result"]["step"] == n_iter, report["result"]
    assert report["result"]["finite"], report["result"]
    assert report["generations"] == 2, report["generations"]
    steps = control.committed_steps(sup.ckpt_dir)
    assert steps and steps[-1] == n_iter, steps

    # structured trail, in causal order:
    # heartbeat_lost -> generation_killed -> remesh -> restore
    kinds = [e["kind"] for e in report["trail"]]
    order = [kinds.index(k) for k in
             ("heartbeat_lost", "generation_killed", "remesh", "restore")]
    assert order == sorted(order), kinds
    lost = next(e for e in report["trail"]
                if e["kind"] == "heartbeat_lost")
    assert lost["pod"] == 1, lost
    rem = next(e for e in report["trail"] if e["kind"] == "remesh")
    assert rem["survivors"] == [0] and rem["n_processes"] == 1, rem
    restore = next(e for e in report["trail"] if e["kind"] == "restore")
    assert restore["generation"] == 1, restore
    assert 0 < restore["step"] < n_iter, restore

    # no orphaned processes: every pid the supervisor ever spawned is
    # gone (ESRCH) or at worst a reaped zombie of OUR process (none --
    # the supervisor wait()s everything it kills)
    import errno
    for pid in report["pids"]:
        try:
            os.kill(pid, 0)
            raise AssertionError(f"orphaned worker pid {pid}")
        except OSError as e:
            assert e.errno == errno.ESRCH, e

    # no stale-generation shards: every committed step dir holds ONLY
    # files named by its own manifest, and the final boundary belongs
    # to the surviving generation
    import json as _json
    for s in steps:
        d = sup.ckpt_dir / f"step_{s:010d}"
        meta = _json.loads((d / "meta.json").read_text())
        want = set(meta["manifest"]["files"])
        have = {p.name for p in d.glob("*.npz")}
        assert have == want, (s, have, want)
        gen = meta.get("generation")
        tag = f"-g{gen:06d}.npz"
        assert all(f.endswith(tag) for f in want), (s, gen, want)
    final_meta = _json.loads(
        (sup.ckpt_dir / f"step_{steps[-1]:010d}" / "meta.json")
        .read_text())
    assert final_meta.get("generation") == 1, final_meta
    shutil.rmtree(tmpdir, ignore_errors=True)
    return {"resumed_at": restore["step"],
            "final_step": report["result"]["step"],
            "generations": report["generations"]}


SCENARIOS = {
    "nan_rollback": scenario_nan_rollback,
    "kernel_fallback": scenario_kernel_fallback,
    "preempt_resume": scenario_preempt_resume,
    "host_loss": scenario_host_loss,
    "corrupt_restore": scenario_corrupt_restore,
    "index_audit": scenario_index_audit,
    "process_kill": scenario_process_kill,
}


def main() -> int:
    import argparse
    import time

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="run all recovery scenarios on tiny data")
    ap.add_argument("--backend", default="interpret",
                    choices=["interpret", "xla", "pallas"])
    ap.add_argument("--only", default=None,
                    help="comma-separated scenario names")
    ap.add_argument("--no-skip", action="store_true",
                    help="fail any scenario that reports itself skipped "
                         "(for CI gates that must not silently go "
                         "vacuous when a capability probe regresses)")
    args = ap.parse_args()
    names = list(SCENARIOS)
    if args.only:
        names = [n for n in names if n in args.only.split(",")]
    failed = 0
    for name in names:
        t0 = time.time()
        try:
            info = SCENARIOS[name](backend=args.backend)
            if isinstance(info, dict) and "skipped" in info:
                if args.no_skip:
                    failed += 1
                    print(f"[faults] {name}: FAILED: required scenario "
                          f"skipped: {info['skipped']}", flush=True)
                else:
                    print(f"[faults] {name}: skipped: {info['skipped']}",
                          flush=True)
                continue
            print(f"[faults] {name}: OK in {time.time() - t0:.1f}s {info}",
                  flush=True)
        except Exception as e:  # pragma: no cover - CI failure surface
            failed += 1
            print(f"[faults] {name}: FAILED: {e!r}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    # re-dispatch through the canonical import so the scenarios share the
    # one _ACTIVE cell funcsne.fit consults (running under `python -m`
    # loads this file as `__main__`, a *second* module object)
    from repro.runtime import faults as _canonical
    raise SystemExit(_canonical.main())
