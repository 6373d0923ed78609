"""Elastic multi-host coordinator for the resilient embedding runtime.

``funcsne.fit`` survives faults a *single* process can survive: NaN
chunks roll back, kernel failures demote, preemption resumes in a fresh
process.  A pod adds the failure mode none of those cover -- a host (its
whole block of devices) drops out while the survivors keep running.
:func:`fit_elastic` is the host-side loop for that case:

  1. drive the chunked distributed program (``make_distributed_step``
     with ``chunk=T``) under the same rollback / backoff / checkpoint
     policy as ``fit`` -- the health telemetry is mesh-reduced inside
     the scan, so one bad shard trips the global rollback;
  2. every checkpoint is written as per-host shard files
     (``Checkpointer.save(host_shard_filter=...)``), so checkpoint I/O
     scales with the pod instead of funnelling through one host;
  3. on a host loss (``faults.HostLost`` here; a heartbeat timeout in a
     real deployment) the survivors quiesce (the in-flight checkpoint
     write lands), ``elastic.remesh`` re-forms the mesh over the
     remaining devices, the last committed chunk boundary is restored
     ONTO THE SHRUNKEN MESH (``Checkpointer.restore(shardings=new)``)
     and the schedule replays from the carried step.

Chunk boundaries are bit-neutral, so no iteration is lost or repeated
across the remesh; the replayed steps differ from the uninterrupted
run only by the collective reduction grouping of the smaller mesh
(fp32-level, quality-neutral -- pinned in tests/test_elastic_resume.py).

The loop runs in two deployment shapes:

  * **simulated pod** (default, one Python process): hosts are
    contiguous device blocks, loss is an injected ``faults.HostLost``,
    and the HostLost handler below remeshes in-process -- the CI-sized
    harness every elastic test drives;
  * **real multi-process pod** (``jax.process_count() > 1``, i.e. the
    caller ran ``jax.distributed.initialize``): every process executes
    this same loop SPMD, each writes ONLY its own generation-tagged
    checkpoint shard (``host_id=jax.process_index()``), and liveness is
    proven through the ``on_boundary`` heartbeat hook.  A real process
    death is NOT handled here -- a survivor cannot re-initialise
    ``jax.distributed`` in-process after a peer dies (jaxlib aborts), so
    the supervisor in ``repro.runtime.control`` kills the whole worker
    generation and relaunches it over the survivors on a fresh
    coordinator port; the relaunched generation re-enters this function
    with ``resume_from=`` pointing at the last committed boundary.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import funcsne
from repro.core.resilience import EmbeddingDiverged
from repro.kernels import fallback
from repro.launch.mesh import host_device_blocks
from repro.runtime import elastic, faults


def fit_elastic(X, *, cfg: "funcsne.FuncSNEConfig" = None,
                n_iter: int = 750, chunk_size: int = None, rng=None,
                hparams: "funcsne.HParams" = None,
                schedule: Callable = None, init: str = "pca",
                n_hosts: int = 1, model: int = 1,
                devices: Optional[Sequence] = None,
                resilience=None, state=None, resume_from=None,
                on_boundary: Optional[Callable[[int], None]] = None,
                generation: Optional[int] = None):
    """``funcsne.fit``'s rollback/checkpoint loop on a device mesh, with
    elastic resume across simulated host loss.  Returns the final
    :class:`~repro.core.funcsne.FuncSNEState` (replicated on the
    surviving mesh).

    ``n_hosts`` partitions ``devices`` (default: all of
    ``jax.devices()``) into contiguous blocks -- the simulated pod.
    ``model`` is the requested tensor-parallel width; the actual mesh is
    whatever :func:`repro.runtime.elastic.remesh` finds feasible for the
    surviving device count (``cfg.dim_hd`` must stay divisible by the
    model axis because ``X`` is feature-sharded), so a remesh after a
    loss may shrink the model axis rather than drop devices.

    A :class:`~repro.runtime.faults.HostLost` raised at a chunk boundary
    is survivable only when ``resilience.checkpoint_dir`` is set and at
    least one boundary committed; otherwise it propagates (there is
    nothing to resume from).

    ``on_boundary(it)`` is called after every committed chunk boundary
    (and once at entry with the starting step): the liveness hook the
    multi-process control plane uses to bump the pod's heartbeat
    counter.  It must be cheap and must not raise.

    Under ``jax.distributed`` (``jax.process_count() > 1``) every
    process runs this loop SPMD over the global device set; checkpoint
    writes automatically switch to one generation-tagged shard per
    process (``generation`` defaults to 0 there) and the process-local
    straggler alarm only logs -- an early checkpoint decided by one
    process's clock would stage an incomplete shard set.  ``n_hosts``
    must stay 1 in that mode (the real process set IS the pod).
    """
    Xh = jnp.asarray(X, jnp.float32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if cfg is None:
        cfg = funcsne.FuncSNEConfig(n_points=Xh.shape[0],
                                    dim_hd=Xh.shape[1])
    if hparams is None:
        hparams = funcsne.default_hparams(cfg.n_points)
    if schedule is None:
        schedule = funcsne.default_schedule
    if chunk_size is None:
        chunk_size = min(50, max(1, n_iter))
    devices = list(jax.devices() if devices is None else devices)
    if not 1 <= n_hosts <= len(devices):
        raise ValueError(f"n_hosts={n_hosts} for {len(devices)} devices")
    n_procs = jax.process_count()
    multiprocess = n_procs > 1
    if multiprocess:
        if n_hosts != 1:
            raise ValueError(
                "n_hosts simulates pods in single-process mode; under "
                "jax.distributed the process set IS the pod (n_hosts=1)")
        if generation is None:
            generation = 0
    beat = on_boundary if on_boundary is not None else (lambda _it: None)

    policy = resilience
    log = policy.log if policy is not None else (lambda *a, **k: None)
    on_mesh_event = (lambda e: policy.log(**e)) if policy is not None \
        else None
    ck = monitor = None
    if policy is not None:
        if policy.checkpoint_dir is not None:
            from repro.checkpoint import Checkpointer
            ck = Checkpointer(policy.checkpoint_dir,
                              keep_last=policy.keep_last)
        from repro.runtime.straggler import StepTimeMonitor
        monitor = StepTimeMonitor(z_thresh=policy.straggler_z,
                                  hang_timeout=policy.hang_timeout,
                                  warmup_steps=policy.straggler_warmup)
    from repro.checkpoint import row_shard_filter

    def build(devs):
        """(mesh, sharded X, replicated sharding) over the survivors."""
        mesh = elastic.remesh(len(devs), model=model, devices=devs,
                              divides=(cfg.dim_hd,),
                              on_event=on_mesh_event)
        Xs = jax.device_put(Xh, NamedSharding(mesh, P(None, "model")))
        return mesh, Xs, NamedSharding(mesh, P())

    mesh, Xs, repl = build(devices)

    if state is not None:
        st = state
    else:
        st = funcsne.init_state(rng, Xh, cfg, init=init,
                                perplexity=hparams.perplexity,
                                validate=False)
    from repro.checkpoint import cfg_compat

    def restore_chain(rck, like):
        """Fallback-chain restore onto the CURRENT mesh, logging one
        ``checkpoint_fallback`` event per damaged boundary skipped."""
        tree, meta, fbs = rck.restore_verified(
            like, shardings=jax.tree.map(lambda _: repl, like),
            expect_compat=cfg_compat(cfg))
        for fb in fbs:
            log("checkpoint_fallback", **fb)
        return tree, meta

    start_it = 0
    lr_scale = ex_scale = 1.0
    if resume_from is not None:
        from repro.checkpoint import Checkpointer
        rck = ck if (ck is not None
                     and str(ck.dir) == str(resume_from)) else \
            Checkpointer(resume_from)
        tree, meta = restore_chain(rck, st)
        st = tree
        start_it = int(meta["step"])
        lr_scale = float(meta.get("lr_scale", 1.0))
        ex_scale = float(meta.get("ex_scale", 1.0))
        log("restore", step=start_it, source=str(resume_from),
            from_generation=meta.get("generation"))
    st = jax.device_put(st, repl)

    def save_all_hosts(it, st, blocking=False):
        meta = {"lr_scale": lr_scale, "ex_scale": ex_scale,
                "compat": cfg_compat(cfg)}
        if multiprocess:
            # real pod: THIS process writes only its own generation-
            # tagged row shard; whichever process completes the set
            # commits the merged step dir (and evicts any stale shards
            # a dead generation left staged)
            ck.save(it, st, metadata=meta, blocking=blocking,
                    host_shard_filter=row_shard_filter(
                        jax.process_index(), n_procs, cfg.n_points),
                    host_id=jax.process_index(), n_hosts=n_procs,
                    generation=generation)
            return
        # one save() per simulated host: each writes only its row slice
        # (+ host 0 the replicated leaves); the completing write commits
        # the merged step dir.  save() joins the previous write first,
        # so the per-host writes serialise the way distinct hosts would
        # proceed independently.
        if n_hosts == 1:
            ck.save(it, st, metadata=meta, blocking=blocking,
                    generation=generation)
            return
        for h in range(n_hosts):
            ck.save(it, st, metadata=meta,
                    host_shard_filter=row_shard_filter(
                        h, n_hosts, cfg.n_points),
                    host_id=h, n_hosts=n_hosts, generation=generation)
        if blocking:
            ck.wait()

    chunks = {}         # T -> compiled program for the CURRENT mesh
    it = start_it
    retries = 0
    n_healthy = 0
    fb_seen = fallback.n_events()
    guard = fallback.enabled(policy.sticky_fallback) \
        if policy is not None else contextlib.nullcontext()
    with contextlib.ExitStack() as stack:
        stack.enter_context(guard)
        if ck is not None:
            stack.callback(ck.close)    # flush on every exit path
        beat(it)    # entry beat: the pod is alive before first compile
        while it < n_iter:
            with funcsne._span("chunk"):
                T = min(chunk_size, n_iter - it)
                if T not in chunks:
                    chunks[T], _ = funcsne.make_distributed_step(
                        cfg, mesh, chunk=T, schedule=schedule, n_iter=n_iter)
                hp_run = funcsne._scaled_hp(hparams, lr_scale, ex_scale)
                if policy is not None or faults.current() is not None:
                    # donated input: dispatch a copy, keep `st` as the
                    # rollback anchor (scripted faults poison the copy)
                    st_in = faults.corrupt_state(funcsne._copy_state(st), it)
                else:
                    st_in = st
                t0 = time.time()
                with funcsne._span("dispatch"):
                    st_out, _, metrics = chunks[T](st_in, Xs, hp_run)
                alarm = None
                if policy is not None:
                    with funcsne._span("sync"):     # the one host sync
                        m = jax.device_get(metrics)
                    alarm = monitor.observe(time.time() - t0)
                    if alarm is not None:
                        log("straggler", step=it, alarm=alarm)
                    for e in fallback.events(fb_seen):
                        log(**e)
                    fb_seen = fallback.n_events()
                    reason = policy.check(m)
                    if reason is None and policy.audit_every \
                            and (n_healthy + 1) % policy.audit_every == 0:
                        # chunk-boundary invariant audit (index corruption
                        # is invisible to the finite-fraction probes); the
                        # reductions AllReduce across the mesh, so one bad
                        # replica trips the global rollback
                        with funcsne._span("audit"):
                            aud = jax.device_get(
                                funcsne.audit_state(st_out, cfg, Xs))
                        reason = policy.audit_check(aud)
                        if reason is not None:
                            log("audit_violation", step=it, reason=reason)
                    if reason is not None:
                        if retries >= policy.max_retries:
                            log("giving_up", step=it, reason=reason,
                                retries=retries)
                            raise EmbeddingDiverged(it, reason, retries,
                                                    policy.events)
                        retries += 1
                        lr_scale *= policy.lr_backoff
                        ex_scale *= policy.exaggeration_backoff
                        log("rollback", step=it, reason=reason,
                            retry=retries, lr_scale=lr_scale,
                            ex_scale=ex_scale)
                        beat(it)    # a retry storm is alive, not dead
                        continue
                    retries = 0
                st = st_out
                it += T
                if policy is not None:
                    n_healthy += 1
                    if ck is not None:
                        saved = n_healthy % policy.checkpoint_every == 0
                        if saved:
                            with funcsne._span("checkpoint"):
                                save_all_hosts(it, st)
                        if alarm is not None and not multiprocess:
                            # hang/straggler escalation: commit this
                            # boundary now so a kill loses at most one chunk.
                            # Multi-process pods skip this: the alarm is
                            # decided by ONE process's clock, and a shard
                            # set only some processes stage never commits
                            # (the straggler event above still logs).
                            with funcsne._span("checkpoint"):
                                if saved:
                                    ck.wait()
                                else:
                                    save_all_hosts(it, st, blocking=True)
                            log("early_checkpoint", step=it, alarm=alarm)
                beat(it)
                faults.maybe_corrupt_checkpoint(it, ck)
                faults.maybe_preempt(it)
                try:
                    faults.maybe_host_loss(it)
                except faults.HostLost as e:
                    if ck is None or ck.latest_step() is None:
                        raise   # nothing committed: the run is not resumable
                    log("host_lost", step=e.step, host=e.host)
                    ck.wait()   # quiesce: the in-flight write is the truth
                    blocks = host_device_blocks(devices, n_hosts)
                    lost = blocks[e.host % n_hosts]
                    devices = [d for d in devices if d not in lost]
                    n_hosts = max(1, n_hosts - 1)
                    mesh, Xs, repl = build(devices)
                    chunks.clear()          # old programs pin the old mesh
                    # fallback-chain restore: the newest boundary may be the
                    # one the lost host's write tore -- degrade to the last
                    # verified one instead of materialising garbage
                    tree, meta = restore_chain(ck, st)
                    st = tree
                    it = int(meta["step"])
                    lr_scale = float(meta.get("lr_scale", 1.0))
                    ex_scale = float(meta.get("ex_scale", 1.0))
                    retries = 0
                    log("remesh", step=it, host_lost=e.host,
                        n_devices=len(devices), n_hosts=n_hosts,
                        mesh=dict(mesh.shape))
        if ck is not None:
            ck.wait()   # surface async write failures before returning
    return st
