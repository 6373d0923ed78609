"""Resilience layer for the chunked embedding driver (``funcsne.fit``).

The paper's pitch is an *always-on* interactive session: hyperparameters
are turned live, points stream in and out, and the optimisation simply
keeps running.  A session that dies on the first NaN chunk, diverging
learning rate or preempted worker is a batch job with extra steps.  This
module is the host-side half of the contract:

  * :class:`ResiliencePolicy` -- what ``fit`` should snapshot, when to
    trip a health probe, how far to back off on retry, and whether Pallas
    kernel failures demote to their XLA references (sticky fallback);
  * :class:`EmbeddingDiverged` -- the structured error raised when the
    bounded retry budget is exhausted (carries the step, trip reason and
    the full event log, so a service can triage without re-running);
  * the health probe itself (:meth:`ResiliencePolicy.check`) reads ONLY
    the on-device :class:`~repro.core.funcsne.ChunkMetrics` telemetry
    that already crosses the host boundary once per chunk -- fault
    detection adds zero extra host syncs.

The device-side half lives in ``funcsne._chunk_fn`` (finite-fraction /
max-|Y| / first-bad-step scalars folded into the chunk scan) and
``repro.kernels.fallback`` (sticky demotion registry); the deterministic
fault sources used by tests and CI live in ``repro.runtime.faults``.

On a mesh the same contract holds shard-globally: the chunk program
pmin/pmax-reduces the health scalars across every shard before the host
reads them (``health_reduce=True`` in ``make_distributed_step``), so
:meth:`ResiliencePolicy.check` sees the WORST shard's telemetry and a
NaN confined to one device's replica trips the global rollback.  The
policy code is identical either way -- it only ever consumes the one
ChunkMetrics tuple -- which is what lets
``repro.runtime.coordinator.fit_elastic`` reuse it unchanged for the
multi-host elastic loop (per-host checkpoint shards, remesh-and-resume
on host loss).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional


class EmbeddingDiverged(RuntimeError):
    """Retry budget exhausted: the run kept tripping health probes.

    Attributes:
      step:    global iteration the last failed chunk started at.
      reason:  the final trip reason string.
      retries: retries consumed before giving up.
      events:  the policy's full structured event log.
    """

    def __init__(self, step: int, reason: str, retries: int,
                 events: List[dict]):
        super().__init__(
            f"embedding diverged at step {step} after {retries} "
            f"rollback-retries: {reason}")
        self.step = step
        self.reason = reason
        self.retries = retries
        self.events = events


@dataclasses.dataclass
class ResiliencePolicy:
    """Checkpoint / rollback / degradation policy consumed by ``fit``.

    With a policy active, ``fit`` keeps one extra on-device copy of the
    state (the rollback anchor; the chunk program donates its input) and
    checks the chunk's health telemetry after every dispatch.  A tripped
    probe rolls the state back to the last healthy chunk boundary and
    retries with the learning rate (and optionally exaggeration)
    multiplied by ``lr_backoff`` / ``exaggeration_backoff`` -- the
    backoff compounds per retry and *persists* once a retry succeeds (a
    run that diverged at lr is not re-trusted with lr), which is why a
    clean run under a policy is bit-identical to ``resilience=None``:
    backoff only ever engages after a trip.

    ``checkpoint_dir`` additionally snapshots the full ``FuncSNEState``
    (embedding, velocities, KNN tables, RNG key, reverse-edge cache --
    everything, so resume is bit-deterministic at chunk granularity)
    through :class:`repro.checkpoint.Checkpointer` every
    ``checkpoint_every`` healthy chunks; ``fit(resume_from=dir)`` picks
    up after a kill bit-identically to the uninterrupted run.
    """
    # -- checkpointing ----------------------------------------------------
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 1           # healthy chunks between snapshots
    keep_last: int = 3
    # -- rollback & retry -------------------------------------------------
    max_retries: int = 3                # consecutive trips before raising
    lr_backoff: float = 0.5
    exaggeration_backoff: float = 1.0
    # -- health probe thresholds ------------------------------------------
    min_finite_frac: float = 1.0        # trip when finite_frac < this
    max_abs_y: float = 1e8              # trip when max |Y| exceeds this
    # -- chunk-boundary state audit ---------------------------------------
    # run funcsne.audit_state every N healthy chunks (0 = off): catches
    # index-table corruption that is invisible to the finite-fraction
    # probes (poisoned indices are perfectly finite integers); costs one
    # extra host sync per audited chunk, so leave sparse in production
    audit_every: int = 0
    # -- graceful degradation ---------------------------------------------
    # opt-in: a raising Pallas launch is answered by its XLA ref for the
    # rest of the run.  Off by default, so a kernel that fails to lower
    # fails the run instead of passing on the reference path.
    sticky_fallback: bool = False
    # -- hang / straggler watchdog ----------------------------------------
    hang_timeout: float = 600.0         # seconds per *chunk* dispatch
    straggler_z: float = 4.0
    straggler_warmup: int = 5
    # -- telemetry sink ---------------------------------------------------
    on_event: Optional[Callable[[dict], None]] = None
    events: List[dict] = dataclasses.field(default_factory=list)

    def log(self, kind: str, **info) -> dict:
        event = {"kind": kind, **info}
        self.events.append(event)
        if self.on_event is not None:
            self.on_event(event)
        return event

    def check(self, metrics) -> Optional[str]:
        """Trip reason from one chunk's telemetry, or None when healthy.

        Comparisons are written so NaN telemetry trips too (a NaN
        ``finite_frac`` fails ``>=``): a probe that can itself go NaN
        must fail closed.
        """
        ff = float(metrics.finite_frac)
        if not (ff >= self.min_finite_frac):
            bad = int(metrics.bad_step)
            return (f"non-finite embedding: finite_frac={ff:.4f} < "
                    f"{self.min_finite_frac} (first bad step {bad})")
        ym = float(metrics.y_max_abs)
        if not (ym <= self.max_abs_y) or math.isnan(ym):
            return (f"embedding explosion: max|Y|={ym:.3e} > "
                    f"{self.max_abs_y:.3e}")
        return None

    def audit_check(self, audit) -> Optional[str]:
        """Trip reason from an :class:`~repro.core.funcsne.AuditResult`
        (any non-zero violation counter), or None when clean.  Feeds the
        same rollback/backoff path as :meth:`check`."""
        bad = [f"{name}={int(v)}" for name, v in
               zip(audit._fields, audit) if int(v) != 0]
        if bad:
            return "state audit violation: " + ", ".join(bad)
        return None
