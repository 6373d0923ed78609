"""Sticky per-kernel-family degradation to the XLA reference path.

A production embedding service would rather run a kernel family on its
(slower, always-correct) XLA reference than crash the whole session the
moment one Pallas launch fails to build -- a Mosaic lowering bug on a new
shape, a VMEM plan that doesn't fit, a driver hiccup.  Every kernel
``ops.py`` wrapper routes its Pallas/interpret dispatch through
:func:`guarded`:

  * disabled (the default) it is a pure passthrough -- exceptions
    propagate exactly as before, so kernel tests keep failing loudly;
  * enabled (``funcsne.fit`` turns it on while a ``ResiliencePolicy``
    with the opt-in ``sticky_fallback=True`` is active), a raising Pallas
    launch demotes its *family* to the XLA ref for the remainder of the
    process and the call is answered by the reference instead.  The demotion is
    sticky: later traces consult the registry up front, so one failure
    never re-raises per chunk.

Demotions are recorded as structured events (:func:`events`) -- the
telemetry channel the resilience layer drains into its own log.
``repro.runtime.faults.KernelLaunchFault`` injects a failure right
before the Pallas kernel is built, so the whole path is exercised
deterministically in CI.
"""
from __future__ import annotations

import contextlib
import threading
import warnings
from typing import Callable, Dict, List

from repro.runtime import faults

# Lock discipline: EVERY access to the module registries below -- reads
# included -- happens under _LOCK (async checkpoint writers and the
# chunk dispatch thread consult this module concurrently; a reader
# iterating _EVENTS while a writer appends is a race even under the
# GIL's best behaviour).  The lock is never held across a kernel launch:
# guarded() snapshots what it needs, releases, then runs.
_LOCK = threading.Lock()
_ENABLED = False
_DEMOTED: Dict[str, str] = {}       # family -> reason
_EVENTS: List[dict] = []


def is_enabled() -> bool:
    with _LOCK:
        return _ENABLED


@contextlib.contextmanager
def enabled(on: bool = True):
    """Enable (or force-disable) guarded launches within a scope."""
    global _ENABLED
    with _LOCK:
        prev, _ENABLED = _ENABLED, bool(on)
    try:
        yield
    finally:
        with _LOCK:
            _ENABLED = prev


def demote(family: str, reason) -> None:
    """Sticky-demote ``family`` to its XLA reference path."""
    with _LOCK:
        if family in _DEMOTED:
            return
        _DEMOTED[family] = str(reason)
        _EVENTS.append({"kind": "kernel_demoted", "family": family,
                        "reason": str(reason)})
    warnings.warn(f"[kernels.fallback] demoting {family!r} to its XLA "
                  f"reference for the rest of the run: {reason}",
                  RuntimeWarning, stacklevel=2)


def is_demoted(family: str) -> bool:
    with _LOCK:
        return family in _DEMOTED


def demotions() -> Dict[str, str]:
    with _LOCK:
        return dict(_DEMOTED)


def events(since: int = 0) -> List[dict]:
    with _LOCK:
        return list(_EVENTS[since:])


def n_events() -> int:
    with _LOCK:
        return len(_EVENTS)


def reset() -> None:
    """Clear all sticky state (tests)."""
    global _ENABLED
    with _LOCK:
        _ENABLED = False
        _DEMOTED.clear()
        _EVENTS.clear()


def guarded(family: str, run_pallas: Callable[[], object],
            run_xla: Callable[[], object]):
    """Run ``run_pallas`` under the sticky-fallback contract.

    Passthrough when disabled.  When enabled: demoted families are
    answered by ``run_xla`` up front; otherwise injected faults
    (``repro.runtime.faults``) and real launch/lowering exceptions demote
    the family and the XLA ref answers this call and every later one.
    """
    if not is_enabled():
        return run_pallas()
    if is_demoted(family):
        return run_xla()
    try:
        faults.check_kernel(family)
        return run_pallas()
    except Exception as e:
        demote(family, repr(e))
        return run_xla()
