"""Public jit'd wrapper for the pairwise squared-distance kernel.

Backend selection:
  'pallas'    -- compiled Pallas kernel (TPU runtime)
  'interpret' -- Pallas interpret mode (CPU validation of the kernel body)
  'xla'       -- pure-jnp oracle (default on CPU; also the dry-run lowering path)
  'auto'      -- 'pallas' when a TPU is present, else 'xla'
"""
from __future__ import annotations

from repro.kernels import fallback
from repro.kernels.backend import resolve
from repro.kernels.pairwise_sqdist.kernel import (
    pairwise_sqdist_gather_pallas, pairwise_sqdist_pallas)
from repro.kernels.pairwise_sqdist.ref import (
    pairwise_sqdist_gather_ref, pairwise_sqdist_ref)


def pairwise_sqdist(q, c, *, backend: str = "auto"):
    """Squared distances between queries (B, M) and candidates (B, C, M)."""
    backend = resolve(backend)
    if backend in ("pallas", "interpret"):
        return fallback.guarded(
            "pairwise_sqdist",
            lambda: pairwise_sqdist_pallas(q, c,
                                           interpret=backend == "interpret"),
            lambda: pairwise_sqdist_ref(q, c))
    if backend == "xla":
        return pairwise_sqdist_ref(q, c)
    raise ValueError(f"unknown backend {backend!r}")


def pairwise_sqdist_gather(x, qid, cand, *, backend: str = "auto"):
    """Index-taking squared distances: ``||x[qid[b]] - x[cand[b, j]]||^2``.

    Unlike :func:`pairwise_sqdist` the (B, C, M) gathered operand is never
    materialised in HBM -- the Pallas kernel DMAs the needed rows per block.
    The 'xla' path is the pure-jnp fallback used on CPU and as the dry-run
    lowering; it gathers explicitly but keeps the same semantics.
    """
    backend = resolve(backend)
    if backend in ("pallas", "interpret"):
        return fallback.guarded(
            "pairwise_sqdist",
            lambda: pairwise_sqdist_gather_pallas(
                x, qid, cand, interpret=backend == "interpret"),
            lambda: pairwise_sqdist_gather_ref(x, qid, cand))
    if backend == "xla":
        return pairwise_sqdist_gather_ref(x, qid, cand)
    raise ValueError(f"unknown backend {backend!r}")
