"""Public jit'd wrapper for the merge-fused neighbour refinement kernel.

Backend selection matches the other kernel packages:
  'pallas'    -- compiled Pallas kernel (TPU runtime)
  'interpret' -- Pallas interpret mode (CPU validation of the kernel body)
  'xla'       -- the pure-jnp reference: the selection pipeline
                 (dedup_candidates + gather-ref distances + merge_knn)
  'auto'      -- 'pallas' when a TPU is present, else 'xla'
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.kernels import fallback
from repro.kernels.backend import resolve
from repro.kernels.knn_merge.kernel import (knn_merge_cand_pallas,
                                            knn_merge_pallas)
from repro.kernels.knn_merge.ref import knn_merge_cand_ref, knn_merge_ref
from repro.kernels.pairwise_sqdist.kernel import (VMEM_ROWS_BUDGET,
                                                  packed_bytes, row_source)


def merge_sources(n: int, d: int) -> tuple:
    """(row source, flag source) of a candidate-fused launch over an
    (n, d) source matrix, each ``'vmem'`` (resident packed table) or
    ``'dma'`` (one HBM DMA per id).  A function of the static shape alone.

    The launch's resident tables share ``VMEM_ROWS_BUDGET``.  The row
    table is placed first (``row_source``: d small, packing within the
    budget), since it saves G+1 DMAs per query against the flag table's
    C; the (n/128, 128) flag table takes what is left, so where both do
    not fit the flags give way first.
    """
    rows = row_source(n, d)
    used = packed_bytes(n, d) if rows == "vmem" else 0
    flags = "vmem" if used + packed_bytes(n, 1) <= VMEM_ROWS_BUDGET \
        else "dma"
    return rows, flags


def knn_merge(x, qid, cur_idx, cur_d, cand=None, *, cand_active=None,
              cur_valid=None, backend: str = "auto", sources=None,
              salt=None, first_tables=(), second_tables=(), active=None):
    """Score C candidates, dedup, and top-K merge -- ONE fused operation.

    Replaces the per-iteration selection epilogue ``dedup_candidates`` ->
    ``pairwise_sqdist_gather`` -> ``merge_knn``: the Pallas path performs
    the dedup and the (stable, top_k-tie-identical) merge in-register per
    row block, so no (B, C) distance buffer, no (B, C, K)/(B, C, C) dedup
    broadcast tensor and no sort exist in the step HLO.

    Candidate-fused mode (§Perf H17): pass ``sources``/``salt`` instead
    of a precomputed ``cand`` and the candidates themselves are *derived*
    from the counter-based hash RNG plus chained gathers through the
    neighbour tables -- in-kernel on the Pallas path (no (B, C) candidate
    tensor, no threefry, no (B, s, K2) two-hop broadcast in the HLO), or
    via the bit-identical jnp reference sampler on the 'xla' path.

    Args:
      x: (N, M) source matrix (X for HD refinement, Y for LD).
      qid: (B,) int32 query row ids.
      cur_idx: (B, K) int32 resident neighbour list; SENTINEL = invalid.
      cur_d: (B, K) f32 stored squared distances (+inf = invalid), or
        ``None`` to re-score the current neighbours in-kernel (LD mode:
        the embedding moved since the list was merged).  ``None`` requires
        ``cur_valid``.
      cand: (B, C) int32 candidate ids (SENTINEL / out-of-range allowed);
        in candidate-fused mode, the optional (B, C_extra) slab backing
        the ``("extra", c)`` source slots (e.g. cached reverse edges).
      cand_active: optional (B, C) bool extra validity mask (active-row
        membership); structural dedup (self / current / earlier-duplicate
        / SENTINEL) always happens inside.  Candidate-fused mode computes
        this internally from ``active`` instead.
      cur_valid: (B, K) bool validity of current slots, rescore mode only.
      sources: static candidate layout (see ``knn_lib.counter_candidates``)
        -- presence selects candidate-fused mode.
      salt: int32 counter-RNG salt (candidate-fused mode).
      first_tables: tuple of (B, Kf) resident first-table slabs.
      second_tables: tuple of (N2, K2) global tables for two-hop chains.
      active: (N,) bool global row membership, or None == all active.
    Returns:
      (new_idx (B, K) int32, new_d (B, K) f32, improved (B,) bool) --
      the ``merge_knn`` contract: sorted ascending, stable ties,
      ``improved`` true iff a candidate beat the pre-merge worst slot.
    """
    rescore = cur_d is None
    if rescore:
        assert cur_valid is not None, "rescore mode requires cur_valid"
    else:
        assert cur_valid is None, "cur_valid is a rescore-mode option"
    backend = resolve(backend)

    if sources is not None:
        assert salt is not None, "candidate-fused mode requires a salt"
        assert cand_active is None, \
            "candidate-fused mode derives cand_active from `active`"
        # zero-width sources are dropped up front so the static layout the
        # kernel specialises on matches the ref's concatenation exactly
        sources = tuple(s for s in sources if s[-1] > 0)

        def run_ref():
            return knn_merge_cand_ref(
                x, qid, cur_idx, cur_d, salt=salt, sources=sources,
                first_tables=first_tables, second_tables=second_tables,
                extra=cand, active=active, cur_valid=cur_valid)

        if backend == "xla":
            return run_ref()
        if backend in ("pallas", "interpret"):
            cur_w = cur_valid if rescore else cur_d
            rows, flags = merge_sources(*x.shape)

            def run_kernel():
                # HLO metadata only (outside the kernel's jit, so the
                # launch keeps its name): a trace shows which sources ran
                flag_scope = contextlib.nullcontext() if active is None \
                    else jax.named_scope(f"knn_merge.{flags}_flags")
                with jax.named_scope(f"knn_merge.{rows}_rows"), flag_scope:
                    return knn_merge_cand_pallas(
                        x, qid, cur_idx, cur_w, salt, first_tables,
                        second_tables, cand, active, sources=sources,
                        rescore=rescore, row_source=rows, flag_source=flags,
                        interpret=(backend == "interpret"))

            return fallback.guarded("knn_merge", run_kernel, run_ref)
        raise ValueError(f"unknown backend {backend!r}")

    def run_ref():
        return knn_merge_ref(x, qid, cur_idx, cur_d, cand,
                             cand_active=cand_active, cur_valid=cur_valid)

    if backend == "xla":
        return run_ref()
    if backend in ("pallas", "interpret"):
        ca = cand_active if cand_active is not None \
            else jnp.ones(cand.shape, bool)
        cur_w = cur_valid if rescore else cur_d
        return fallback.guarded(
            "knn_merge",
            lambda: knn_merge_pallas(x, qid, cur_idx, cur_w, cand, ca,
                                     rescore=rescore,
                                     interpret=(backend == "interpret")),
            run_ref)
    raise ValueError(f"unknown backend {backend!r}")
