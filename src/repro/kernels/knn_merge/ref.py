"""Pure-jnp oracles for the merge-fused neighbour refinement.

Two reference implementations, same interface as the kernel:

  * :func:`knn_merge_ref` -- the selection pipeline
    (``knn_lib.dedup_candidates`` + gather-ref distances +
    ``knn_lib.merge_knn``).  This is the 'xla' backend.
  * :func:`knn_merge_rank_ref` -- the kernel's stable-rank selection
    (``merge_select``) as a flat XLA program: identical outputs with no
    ``top_k``/sort and no (B, C, K) dedup broadcast, used as the
    algebraic cross-check of the merge algorithm and as the B side of the
    selection-epilogue A/B benchmark.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.knn_merge.kernel import merge_select
from repro.kernels.pairwise_sqdist.ref import pairwise_sqdist_gather_ref


def _knn_lib():
    # Deferred: repro.core.__init__ imports funcsne, which imports this
    # package -- a module-level import here would close that cycle in
    # whichever direction loses the import race.
    from repro.core import knn as knn_lib
    return knn_lib


def _score(x, qid, cur_idx, cur_d, cand, cur_valid):
    """(cur_d, cand_d): stored or re-scored current distances and the
    candidates' distances, via the gather reference."""
    if cur_d is None:
        # LD rescore: one fused launch scores current + candidate rows
        # (the embedding moved since the list was merged)
        both = jnp.concatenate([cur_idx, cand], axis=1)
        both_d = pairwise_sqdist_gather_ref(x, qid, both)
        cur_d, cand_d = jnp.split(both_d, [cur_idx.shape[1]], axis=1)
        cur_d = jnp.where(cur_valid, cur_d, jnp.inf)
    else:
        cand_d = pairwise_sqdist_gather_ref(x, qid, cand)
    return cur_d, cand_d


def knn_merge_ref(x, qid, cur_idx, cur_d, cand, *, cand_active=None,
                  cur_valid=None):
    """Legacy-pipeline oracle; see ops.py for the argument contract."""
    knn_lib = _knn_lib()
    valid = knn_lib.dedup_candidates(qid, cur_idx, cand)
    if cand_active is not None:
        valid &= cand_active
    cur_d, cand_d = _score(x, qid, cur_idx, cur_d, cand, cur_valid)
    return knn_lib.merge_knn(cur_idx, cur_d, cand, cand_d, valid)


def knn_merge_rank_ref(x, qid, cur_idx, cur_d, cand, *, cand_active=None,
                       cur_valid=None):
    """Stable-rank-selection oracle: the kernel's algorithm, flat XLA."""
    cur_d, cand_d = _score(x, qid, cur_idx, cur_d, cand, cur_valid)
    if cand_active is None:
        cand_active = jnp.ones(cand.shape, bool)
    return merge_select(qid[:, None], cur_idx, cur_d, cand, cand_d,
                        cand_active)


def knn_merge_cand_ref(x, qid, cur_idx, cur_d, *, salt, sources,
                       first_tables=(), second_tables=(), extra=None,
                       active=None, cur_valid=None, rank=False):
    """Candidate-fused oracle (§Perf H17): the counter-RNG jnp sampler
    feeding the selection pipeline.

    Generates the candidate block with ``knn_lib.counter_candidates``
    (bit-identical draws to the kernel's in-register generation -- flat
    two-hop gathers, no (B, s, K2) broadcast, no threefry) and resolves
    per-candidate activity as ``active[clip(cand)]`` exactly like the
    kernel's element DMAs.  ``rank=True`` runs the stable-rank selection
    (the kernel's algorithm as flat XLA) instead of the
    dedup+top_k pipeline; both give identical outputs.
    """
    knn_lib = _knn_lib()
    cand = knn_lib.counter_candidates(salt, qid, sources, first_tables,
                                      second_tables, n_total=x.shape[0],
                                      extra=extra)
    cand_active = None
    if active is not None:
        cand_active = active[jnp.clip(cand, 0, active.shape[0] - 1)]
    fn = knn_merge_rank_ref if rank else knn_merge_ref
    return fn(x, qid, cur_idx, cur_d, cand, cand_active=cand_active,
              cur_valid=cur_valid)
