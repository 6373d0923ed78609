"""Public jit'd wrapper for the fused NE force kernel."""
from __future__ import annotations

import jax

from repro.kernels import fallback
from repro.kernels.backend import resolve
from repro.kernels.ne_forces.kernel import (ne_forces_gather_pallas,
                                            ne_forces_pallas,
                                            ne_forces_scatter_pallas)
from repro.kernels.ne_forces.ref import (ne_forces_gather_ref, ne_forces_ref,
                                         ne_forces_scatter_ref)
from repro.kernels.pairwise_sqdist.kernel import row_source


# VMEM budget for the scatter kernel's resident per-segment (chunk_n, d)
# fields.  Mosaic pads the trailing dim to the 128-lane tile and all S
# segment fields stay resident (double-buffered) across a chunk's sweep,
# so 2 * S * chunk_n * 512B at d<=128 plus the staged rows and the edge
# scratch must fit the kernel's scoped-VMEM limit
# (``kernel.SCATTER_VMEM_LIMIT``).  The budget sizes the *chunk*: N only
# raises the chunk count.
_SCATTER_VMEM_BUDGET = 24 * 2 ** 20


def scatter_chunk_plan(n: int, d: int, n_segments: int) -> int:
    """Rows binned per grid step so the S resident fields fit VMEM.

    Returns ``chunk_n`` (== n when everything fits in one chunk).  Every
    shape gets a plan; a large N only costs more chunks.
    """
    lane_padded = -(-d // 128) * 128
    bytes_per_row = n_segments * lane_padded * 4
    max_rows = _SCATTER_VMEM_BUDGET // max(bytes_per_row, 1)
    if max_rows >= n:
        return n
    return max(8, (max_rows // 8) * 8)      # keep sublane-tile alignment


def ne_forces(y, nbr, coef, alpha, *, mode: str, backend: str = "auto"):
    """Fused variable-tail force evaluation; see ref.py for semantics."""
    backend = resolve(backend)
    if backend in ("pallas", "interpret"):
        return fallback.guarded(
            "ne_forces",
            lambda: ne_forces_pallas(y, nbr, coef, alpha, mode=mode,
                                     interpret=backend == "interpret"),
            lambda: ne_forces_ref(y, nbr, coef, alpha, mode=mode))
    if backend == "xla":
        return ne_forces_ref(y, nbr, coef, alpha, mode=mode)
    raise ValueError(f"unknown backend {backend!r}")


def ne_forces_gather(x, qid, nbr_idx, coef, alpha, *, segments,
                     emit_edges=None, scatter_fused: bool = False,
                     scatter_back=None, backend: str = "auto"):
    """Index-taking, segmented force evaluation in ONE launch.

    Unlike :func:`ne_forces` the (B, K, d) gathered neighbour buffer is
    never materialised in HBM, and several neighbour segments (e.g. HD
    attraction + LD repulsion + negative samples) are evaluated over the
    concatenated neighbour axis in a single kernel launch: one read of the
    embedding instead of three.  ``segments`` is a static tuple of
    ``(mode, size)`` pairs.

    Two output modes:
      * edge-emitting (default): returns per-segment tuples
        (aggs, edges, wsums) -- see ref.py for semantics; ``emit_edges``
        elides the (B, K_s, d) edge output of segments whose symmetric
        contribution the caller discards.
      * ``scatter_fused=True``: the symmetrisation itself moves into the
        op -- per-edge forces are accumulated in-kernel into per-segment
        (N, d) displacement-field partials (+edge at the query row,
        -edge at the neighbour row where ``scatter_back[s]``), so no
        per-edge tensor round-trips through HBM at all.  Returns
        (scats, wsums); ``emit_edges`` must be left None.
    """
    segments = tuple((str(m), int(s)) for m, s in segments)
    backend = resolve(backend)
    if scatter_fused:
        assert emit_edges is None, "emit_edges is an edge-mode option"
        if scatter_back is not None:
            scatter_back = tuple(bool(b) for b in scatter_back)
        chunk_n = scatter_chunk_plan(x.shape[0], x.shape[1], len(segments))

        def run_scatter_ref():
            return ne_forces_scatter_ref(x, qid, nbr_idx, coef, alpha,
                                         segments=segments,
                                         scatter_back=scatter_back)

        if backend in ("pallas", "interpret"):
            return fallback.guarded(
                "ne_forces",
                lambda: ne_forces_scatter_pallas(
                    x, qid, nbr_idx, coef, alpha, segments=segments,
                    scatter_back=scatter_back, chunk_n=chunk_n,
                    interpret=backend == "interpret"),
                run_scatter_ref)
        if backend == "xla":
            return run_scatter_ref()
        raise ValueError(f"unknown backend {backend!r}")
    assert scatter_back is None, "scatter_back is a scatter_fused option"
    if emit_edges is not None:
        emit_edges = tuple(bool(e) for e in emit_edges)
    if backend in ("pallas", "interpret"):
        rows = row_source(*x.shape)

        def run_kernel():
            # HLO metadata only (outside the kernel's jit, so the launch
            # keeps its name): a trace shows which row source ran
            with jax.named_scope(f"ne_forces.{rows}_rows"):
                return ne_forces_gather_pallas(
                    x, qid, nbr_idx, coef, alpha, segments=segments,
                    emit_edges=emit_edges, row_source=rows,
                    interpret=backend == "interpret")

        return fallback.guarded(
            "ne_forces", run_kernel,
            lambda: ne_forces_gather_ref(x, qid, nbr_idx, coef, alpha,
                                         segments=segments,
                                         emit_edges=emit_edges))
    if backend == "xla":
        return ne_forces_gather_ref(x, qid, nbr_idx, coef, alpha,
                                    segments=segments,
                                    emit_edges=emit_edges)
    raise ValueError(f"unknown backend {backend!r}")
