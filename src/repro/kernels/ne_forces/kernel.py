"""Pallas TPU kernel: fused variable-tail NE force evaluation.

The paper's GPU implementation evaluates the LD kernel w_ij, the force
vector, and the Z-estimator partial sums in separate passes with atomics.
On TPU we fuse them: one VMEM-resident pass over a (block_b, K, d) tile
computes LD squared distances, the closed-form tail powers

    w^(1/alpha)     = (1 + d2/alpha)^(-1)          (attraction weight)
    w^(1+1/alpha)   = (1 + d2/alpha)^(-(alpha+1))  (repulsion weight)

and emits the per-point aggregate force, the per-edge forces (for the
scatter-free symmetrisation outside the kernel), and the w partial sums
(Z-hat estimator).  alpha is a *traced* (1,1) scalar so interactive
hyperparameter changes never recompile (paper Sec. 3).

Grid: (B/block_b,) -- one parallel sweep; K and d live fully in VMEM
(K <= ~128 neighbours, d <= ~64 embedding dims by design).  On TPU the
(K, d) trailing dims map to (sublane, lane); Mosaic pads d to the 128-lane
tile, so at visualisation-scale d (2..8) the arithmetic is lane-sparse.
The index-taking variant below is bound by neither bytes nor arithmetic
when it fetches each neighbour row with its own HBM DMA: at d=2 a v5e
spends ~33 ns per 512-byte row DMA, 1.9% of HBM bandwidth.  So at small
d it reads the rows from a lane-dense packing of the whole embedding
held in VMEM instead (``row_source='vmem'``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params
from repro.kernels.pairwise_sqdist.kernel import (LANES, ROW_SHIFT,
                                                  VMEM_ROWS_LIMIT, pack_rows,
                                                  pad_lanes, smem_ids,
                                                  unpack_rows)


def _edge_wsum(delta, coef, alpha, mode: str):
    """Closed-form tail powers -> (edge, wsum); the single in-kernel copy
    of the force math shared by the pre-gather and gather-fused kernels
    (semantics in ref.py)."""
    d2 = jnp.sum(delta * delta, axis=-1)            # (bb, K)
    base = 1.0 + d2 / alpha
    if mode == "attraction":
        wexp = 1.0 / base
        edge = (coef * wexp)[..., None] * delta
        wsum = jnp.sum(coef * wexp, axis=-1)
    else:
        logb = jnp.log(base)
        wexp = jnp.exp(-(alpha + 1.0) * logb)
        w = jnp.exp(-alpha * logb)
        edge = (coef * wexp)[..., None] * (-delta)
        wsum = jnp.sum(coef * w, axis=-1)
    return edge, wsum


def _ne_forces_kernel(alpha_ref, y_ref, nbr_ref, coef_ref, agg_ref, edge_ref,
                      wsum_ref, *, mode: str):
    alpha = alpha_ref[0, 0]
    y = y_ref[...].astype(jnp.float32)              # (bb, d)
    nbr = nbr_ref[...].astype(jnp.float32)          # (bb, K, d)
    coef = coef_ref[...].astype(jnp.float32)        # (bb, K)

    edge, wsum = _edge_wsum(nbr - y[:, None, :], coef, alpha, mode)
    agg_ref[...] = jnp.sum(edge, axis=1)
    edge_ref[...] = edge
    wsum_ref[...] = wsum[:, None]


@functools.partial(
    jax.jit, static_argnames=("mode", "block_b", "interpret"))
def ne_forces_pallas(y, nbr, coef, alpha, *, mode: str, block_b: int = 128,
                     interpret: bool = False):
    """(B,d), (B,K,d), (B,K), scalar -> (agg (B,d), edge (B,K,d), wsum (B,))."""
    B, d = y.shape
    _, K, _ = nbr.shape
    block_b = min(block_b, _round_up(B, 8))
    Bp = _round_up(B, block_b)
    if Bp != B:
        y = jnp.pad(y, ((0, Bp - B), (0, 0)))
        nbr = jnp.pad(nbr, ((0, Bp - B), (0, 0), (0, 0)))
        coef = jnp.pad(coef, ((0, Bp - B), (0, 0)))
    alpha_arr = jnp.asarray(alpha, jnp.float32).reshape(1, 1)

    grid = (Bp // block_b,)
    agg, edge, wsum = pl.pallas_call(
        functools.partial(_ne_forces_kernel, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_b, K, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, K), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_b, K, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, d), jnp.float32),
            jax.ShapeDtypeStruct((Bp, K, d), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(alpha_arr, y, nbr, coef)
    return agg[:B], edge[:B], wsum[:B, 0]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# --------------------------------------------------------------------------
# Gather-fused, segmented variant.
#
# The pre-gather kernel above receives Y[idx] as a dense (B, K, d) operand,
# which XLA materialises in HBM before the launch -- and FUnc-SNE launches
# it three times per step (HD attraction, LD repulsion, negatives), reading
# the embedding three times.  This variant
#   * takes *indices* and DMAs only the needed embedding rows per block
#     (Y stays in HBM/ANY memory; the (B, K, d) buffer never exists), and
#   * evaluates several neighbour *segments* with independent modes in one
#     launch over the concatenated neighbour axis, so one gather of y_l and
#     one kernel launch replace all three per-step force launches.
# Segment boundaries are static config, so each segment's closed-form tail
# power is compiled straight-line -- no per-edge mode mask is evaluated.
#
# Row sources (static ``row_source``, chosen by ``row_source`` in
# ``pairwise_sqdist.kernel`` from (N, d) alone):
#
# * ``'dma'``: Mosaic DMAs only lane-aligned rows, so the embedding is read
#   lane-padded (``pad_lanes``: d -> 128, zero columns give zero deltas)
#   with one HBM row DMA per query and per neighbour.  The b loop is
#   double-buffered: rows are processed in ``sub_b`` sub-blocks through
#   2-slot VMEM staging with sub-block p+1's row DMAs started before
#   sub-block p is computed.
# * ``'vmem'``: at small d the embedding is packed lane-dense and held in
#   VMEM for the launch (``pack_rows`` / ``unpack_rows``, shared with the
#   merge kernel in ``pairwise_sqdist.kernel``): each id costs one on-core
#   load of its packed row into single-slot staging and a one-hot lane
#   select, so the math sees exactly the rows the DMA path stages.
#
# Either way the math runs on lane-padded rows and outputs keep their true
# width d.  Index slabs are staged into SMEM by the pipeline
# (O(block_b * K), never O(B)).


def _dma_query_and_neighbour_rows(x_ref, qid_ref, nbr_ref, q_scr, n_scr, sem):
    """Stage x[qid[r]] -> q_scr[r] and x[nbr[r, k]] -> n_scr[r, k] row DMAs.

    Issued back-to-back on one semaphore and drained in issue order
    (distinct destination slots -> no WAR hazard).  Used by the
    scatter-fused kernel, which stages a whole block at once.
    """
    block_b, K, _ = n_scr.shape

    def q_dma(r):
        return pltpu.make_async_copy(x_ref.at[qid_ref[0, r]], q_scr.at[r],
                                     sem)

    def n_dma(r, k):
        return pltpu.make_async_copy(x_ref.at[nbr_ref[r, k]], n_scr.at[r, k],
                                     sem)

    def issue(r, _):
        q_dma(r).start()
        jax.lax.fori_loop(0, K, lambda k, x: (n_dma(r, k).start(), x)[1],
                          None)
        return _

    def drain(r, _):
        q_dma(r).wait()
        jax.lax.fori_loop(0, K, lambda k, x: (n_dma(r, k).wait(), x)[1],
                          None)
        return _

    jax.lax.fori_loop(0, block_b, issue, None)
    jax.lax.fori_loop(0, block_b, drain, None)


def _ne_forces_gather_kernel(qid_ref, nbr_ref, alpha_ref, coef_ref, *refs,
                             segments: tuple, emit_edges: tuple, sub_b: int,
                             d: int, row_source: str):
    """qid (1, bb) SMEM; nbr (bb, K) SMEM; alpha (1,1) SMEM; coef (bb, K)
    VMEM; then by ``row_source``: 'dma': x (N, dp) ANY lane-padded;
    'vmem': qid_v (bb, 1) and nbr_v (bb, K) VMEM ids, x (R, d*128) VMEM
    packed (``pack_rows``) -> per segment s: agg (bb, d), edge (bb, K_s, d)
    for segments with emit_edges[s], wsum (bb, 1); then scratch: 'dma':
    q_scr (2, sub_b, dp), n_scr (2, sub_b, K, dp), sem (2,); 'vmem':
    q_scr (sub_b, d*128), n_scr (sub_b, K, d*128)."""
    vmem = row_source == "vmem"
    if vmem:
        qid_v_ref, nbr_v_ref, x_ref, *refs = refs
    else:
        x_ref, *refs = refs
    S = len(segments)
    E = sum(emit_edges)
    agg_refs = refs[:S]
    edge_refs = refs[S:S + E]
    wsum_refs = refs[S + E:2 * S + E]
    block_b, K = coef_ref.shape
    n_sub = block_b // sub_b
    alpha = alpha_ref[0, 0]

    if vmem:
        q_scr, n_scr = refs[2 * S + E:]

        def rows(p):
            """Load sub-block ``p``'s packed rows from the resident table
            and unpack them to (sub_b, 128) / (sub_b, K, 128)."""
            base = p * sub_b

            def row(lr, _):
                r = base + lr
                q_scr[pl.ds(lr, 1)] = x_ref[pl.ds(qid_ref[0, r] >> ROW_SHIFT,
                                                  1)]
                for k in range(K):
                    n_scr[lr, pl.ds(k, 1)] = x_ref[
                        pl.ds(nbr_ref[r, k] >> ROW_SHIFT, 1)]
                return _

            jax.lax.fori_loop(0, sub_b, row, None)
            q_lane = qid_v_ref[pl.ds(base, sub_b)] & (LANES - 1)
            n_lane = nbr_v_ref[pl.ds(base, sub_b)] & (LANES - 1)
            return (unpack_rows(q_scr[...], q_lane, d),
                    unpack_rows(n_scr[...], n_lane[:, :, None], d))
    else:
        q_scr, n_scr, sem = refs[2 * S + E:]

        def sub_copies(p, op):
            """Start/wait the 2-slot staged row DMAs of sub-block ``p``."""
            slot = p % 2

            def row(lr, _):
                r = p * sub_b + lr
                op(pltpu.make_async_copy(x_ref.at[qid_ref[0, r]],
                                         q_scr.at[slot, lr], sem.at[slot]))
                jax.lax.fori_loop(
                    0, K, lambda k, x: (op(pltpu.make_async_copy(
                        x_ref.at[nbr_ref[r, k]], n_scr.at[slot, lr, k],
                        sem.at[slot])), x)[1], None)
                return _

            jax.lax.fori_loop(0, sub_b, row, None)

        sub_copies(0, lambda cp: cp.start())

        def rows(p):
            slot = p % 2

            @pl.when(p + 1 < n_sub)
            def _prefetch():                 # overlap: copy p+1, compute p
                sub_copies(p + 1, lambda cp: cp.start())

            sub_copies(p, lambda cp: cp.wait())
            return (q_scr[slot].astype(jnp.float32),     # (sub_b, dp)
                    n_scr[slot].astype(jnp.float32))     # (sub_b, K, dp)

    def body(p, _):
        y, nbr = rows(p)
        base = p * sub_b
        coef = coef_ref[pl.ds(base, sub_b)].astype(jnp.float32)

        k0, e_i = 0, 0
        for s, (mode, size) in enumerate(segments):
            sl = slice(k0, k0 + size)
            delta = nbr[:, sl] - y[:, None, :]      # (sub_b, size, dp)
            edge, wsum = _edge_wsum(delta, coef[:, sl], alpha, mode)
            if emit_edges[s]:
                edge_refs[e_i][pl.ds(base, sub_b)] = edge[..., :d]
                e_i += 1
            agg_refs[s][pl.ds(base, sub_b)] = jnp.sum(edge, axis=1)[:, :d]
            wsum_refs[s][pl.ds(base, sub_b)] = wsum[:, None]
            k0 += size
        return _

    jax.lax.fori_loop(0, n_sub, body, None)


def _pick_sub_b(block_b: int) -> int:
    """Double-buffer sub-block: small blocks stay monolithic (nothing to
    overlap), bigger ones pipeline in 8-row (one f32 sublane) sub-blocks."""
    if block_b <= 16 or block_b % 8:
        return block_b
    return 8


@functools.partial(
    jax.jit, static_argnames=("segments", "emit_edges", "block_b", "sub_b",
                              "row_source", "interpret"))
def ne_forces_gather_pallas(x, qid, nbr_idx, coef, alpha, *,
                            segments: tuple, emit_edges: tuple = None,
                            block_b: int = 128, sub_b: int = None,
                            row_source: str = "dma",
                            interpret: bool = False):
    """Index-taking segmented force kernel.

    Args:
      x: (N, d) embedding; read in HBM/ANY memory space one row DMA at a
        time (``row_source='dma'``), or packed and held in VMEM for the
        launch (``'vmem'``, see the block comment above).
      qid: (B,) int32 row ids of the points the forces act on.
      nbr_idx: (B, K) int32 neighbour ids, K = sum of segment sizes;
        clipped to [0, N) (callers zero invalid slots via ``coef``).
      coef: (B, K) f32 per-edge coefficients.
      alpha: traced scalar tail parameter.
      segments: static tuple of ``(mode, size)`` pairs partitioning the
        neighbour axis, mode in {'attraction', 'repulsion'}.
      emit_edges: static per-segment bools (default: all True); a False
        segment skips its (B, K_s, d) edge output entirely -- no HBM
        write for edges the caller would discard (e.g. negative samples,
        whose symmetric contribution is never scattered).
      sub_b: double-buffer sub-block size (must divide ``block_b``);
        default: 8-row sub-blocks for blocks > 16 rows.
      row_source: static 'dma' or 'vmem'; both give the same results.
    Returns (one entry per segment -- no packed buffers, so consumers
    never pay a concat/re-slice round-trip):
      aggs: tuple of (B, d) per-point aggregate forces,
      edges: tuple of (B, K_s, d) per-edge forces (for the scatter-free
        symmetrisation outside the kernel); ``None`` where
        ``emit_edges[s]`` is False,
      wsums: tuple of (B,) w partial sums (Z-hat estimator terms).
    """
    N, d = x.shape
    assert row_source in ("dma", "vmem"), row_source
    vmem = row_source == "vmem"
    x = pack_rows(x) if vmem else pad_lanes(x)
    dp = x.shape[1]
    B, K = nbr_idx.shape
    S = len(segments)
    if emit_edges is None:
        emit_edges = (True,) * S
    assert len(emit_edges) == S, (emit_edges, segments)
    assert K == sum(size for _, size in segments), (K, segments)
    assert all(mode in ("attraction", "repulsion") for mode, _ in segments)
    assert all(size > 0 for _, size in segments), segments

    qid = jnp.clip(qid.astype(jnp.int32), 0, N - 1)
    nbr_idx = jnp.clip(nbr_idx.astype(jnp.int32), 0, N - 1)
    coef = coef.astype(jnp.float32)

    block_b = min(block_b, _round_up(B, 8))
    if sub_b is None:
        sub_b = _pick_sub_b(block_b)
    assert block_b % sub_b == 0, (block_b, sub_b)
    while block_b > 8 and 2 * (K + 1) * min(sub_b, block_b) * dp \
            * x.dtype.itemsize > 8 * 2 ** 20:
        block_b //= 2
        # a halved block_b may no longer be a multiple of sub_b: every row
        # of a block must land in some sub-block, so re-derive a divisor
        sub_b = math.gcd(sub_b, block_b)
    Bp = _round_up(B, block_b)
    if Bp != B:
        qid = jnp.pad(qid, (0, Bp - B))
        nbr_idx = jnp.pad(nbr_idx, ((0, Bp - B), (0, 0)))
        coef = jnp.pad(coef, ((0, Bp - B), (0, 0)))
    alpha_arr = jnp.asarray(alpha, jnp.float32).reshape(1, 1)

    if vmem:
        # ids again as VMEM blocks (lane offsets for the unpack); the
        # packed table is one block fetched once and held for the launch
        row_args = (qid.reshape(-1, 1), nbr_idx, x)
        row_specs = [pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
                     pl.BlockSpec((block_b, K), lambda i: (i, 0)),
                     pl.BlockSpec(x.shape, lambda i: (0, 0),
                                  pipeline_mode=pl.Buffered(1))]
        scratch = [pltpu.VMEM((sub_b, dp), x.dtype),
                   pltpu.VMEM((sub_b, K, dp), x.dtype)]
        limit = {"vmem_limit_bytes": VMEM_ROWS_LIMIT}
    else:
        row_args = (x,)
        row_specs = [pl.BlockSpec(memory_space=pl.ANY)]
        scratch = [pltpu.VMEM((2, sub_b, dp), x.dtype),
                   pltpu.VMEM((2, sub_b, K, dp), x.dtype),
                   pltpu.SemaphoreType.DMA((2,))]
        limit = {}
    qid, qid_spec = smem_ids(qid, block_b)
    grid = (Bp // block_b,)
    emitted_sizes = [size for (_, size), em in zip(segments, emit_edges)
                     if em]
    E = len(emitted_sizes)
    call = pl.pallas_call(
        functools.partial(_ne_forces_gather_kernel, segments=segments,
                          emit_edges=emit_edges, sub_b=sub_b, d=d,
                          row_source=row_source),
        grid=grid,
        in_specs=[
            qid_spec,
            pl.BlockSpec((block_b, K), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_b, K), lambda i: (i, 0)),
        ] + row_specs,
        out_specs=(
            [pl.BlockSpec((block_b, d), lambda i: (i, 0))] * S
            + [pl.BlockSpec((block_b, size, d), lambda i: (i, 0, 0))
               for size in emitted_sizes]
            + [pl.BlockSpec((block_b, 1), lambda i: (i, 0))] * S
        ),
        out_shape=(
            [jax.ShapeDtypeStruct((Bp, d), jnp.float32)] * S
            + [jax.ShapeDtypeStruct((Bp, size, d), jnp.float32)
               for size in emitted_sizes]
            + [jax.ShapeDtypeStruct((Bp, 1), jnp.float32)] * S
        ),
        scratch_shapes=scratch,
        # one independent row block per grid step: Mosaic may split the
        # sweep across TensorCores (each core keeps its own scratch)
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",), **limit),
        interpret=interpret,
    )
    outs = call(qid, nbr_idx, alpha_arr, coef, *row_args)
    aggs = tuple(o[:B] for o in outs[:S])
    edge_iter = iter(outs[S:S + E])
    edges = tuple(next(edge_iter)[:B] if em else None for em in emit_edges)
    wsums = tuple(o[:B, 0] for o in outs[S + E:])
    return aggs, edges, wsums


# --------------------------------------------------------------------------
# Scatter-fused epilogue.
#
# The gather-fused kernel above still *returns* per-edge forces so the
# caller can symmetrise them (buf.at[nbr].add(-edge)) -- two (B, K, d)
# HBM round-trips per step that exist only to feed an XLA scatter.  This
# variant folds the symmetrisation into the kernel: each edge's force is
# accumulated straight into a per-segment (N, d) displacement field
# (+edge at the query row, -edge at the neighbour row for symmetrised
# segments), binned by index with serialised VMEM read-modify-writes, so
# no (B, K_s, d) edge tensor is ever written to HBM.
#
# Segment scale factors (attraction/repulsion/negative-sampling weights)
# stay *outside*: the repulsion scale depends on the Z estimator, which
# is computed from this very launch's wsums, so the kernel returns raw
# per-segment fields and the caller combines them with traced scalars.
#
# VMEM: the field of one *N-chunk* of ``chunk_n`` target rows per segment
# stays resident (lane-padded, (chunk_n, 128) f32) while the inner grid
# axis sweeps every row block and accumulates into it; the outer axis
# walks the chunks.  Each (chunk, block) step re-stages the block's rows,
# replays the (vectorised) tail-power math into VMEM scratch and bins the
# edges whose target falls inside the chunk.  ops.py picks ``chunk_n`` so
# the slabs fit the VMEM budget (see ``scatter_chunk_plan``): N only
# raises the chunk count.


def _ne_forces_scatter_kernel(qid_ref, nbr_ref, alpha_ref, coef_ref, x_ref,
                              *refs, segments: tuple, scatter_back: tuple,
                              chunk_n: int):
    """qid (1, bb) SMEM; nbr (bb, K) SMEM; alpha (1,1) SMEM; coef (bb, K)
    VMEM; x (N, dp) ANY lane-padded -> per segment s: scat (chunk_n, dp)
    resident field of N-chunk c, wsum (bb, 1); then scratch (q_scr,
    n_scr, edge_scr, agg_scr, sem)."""
    S = len(segments)
    scat_refs = refs[:S]
    wsum_refs = refs[S:2 * S]
    q_scr, n_scr, edge_scr, agg_scr, sem = refs[2 * S:]
    block_b = n_scr.shape[0]
    c, i = pl.program_id(0), pl.program_id(1)
    off = c * chunk_n

    @pl.when(i == 0)
    def _zero():         # a chunk's field accumulates over every row block
        for sc in scat_refs:
            sc[...] = jnp.zeros_like(sc)

    _dma_query_and_neighbour_rows(x_ref, qid_ref, nbr_ref, q_scr, n_scr, sem)
    alpha = alpha_ref[0, 0]
    y = q_scr[...].astype(jnp.float32)              # (bb, dp)
    nbr = n_scr[...].astype(jnp.float32)            # (bb, K, dp)
    coef = coef_ref[...].astype(jnp.float32)        # (bb, K)

    def in_chunk(t):
        return (t >= off) & (t < off + chunk_n)

    def accumulate(sc, k0, size, back):
        # Index-binned accumulation: serialised read-modify-writes handle
        # duplicate targets (negatives / shared neighbours) exactly; the
        # chunk guard keeps every write inside this step's (chunk_n, dp)
        # slab.
        def nbr_body(r):
            def body(k, _):
                t = nbr_ref[r, k0 + k]

                @pl.when(in_chunk(t))
                def _bin():
                    sc[pl.ds(t - off, 1)] -= edge_scr[r, pl.ds(k, 1)]
                return _
            jax.lax.fori_loop(0, size, body, None)

        def row_body(r, _):
            q = qid_ref[0, r]

            @pl.when(in_chunk(q))
            def _bin():
                sc[pl.ds(q - off, 1)] += agg_scr[pl.ds(r, 1)]
            if back:
                nbr_body(r)
            return _

        jax.lax.fori_loop(0, block_b, row_body, None)

    k0 = 0
    for s, (mode, size) in enumerate(segments):
        sl = slice(k0, k0 + size)
        edge, wsum = _edge_wsum(nbr[:, sl] - y[:, None, :], coef[:, sl],
                                alpha, mode)
        wsum_refs[s][...] = wsum[:, None]
        edge_scr[:, :size] = edge
        agg_scr[...] = jnp.sum(edge, axis=1)
        accumulate(scat_refs[s], k0, size, scatter_back[s])
        k0 += size


# scoped-VMEM limit of the scatter kernel: its resident fields outgrow
# Mosaic's 16 MiB default; a TPU v5e core has 128 MiB of VMEM
SCATTER_VMEM_LIMIT = 96 * 2 ** 20


@functools.partial(
    jax.jit, static_argnames=("segments", "scatter_back", "block_b",
                              "chunk_n", "interpret"))
def ne_forces_scatter_pallas(x, qid, nbr_idx, coef, alpha, *,
                             segments: tuple, scatter_back: tuple = None,
                             block_b: int = 64, chunk_n: int = None,
                             interpret: bool = False):
    """Scatter-fused segmented force kernel (see block comment above).

    Args match :func:`ne_forces_gather_pallas` except:
      scatter_back: static per-segment bools (default: all True); True
        segments accumulate each edge's reaction force (-edge) into the
        neighbour's row (the symmetrisation); False segments (e.g.
        negative samples) contribute only the query-side aggregate.
      chunk_n: target rows binned per grid step (default: all N in one
        chunk).  The resident per-segment slab is (chunk_n, d) (lane-
        padded), so ``chunk_n`` bounds VMEM regardless of N.
    Returns:
      scats: tuple of (N, d) f32 per-segment displacement fields --
        scats[s][i] carries every force this launch exerts on point i
        through segment s.  No (B, K_s, d) edge tensor is ever written to
        HBM.
      wsums: tuple of (B,) w partial sums (Z-hat estimator terms).
    """
    N, d = x.shape
    x = pad_lanes(x)
    dp = x.shape[1]
    B, K = nbr_idx.shape
    S = len(segments)
    if scatter_back is None:
        scatter_back = (True,) * S
    assert len(scatter_back) == S, (scatter_back, segments)
    assert K == sum(size for _, size in segments), (K, segments)
    assert all(mode in ("attraction", "repulsion") for mode, _ in segments)
    assert all(size > 0 for _, size in segments), segments
    if chunk_n is None:
        chunk_n = N
    chunk_n = min(chunk_n, N)
    assert chunk_n >= 1, chunk_n

    qid = jnp.clip(qid.astype(jnp.int32), 0, N - 1)
    nbr_idx = jnp.clip(nbr_idx.astype(jnp.int32), 0, N - 1)
    coef = coef.astype(jnp.float32)

    k_max = max(size for _, size in segments)
    block_b = min(block_b, _round_up(B, 8))
    # staged rows + per-segment edge/agg scratch, all lane-padded
    while block_b > 8 and (K + k_max + 2) * block_b * dp * 4 > 4 * 2 ** 20:
        block_b //= 2
    Bp = _round_up(B, block_b)
    if Bp != B:
        # padded rows carry coef 0 -> exact-zero contributions to row qid[0]
        qid = jnp.pad(qid, (0, Bp - B))
        nbr_idx = jnp.pad(nbr_idx, ((0, Bp - B), (0, 0)))
        coef = jnp.pad(coef, ((0, Bp - B), (0, 0)))
    alpha_arr = jnp.asarray(alpha, jnp.float32).reshape(1, 1)

    qid, qid_spec = smem_ids(qid, block_b, grid_axis=1)
    G = Bp // block_b
    Np = _round_up(N, chunk_n)
    n_chunks = Np // chunk_n
    outs = pl.pallas_call(
        functools.partial(_ne_forces_scatter_kernel, segments=segments,
                          scatter_back=scatter_back, chunk_n=chunk_n),
        # chunk-outer: a chunk's field stays resident across the inner
        # sweep over row blocks, which must therefore run in order
        grid=(n_chunks, G),
        in_specs=[
            qid_spec,
            pl.BlockSpec((block_b, K), lambda c, i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda c, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_b, K), lambda c, i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            [pl.BlockSpec((chunk_n, dp), lambda c, i: (c, 0))] * S
            + [pl.BlockSpec((block_b, 1), lambda c, i: (i, 0))] * S
        ),
        out_shape=(
            [jax.ShapeDtypeStruct((Np, dp), jnp.float32)] * S
            + [jax.ShapeDtypeStruct((Bp, 1), jnp.float32)] * S
        ),
        scratch_shapes=[
            pltpu.VMEM((block_b, dp), x.dtype),
            pltpu.VMEM((block_b, K, dp), x.dtype),
            pltpu.VMEM((block_b, k_max, dp), jnp.float32),
            pltpu.VMEM((block_b, dp), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=SCATTER_VMEM_LIMIT),
        interpret=interpret,
    )(qid, nbr_idx, alpha_arr, coef, x)
    scats = tuple(o[:N, :d] for o in outs[:S])
    wsums = tuple(o[:B, 0] for o in outs[S:])
    return scats, wsums
