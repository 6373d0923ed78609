"""Pallas TPU kernel: blocked squared Euclidean distances.

This is the per-iteration hot spot of FUnc-SNE's iterative KNN: for every
point we score C candidate neighbours against the point's HD vector,
``out[b, j] = ||q[b] - c[b, j]||^2``.

TPU adaptation of the paper's GPU code (which assigns one CUDA thread per
(point, candidate) pair and loops over M serially): we tile the feature
dimension M into VMEM-resident blocks and accumulate partial squared
distances across a second grid axis, so HBM traffic is one pass over q and c
and arithmetic runs on 8x128 VPU lanes.  Grid: (B/block_b, M/block_m) with the
M axis innermost ("arbitrary" semantics -> sequential revisit of the same
output block, enabling accumulation).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params


def _sqdist_kernel(q_ref, c_ref, out_ref):
    """One (block_b, block_m) tile: accumulate partial squared distances."""
    m_idx = pl.program_id(1)

    q = q_ref[...].astype(jnp.float32)          # (block_b, block_m)
    c = c_ref[...].astype(jnp.float32)          # (block_b, C, block_m)
    diff = q[:, None, :] - c                    # (block_b, C, block_m)
    partial = jnp.sum(diff * diff, axis=-1)     # (block_b, C)

    @pl.when(m_idx == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(m_idx > 0)
    def _acc():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("block_b", "block_m", "interpret"))
def pairwise_sqdist_pallas(
    q: jnp.ndarray,
    c: jnp.ndarray,
    *,
    block_b: int = 256,
    block_m: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """(B, M), (B, C, M) -> (B, C) float32 squared distances.

    Pads B up to ``block_b`` and M up to ``block_m``; zero-padding of M is
    exact (contributes 0 to the sum), padded B rows are dropped.
    """
    B, M = q.shape
    Bc, C, Mc = c.shape
    assert Bc == B and Mc == M, (q.shape, c.shape)

    block_b = min(block_b, _round_up(B, 8))
    block_m = min(block_m, _round_up(M, 128))
    Bp = _round_up(B, block_b)
    Mp = _round_up(M, block_m)
    if (Bp, Mp) != (B, M):
        q = jnp.pad(q, ((0, Bp - B), (0, Mp - M)))
        c = jnp.pad(c, ((0, Bp - B), (0, 0), (0, Mp - M)))

    grid = (Bp // block_b, Mp // block_m)
    out = pl.pallas_call(
        _sqdist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_m), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, C, block_m), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, C), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, C), jnp.float32),
        interpret=interpret,
    )(q, c)
    return out[:B]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


LANES = 128


def pad_lanes(x):
    """Zero-pad the minor dim of ``x`` to a multiple of the 128-lane tile.

    Mosaic DMAs only lane-aligned row slices out of HBM, so every source
    matrix a row-gather kernel reads is stored lane-padded.  Zero columns
    leave squared distances and force deltas unchanged; an aligned ``x``
    is returned as is (callers that pad once up front pay nothing here).
    """
    pad = -x.shape[-1] % LANES
    if not pad:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def smem_ids(ids, block_b: int, grid_axis: int = 0):
    """(Bp,) int32 row ids -> ((Bp / block_b, 1, block_b) array, SMEM spec).

    1-D int32 operands get a different tiling from XLA than from Mosaic,
    so id vectors travel as one row per row block; the kernel reads
    ``ref[0, r]``.  ``grid_axis`` is the grid axis that walks row blocks.
    """
    return ids.reshape(-1, 1, block_b), pl.BlockSpec(
        (None, 1, block_b), lambda *g: (g[grid_axis], 0, 0),
        memory_space=pltpu.SMEM)


# --------------------------------------------------------------------------
# Resident packed tables, shared by the row-gather kernels.
#
# A row-gather kernel that DMAs one lane-padded row per id is bound by
# neither bytes nor arithmetic at small widths: a v5e spends ~33-45 ns per
# 512-byte row DMA.  At d <= ``VMEM_ROWS_MAX_D`` a whole (N, d) table fits
# VMEM once packed lane-dense (``pack_rows``: 128 points per row, one
# 128-lane tile per coordinate -- the table's own HBM tiling, so packing
# is a pad and one cheap copy), so it stays resident for the launch.  Each
# id then costs one on-core load of its packed row (row ``id >> 7``,
# addressed from SMEM), and a one-hot select on lane ``id & 127`` moves
# the point's d coordinates to lanes 0..d-1 (``unpack_rows``): the math
# sees exactly the rows the DMA path stages.

ROW_SHIFT = LANES.bit_length() - 1      # point id -> its packed row

# Largest packed tables one launch holds in VMEM, all of its resident
# tables together: a d=2 embedding up to n = 2^21, d=4 up to 2^20.
# Wider or larger tables keep one HBM row DMA per id.
VMEM_ROWS_BUDGET = 16 * 2 ** 20
VMEM_ROWS_MAX_D = 4

# scoped-VMEM limit of a launch that holds resident tables: they outgrow
# Mosaic's 16 MiB default; a TPU v5e core has 128 MiB of VMEM
VMEM_ROWS_LIMIT = 64 * 2 ** 20


def pack_rows(x):
    """(N, d) -> (R, d*128) f32, R = N/128 rounded up to the 8-row tile.

    Point i's coordinate c sits at row ``i >> 7``, lane
    ``c*128 + (i & 127)``; padding rows are zero.  On a TPU an (N, d)
    f32 array is stored in (d, 128)-point tiles already, so this is a
    pad and one cheap copy (an interleaved (N*d/128, 128) packing is a
    relayout that XLA takes ~20 s to compile at N=70,000).
    """
    n, d = x.shape
    x = jnp.pad(x.astype(jnp.float32), ((0, -n % (8 * LANES)), (0, 0)))
    return x.reshape(-1, LANES, d).transpose(0, 2, 1).reshape(-1, d * LANES)


def packed_bytes(n: int, d: int) -> int:
    """Bytes of :func:`pack_rows` of an (n, d) table."""
    return _round_up(n, 8 * LANES) * d * 4


def unpack_rows(rows, lane, d: int):
    """Packed rows (..., d*128) of points at lane ``lane`` (..., 1) of
    each 128-lane tile -> (..., 128) with the point's d coordinates at
    lanes 0..d-1, zeros elsewhere.  One-hot selects: every coordinate
    comes out exactly."""
    shape = rows.shape[:-1] + (LANES,)
    ll = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    hit = ll == lane
    out = jnp.zeros(shape, rows.dtype)
    for c in range(d):
        v = jnp.sum(jnp.where(hit, rows[..., c * LANES:(c + 1) * LANES],
                              0.0), axis=-1, keepdims=True)
        out = jnp.where(ll == c, v, out)
    return out


def row_source(n: int, d: int) -> str:
    """Where a row-gather kernel reads the rows of an (n, d) table:
    ``'vmem'`` (resident packed table) when d is small and the packing
    fits ``VMEM_ROWS_BUDGET``, else ``'dma'`` (one HBM row DMA per id).
    A function of the static shape alone."""
    if d <= VMEM_ROWS_MAX_D and packed_bytes(n, d) <= VMEM_ROWS_BUDGET:
        return "vmem"
    return "dma"


# --------------------------------------------------------------------------
# Gather-fused variant: the kernel takes *indices*, not gathered operands.
#
# The pre-gather kernel above forces XLA to materialise X[cand] as an
# (B, C, M) HBM buffer (C+1 copies of every touched row) which the kernel
# then streams from HBM a second time.  Here X stays in HBM/ANY memory and
# each (block_b, block_m) grid step DMAs only the block_b * (C+1) row chunks
# it needs straight into VMEM scratch: per-iteration HBM traffic drops from
# write+read of the gathered buffer to a single gather-read, and the (N,C,M)
# intermediate disappears from the memory high-water mark.
#
# The index slab is staged into SMEM by the pipeline (BlockSpec with
# memory_space=SMEM) so DMA source addresses are scalar reads; SMEM
# footprint is O(block_b * C), never O(B).
#
# Pipelining (two orthogonal levers):
#   * double-buffered b loop: the block's rows are processed in ``sub_b``
#     sub-blocks through 2-slot VMEM staging -- sub-block p+1's row DMAs
#     are issued *before* sub-block p is computed, so the serial
#     issue-all/drain-all/compute-all schedule (DMA latency fully exposed)
#     becomes DMA/compute overlap, and resident staging drops from
#     O(block_b * (C+1) * block_m) to O(2 * sub_b * (C+1) * block_m).
#   * persistent q: when M spans several ``block_m`` chunks, the q rows
#     of a block are DMA'd once (all chunks, issued at j == 0 on
#     per-chunk semaphores) into a (n_mchunks, block_b, block_m) resident
#     slab, saving one q-row DMA round per extra M-chunk; candidate rows
#     still stream per chunk (they are the C-fold bigger term).


def score_gather_block(qid_ref, gat_ref, x_ref, acc, q_scr, c_scr, q_sem,
                       c_sem, *, m_size: int, block_m: int, sub_b: int,
                       persistent_q: bool):
    """One (block_b, block_m) grid step of the row-gather scoring pipeline.

    DMAs the q row and the G gathered rows of each block row straight from
    ``x_ref`` (HBM/ANY) into VMEM staging and accumulates partial squared
    distances into ``acc`` across the M grid axis.  The single copy of the
    pipeline shared by ``pairwise_sqdist_gather`` and the merge-fused
    ``knn_merge`` kernel (which runs its selection epilogue on ``acc``
    after the final chunk).

    qid_ref: (1, block_b) SMEM      query row ids
    gat_ref: (block_b, G) SMEM      gathered (clipped) row ids
    x_ref: (N, M) ANY               lane-padded source matrix (stays in
                                    HBM)
    acc: (block_b, G) VMEM          squared-distance accumulator
                                    (output block or scratch)
    q_scr: (n_mchunks, block_b, block_m) if persistent_q
           else (2, sub_b, block_m) VMEM staging
    c_scr: (2, sub_b, G, block_m) VMEM double-buffer staging
    q_sem: (n_mchunks,) / c_sem: (2,) DMA semaphores
    """
    j = pl.program_id(1)
    block_b, G = acc.shape
    n_sub = block_b // sub_b
    # Ragged M: clamp each chunk's start so the DMA stays in bounds and
    # mask the columns the previous chunk already covered.
    def chunk_start(jc):
        return jnp.minimum(jc * block_m, m_size - block_m)

    m0 = chunk_start(j)

    if persistent_q:
        n_mchunks = q_scr.shape[0]

        def q_dma(jc, r):
            return pltpu.make_async_copy(
                x_ref.at[qid_ref[0, r], pl.ds(chunk_start(jc), block_m)],
                q_scr.at[jc, r], q_sem.at[jc])

        @pl.when(j == 0)
        def _issue_all_q():
            def per_chunk(jc, _):
                jax.lax.fori_loop(
                    0, block_b, lambda r, x: (q_dma(jc, r).start(), x)[1],
                    None)
                return _
            jax.lax.fori_loop(0, n_mchunks, per_chunk, None)

    def sub_copies(p, op):
        """Start/wait the 2-slot staged row DMAs of sub-block ``p``."""
        slot = p % 2

        def row(lr, _):
            r = p * sub_b + lr
            if not persistent_q:
                op(pltpu.make_async_copy(
                    x_ref.at[qid_ref[0, r], pl.ds(m0, block_m)],
                    q_scr.at[slot, lr], c_sem.at[slot]))
            jax.lax.fori_loop(
                0, G, lambda k, x: (op(pltpu.make_async_copy(
                    x_ref.at[gat_ref[r, k], pl.ds(m0, block_m)],
                    c_scr.at[slot, lr, k], c_sem.at[slot])), x)[1], None)
            return _

        jax.lax.fori_loop(0, sub_b, row, None)

    sub_copies(0, lambda cp: cp.start())
    if persistent_q:
        # drain this m-chunk's q rows (issued during j == 0) while the
        # first candidate sub-block is in flight
        jax.lax.fori_loop(0, block_b,
                          lambda r, x: (q_dma(j, r).wait(), x)[1], None)

    def body(p, _):
        slot = p % 2

        @pl.when(p + 1 < n_sub)
        def _prefetch():                     # overlap: copy p+1, compute p
            sub_copies(p + 1, lambda cp: cp.start())

        sub_copies(p, lambda cp: cp.wait())

        base = p * sub_b
        if persistent_q:
            q = q_scr[j, pl.ds(base, sub_b)].astype(jnp.float32)
        else:
            q = q_scr[slot].astype(jnp.float32)     # (sub_b, block_m)
        c = c_scr[slot].astype(jnp.float32)         # (sub_b, G, block_m)
        diff = q[:, None, :] - c
        col = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 2)
        fresh = (m0 + col) >= j * block_m           # not already accumulated
        partial = jnp.sum(jnp.where(fresh, diff * diff, 0.0), axis=-1)

        @pl.when(j == 0)
        def _init():
            acc[pl.ds(base, sub_b)] = partial

        @pl.when(j > 0)
        def _acc():
            acc[pl.ds(base, sub_b)] += partial

        return _

    jax.lax.fori_loop(0, n_sub, body, None)


def _pick_sub_b(block_b: int) -> int:
    """Largest-throughput sub-block that divides ``block_b``: small blocks
    stay monolithic (nothing to overlap), bigger ones pipeline in 8-row
    (one f32 sublane tile) sub-blocks."""
    if block_b <= 16 or block_b % 8:
        return block_b
    return 8


def plan_row_gather(B, M, G, itemsize, *, block_b, block_m, sub_b,
                    persistent_q, row_bytes=0):
    """Tiling plan for the row-gather scoring pipeline (shared with the
    merge-fused ``knn_merge`` kernel): resolves the block/sub-block sizes
    against the VMEM staging budget and the persistent-q heuristic.

    ``row_bytes`` is any further VMEM staging a kernel keeps per block
    row (the candidate-fused merge's activity rows); it is charged
    against the same budget as the row staging, so it shrinks
    ``block_b`` like a wide ``G`` does.

    Returns (block_b, block_m, sub_b, persistent_q, n_mchunks,
    q_scr_shape) with ``G`` gathered rows per block row.
    """
    block_m = min(block_m, M)
    block_b = min(block_b, _round_up(B, 8))
    if sub_b is None:
        sub_b = _pick_sub_b(block_b)
    assert block_b % sub_b == 0, (block_b, sub_b)
    # keep the 2-slot (G+1) row-chunk staging comfortably inside VMEM
    while block_b > 8 and 2 * min(sub_b, block_b) * (G + 1) * block_m \
            * itemsize + block_b * row_bytes > 8 * 2 ** 20:
        block_b //= 2
        # a halved block_b may no longer be a multiple of sub_b: every row
        # of a block must land in some sub-block, so re-derive a divisor
        sub_b = math.gcd(sub_b, block_b)
    n_mchunks = _round_up(M, block_m) // block_m
    if persistent_q is None:
        persistent_q = n_mchunks > 1 and n_mchunks * block_b * block_m \
            * itemsize <= 4 * 2 ** 20
    q_scr_shape = (n_mchunks, block_b, block_m) if persistent_q \
        else (2, sub_b, block_m)
    return block_b, block_m, sub_b, persistent_q, n_mchunks, q_scr_shape


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_m", "sub_b", "persistent_q",
                              "interpret"))
def pairwise_sqdist_gather_pallas(
    x: jnp.ndarray,
    qid: jnp.ndarray,
    cand: jnp.ndarray,
    *,
    block_b: int = 128,
    block_m: int = 512,
    sub_b: int = None,
    persistent_q: bool = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """(N, M), (B,), (B, C) -> (B, C) f32: ``||X[qid[b]] - X[cand[b,j]]||^2``.

    Indices are clipped to [0, N); callers mask invalid slots themselves
    (SENTINEL handling lives in the KNN merge).  B is padded to ``block_b``
    with row-0 gathers that are dropped on exit.  X is lane-padded
    (:func:`pad_lanes`, a no-op when M is a multiple of 128) and M is
    tiled at ``block_m`` with a clamped+masked final chunk.

    ``sub_b`` (must divide ``block_b``) sets the double-buffer sub-block;
    ``persistent_q`` keeps all M-chunks of the block's q rows VMEM-resident
    (auto: on when M spans >1 chunk and the slab stays under ~4MB).
    """
    x = pad_lanes(x)
    N, M = x.shape
    B, = qid.shape
    Bc, C = cand.shape
    assert Bc == B, (qid.shape, cand.shape)

    qid = jnp.clip(qid.astype(jnp.int32), 0, N - 1)
    cand = jnp.clip(cand.astype(jnp.int32), 0, N - 1)

    block_b, block_m, sub_b, persistent_q, n_mchunks, q_scr_shape = \
        plan_row_gather(B, M, C, x.dtype.itemsize, block_b=block_b,
                        block_m=block_m, sub_b=sub_b,
                        persistent_q=persistent_q)
    Bp = _round_up(B, block_b)
    if Bp != B:
        qid = jnp.pad(qid, (0, Bp - B))
        cand = jnp.pad(cand, ((0, Bp - B), (0, 0)))

    qid, qid_spec = smem_ids(qid, block_b)
    grid = (Bp // block_b, n_mchunks)
    out = pl.pallas_call(
        functools.partial(score_gather_block, m_size=M, block_m=block_m,
                          sub_b=sub_b, persistent_q=persistent_q),
        grid=grid,
        in_specs=[
            qid_spec,
            pl.BlockSpec((block_b, C), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_b, C), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, C), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM(q_scr_shape, x.dtype),
            pltpu.VMEM((2, sub_b, C, block_m), x.dtype),
            pltpu.SemaphoreType.DMA((n_mchunks,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # row blocks are independent (Mosaic may split them across
        # TensorCores); the M axis sequentially revisits the same output
        # block to accumulate partial distances, so it must stay serial
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qid, cand, x)
    return out[:B]
