"""Pallas TPU kernel: merge-fused neighbour-list refinement.

The per-iteration KNN refinement has three phases: score C candidate rows
against each query (``pairwise_sqdist_gather``), invalidate duplicates
(``knn_lib.dedup_candidates``), and merge the survivors into the resident
sorted (K,) neighbour list (``knn_lib.merge_knn``).  After PRs 1-3 fused
the scoring, the *selection* still ran as plain XLA: the dedup
materialises (n, C, K) and (n, C, C) broadcast-compare bool tensors in
HBM, the (n, C) candidate distances round-trip through HBM between the
kernel and the merge, and ``merge_knn`` pays a full ``lax.top_k`` sort
over (n, K+C) even though the resident side is already sorted.

This kernel extends the gather-fused scoring loop so each row block,
after accumulating candidate distances in VMEM, performs the dedup and
the top-K merge *in-register* and emits only the new (n, K) idx/d arrays
plus a per-row ``improved`` flag: no candidate-distance buffer, no dedup
broadcast tensor, and no sort anywhere in the step HLO.

The merge is a *stable-rank* selection (``merge_select``): every element
of the virtual [current, candidate] concatenation gets its output rank
from O((K+C)^2) vectorised compares (ties broken by concatenation index,
exactly ``lax.top_k``'s stable order -- and exactly what a sorted
insertion of the C candidates would produce), and rank-k elements are
gathered into slot k by one-hot masked sums.  This is the dense,
branch-free equivalent of NN-descent's per-candidate sorted-insertion
update (Dong et al.); on the 8x128 VPU the quadratic compare block
(<= (block_b, 42, 42) at config defaults) is register-resident noise next
to the row-gather DMAs the loop already pays.

Two modes share the kernel:
  * HD refinement: the stored sorted ``cur_d`` rides in as an operand and
    only the C candidate rows are gathered and scored.
  * LD refinement (``rescore=True``): the embedding moved since the list
    was built, so the kernel gathers and re-scores current *and*
    candidate rows in one sweep (the fused current+candidate split the
    XLA path used to do) and masks invalid current slots to +inf via
    ``cur_valid``.

Scoring IS the ``pairwise_sqdist_gather`` pipeline: ``score_gather_block``
and ``plan_row_gather`` are imported from that package (ONE copy of the
SMEM index slabs, 2-slot double-buffered sub-block row DMAs, persistent-q
slab and clamped+masked final M chunk), with the accumulator landing in a
(block_b, G) scratch instead of an output block.  Grid is
(B/block_b, M/block_m) with ``dimension_semantics=("parallel",
"arbitrary")``: row blocks are independent, the M axis sequentially
revisits the block's accumulator and runs the merge on its final chunk.
At small widths the candidate-fused kernel below scores from a packed
table resident in VMEM instead (``score_packed_block``), in one M chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params
from repro.kernels.pairwise_sqdist.kernel import (LANES, ROW_SHIFT,
                                                  VMEM_ROWS_LIMIT, _round_up,
                                                  pack_rows, pad_lanes,
                                                  plan_row_gather,
                                                  score_gather_block,
                                                  smem_ids, unpack_rows)

_SENTINEL = jnp.iinfo(jnp.int32).max


def merge_select(qid_col, cur_idx, cur_d, cand, cand_d, ext_valid):
    """In-register dedup + stable-rank top-K merge of one row block.

    Bit-reproduces ``knn_lib.dedup_candidates`` followed by
    ``knn_lib.merge_knn`` (whose ``lax.top_k`` breaks distance ties by
    concatenation index) as flat compare/select arithmetic: no sort, no
    dynamic gather, no (B, C, K) HBM tensor.  Shared by the Pallas kernel
    body and the ``knn_merge_rank_ref`` XLA implementation.

    Args:
      qid_col: (B, 1) int32 query row ids.
      cur_idx: (B, K) int32 resident neighbour ids (SENTINEL = invalid).
      cur_d: (B, K) f32 resident squared distances (+inf = invalid).
      cand: (B, C) int32 candidate ids (unclipped; SENTINEL = invalid).
      cand_d: (B, C) f32 candidate squared distances.
      ext_valid: (B, C) bool extra validity (e.g. active-row membership).
    Returns:
      (new_idx (B, K) int32, new_d (B, K) f32, improved (B,) bool).
    """
    _, k = cur_idx.shape
    c = cand.shape[1]
    i32 = jnp.int32

    def count(mask):                    # bool any() via i32 sum: TPU-safe
        return jnp.sum(mask.astype(i32), axis=-1)

    # ---- dedup (knn_lib.dedup_candidates semantics) ----
    self_dup = cand == qid_col
    in_cur = count(cand[:, :, None] == cur_idx[:, None, :]) > 0
    ci = jax.lax.broadcasted_iota(i32, (1, c, c), 1)
    cj = jax.lax.broadcasted_iota(i32, (1, c, c), 2)
    within = count((cand[:, :, None] == cand[:, None, :]) & (cj < ci)) > 0
    valid = ext_valid & ~(self_dup | in_cur | within | (cand == _SENTINEL))
    cand_d = jnp.where(valid, cand_d, jnp.inf)
    improved = count(cand_d < cur_d[:, k - 1:k]) > 0

    # ---- stable ranks over the virtual [cur, cand] concatenation ----
    # rank(e) = #{e': d[e'] < d[e]  or  (d[e'] == d[e] and e' before e)};
    # "before" is concatenation order, so cur always precedes cand and
    # within each side the original index decides -- lax.top_k's tie rule.
    cur_e = cur_d[:, :, None]           # element being ranked
    cand_e = cand_d[:, :, None]
    kk = jax.lax.broadcasted_iota(i32, (1, k, k), 1)
    kp = jax.lax.broadcasted_iota(i32, (1, k, k), 2)
    cur_vs_cur = (cur_d[:, None, :] < cur_e) \
        | ((cur_d[:, None, :] == cur_e) & (kp < kk))
    cand_vs_cur = cand_d[:, None, :] < cur_e          # cand never ties-first
    rank_cur = count(cur_vs_cur) + count(cand_vs_cur)
    cur_vs_cand = cur_d[:, None, :] <= cand_e         # cur always ties-first
    cand_vs_cand = (cand_d[:, None, :] < cand_e) \
        | ((cand_d[:, None, :] == cand_e) & (cj < ci))
    rank_cand = count(cur_vs_cand) + count(cand_vs_cand)

    # ---- one-hot rank -> slot selection (ranks >= K fall off the list) ----
    slot = jax.lax.broadcasted_iota(i32, (1, 1, k), 2)
    hit_cur = rank_cur[:, :, None] == slot            # (B, K, K)
    hit_cand = rank_cand[:, :, None] == slot          # (B, C, K)
    new_d = jnp.sum(jnp.where(hit_cur, cur_d[:, :, None], 0.0), axis=1) \
        + jnp.sum(jnp.where(hit_cand, cand_d[:, :, None], 0.0), axis=1)
    new_idx = jnp.sum(jnp.where(hit_cur, cur_idx[:, :, None], 0), axis=1) \
        + jnp.sum(jnp.where(hit_cand, cand[:, :, None], 0), axis=1)
    return new_idx.astype(i32), new_d, improved


def slice_rows(block_b: int) -> int:
    """Rows of one merge-epilogue slice: one f32 sublane tile, or the
    whole block where it is not a multiple of one."""
    return block_b if block_b % 8 else 8


def for_row_slices(block_b: int, fn):
    """Run ``fn(pl.ds(base, rows))`` over the row slices of a block.

    The merge's rank compares build (rows, K+C, K+C) intermediates; over a
    whole 128-row block they would outgrow VMEM, so the epilogue walks
    the block one f32 sublane tile at a time (``slice_rows``).
    """
    rows = slice_rows(block_b)

    def body(p, _):
        fn(pl.ds(pl.multiple_of(p * rows, rows), rows))
        return _

    jax.lax.fori_loop(0, block_b // rows, body, None)


def _knn_merge_kernel(qid_ref, gat_ref, cur_idx_ref, cand_ref, qid_v_ref,
                      curw_ref, candval_ref, x_ref, idx_out, d_out, imp_out,
                      acc, q_scr, c_scr, q_sem, c_sem, *, m_size: int,
                      block_m: int, sub_b: int, persistent_q: bool,
                      k_cur: int, rescore: bool):
    """One (block_b, block_m) tile: gather+score rows, merge on last chunk.

    qid_ref: (1, block_b) SMEM      query row ids (DMA addresses)
    gat_ref: (block_b, G) SMEM      clipped gather ids (G = C, or K+C when
                                    ``rescore``: [cur, cand] order)
    cur_idx_ref: (block_b, K) VMEM  unclipped resident ids (dedup compares)
    cand_ref: (block_b, C) VMEM     unclipped candidate ids
    qid_v_ref: (block_b, 1) VMEM    query ids (self-dedup compares)
    curw_ref: (block_b, K) VMEM     f32 cur_d (HD) / i32 cur_valid (rescore)
    candval_ref: (block_b, C) VMEM  i32 external candidate validity
    x_ref: (N, M) ANY               lane-padded source (stays in HBM)
    idx_out/d_out: (block_b, K)     merged neighbour list
    imp_out: (block_b, 1) i32       per-row improved flag
    acc: (block_b, G) VMEM          squared-distance accumulator scratch
    q_scr/c_scr/q_sem/c_sem         score_gather_block staging (G rows)
    """
    score_gather_block(qid_ref, gat_ref, x_ref, acc, q_scr, c_scr, q_sem,
                       c_sem, m_size=m_size, block_m=block_m, sub_b=sub_b,
                       persistent_q=persistent_q)
    j = pl.program_id(1)

    @pl.when(j == pl.num_programs(1) - 1)
    def _merge():
        def rows(sl):
            if rescore:
                cur_d = jnp.where(curw_ref[sl] != 0, acc[sl, :k_cur],
                                  jnp.inf)
                cand_d = acc[sl, k_cur:]
            else:
                cur_d = curw_ref[sl]
                cand_d = acc[sl]
            new_idx, new_d, improved = merge_select(
                qid_v_ref[sl], cur_idx_ref[sl], cur_d, cand_ref[sl], cand_d,
                candval_ref[sl] != 0)
            idx_out[sl] = new_idx
            d_out[sl] = new_d
            imp_out[sl] = improved.astype(jnp.int32)[:, None]

        for_row_slices(acc.shape[0], rows)


@functools.partial(
    jax.jit, static_argnames=("rescore", "block_b", "block_m", "sub_b",
                              "persistent_q", "interpret"))
def knn_merge_pallas(
    x: jnp.ndarray,
    qid: jnp.ndarray,
    cur_idx: jnp.ndarray,
    cur_w: jnp.ndarray,
    cand: jnp.ndarray,
    cand_valid: jnp.ndarray,
    *,
    rescore: bool,
    block_b: int = 128,
    block_m: int = 512,
    sub_b: int = None,
    persistent_q: bool = None,
    interpret: bool = False,
):
    """Merge-fused refinement: score, dedup and top-K merge in one launch.

    Args:
      x: (N, M) source matrix, kept in HBM/ANY memory space.
      qid: (B,) int32 query row ids (assumed in-range).
      cur_idx: (B, K) int32 resident neighbour ids; SENTINEL = invalid.
      cur_w: (B, K) -- the stored sorted squared distances (f32) in HD
        mode, or the current-slot validity mask (bool) when ``rescore``.
      cand: (B, C) int32 candidate ids (out-of-range ids are gathered
        clipped, exactly like the ref, and deduped on their raw value).
      cand_valid: (B, C) bool external validity (active-row membership).
      rescore: gather + re-score the current neighbours too (LD mode: the
        embedding moved since ``cur_idx`` was merged).
    Returns:
      (new_idx (B, K) int32, new_d (B, K) f32, improved (B,) bool).
    """
    x = pad_lanes(x)
    N, M = x.shape
    B, K = cur_idx.shape
    Bc, C = cand.shape
    assert Bc == B and qid.shape == (B,), (x.shape, qid.shape, cand.shape)
    assert cur_w.shape == (B, K), (cur_w.shape, cur_idx.shape)

    qid = qid.astype(jnp.int32)
    cur_idx = cur_idx.astype(jnp.int32)
    cand = cand.astype(jnp.int32)
    gat = jnp.clip(cand, 0, N - 1)
    if rescore:
        gat = jnp.concatenate([jnp.clip(cur_idx, 0, N - 1), gat], axis=1)
        cur_w = cur_w.astype(jnp.int32)       # validity mask travels as i32
    else:
        cur_w = cur_w.astype(jnp.float32)
    cand_valid = cand_valid.astype(jnp.int32)
    G = gat.shape[1]

    block_b, block_m, sub_b, persistent_q, n_mchunks, q_scr_shape = \
        plan_row_gather(B, M, G, x.dtype.itemsize, block_b=block_b,
                        block_m=block_m, sub_b=sub_b,
                        persistent_q=persistent_q)
    Bp = _round_up(B, block_b)
    if Bp != B:
        pad = Bp - B
        qid = jnp.pad(qid, (0, pad))
        cur_idx = jnp.pad(cur_idx, ((0, pad), (0, 0)))
        cand = jnp.pad(cand, ((0, pad), (0, 0)))
        gat = jnp.pad(gat, ((0, pad), (0, 0)))
        cur_w = jnp.pad(cur_w, ((0, pad), (0, 0)))
        cand_valid = jnp.pad(cand_valid, ((0, pad), (0, 0)))

    qid_s, qid_spec = smem_ids(qid, block_b)
    grid = (Bp // block_b, n_mchunks)
    outs = pl.pallas_call(
        functools.partial(_knn_merge_kernel, m_size=M, block_m=block_m,
                          sub_b=sub_b, persistent_q=persistent_q, k_cur=K,
                          rescore=rescore),
        grid=grid,
        in_specs=[
            qid_spec,
            pl.BlockSpec((block_b, G), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_b, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, C), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, C), lambda i, j: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((block_b, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, K), jnp.int32),
            jax.ShapeDtypeStruct((Bp, K), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, G), jnp.float32),
            pltpu.VMEM(q_scr_shape, x.dtype),
            pltpu.VMEM((2, sub_b, G, block_m), x.dtype),
            pltpu.SemaphoreType.DMA((n_mchunks,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qid_s, gat, cur_idx, cand, qid[:, None], cur_w, cand_valid, x)
    new_idx, new_d, imp = outs
    return new_idx[:B], new_d[:B], imp[:B, 0] != 0


# --------------------------------------------------------------------------
# Candidate-fused sampling (§Perf H17): the kernel *generates* the
# candidate slots it scores.
#
# After PR 4 the selection epilogue lived in-kernel but candidate
# *generation* still ran as plain XLA: per step, `sample_hops`
# materialised an (n, s, K2) two-hop gather broadcast in HBM, the
# threefry split/randint chain re-ran, and the resulting (n, C) candidate
# tensor round-tripped HBM just to be re-read by this kernel's SMEM
# slabs.  Here the candidates are *derived* inside the kernel from state
# it already stages:
#
#   * draws come from the counter-based hash RNG in ``repro.core.knn``
#     (``hash3(salt, row, draw)``): the identical int32 arithmetic runs
#     scalar-side (SMEM values -> DMA addresses) and vector-side (VPU
#     lanes -> the merge's dedup operands), and the pure-jnp reference
#     sampler (``knn_lib.counter_candidates``) is bit-exact against both;
#   * one-hop picks read the row's resident first-table slab
#     (SMEM for addresses, VMEM one-hot for the vector value);
#   * uniform probes are pure hash arithmetic;
#   * two-hop picks ``second[first[r, a], b]`` are resolved by the
#     wrapper with one flat (B, c) element gather (``knn_lib.two_hop_picks``,
#     no (B, c, K2) broadcast) and ride in like the precomputed "extra"
#     slots (e.g. the cached reverse-edge table): Mosaic DMAs only
#     lane-aligned rows, so a 4-byte chained element DMA cannot be used.
#
# Per-candidate ``active``-row flags come from a lane-dense (N/128, 128)
# packing of the mask (``pack_rows`` of the (N, 1) flags), and the
# candidate's flag is picked from the 128-flag row holding it with a
# one-hot lane select (``pick_flags``).  Where the rows come from is
# static (``flag_source``, chosen by ``ops.merge_sources`` from N alone):
#
#   * ``'dma'``: the table stays in HBM; the kernel DMAs each candidate's
#     flag row at generation time and awaits it just before the merge, so
#     the activity gather overlaps the scoring sweep;
#   * ``'vmem'``: the table is resident in VMEM for the launch, and each
#     merge slice loads its candidates' flag rows on-core.
#
# Rows are scored the same two ways (``row_source``): one lane-padded HBM
# row DMA per id through ``score_gather_block``, or, at small widths, on-
# core loads from a packed table resident in VMEM (``score_packed_block``,
# the force kernel's ``'vmem'`` row source).  Both stage the same
# lane-padded rows and feed them to the same distance arithmetic, so the
# outputs are identical.


def _slot_plan(sources):
    """Static per-slot layout of a ``sources`` tuple (see
    ``knn_lib.counter_candidates`` for the grammar).  Slot ``g`` draws
    the hash counters ``2g`` (a) and ``2g + 1`` (b)."""
    slots = []
    n_chain = n_extra = 0
    for src in sources:
        kind, c = src[0], src[-1]
        for _ in range(c):
            ent = {"kind": kind, "g": len(slots)}
            if kind == "one_hop":
                ent["f"] = src[1]
            elif kind == "two_hop":
                ent["t"] = n_chain
                n_chain += 1
            elif kind == "extra":
                ent["e"] = n_extra
                n_extra += 1
            elif kind != "uniform":
                raise ValueError(f"unknown candidate source {kind!r}")
            slots.append(ent)
    return slots, n_chain, n_extra


def pick_flags(flag_rows, cand, n_rows: int):
    """(rows, C, 128) packed flag rows of (rows, C) candidate ids -> the
    candidates' own flags, (rows, C) bool: lane ``clip(id) & 127``."""
    lane = jnp.clip(cand, 0, n_rows - 1) & (LANES - 1)
    ll = jax.lax.broadcasted_iota(jnp.int32, (1, 1, LANES), 2)
    return jnp.sum(jnp.where(lane[:, :, None] == ll, flag_rows, 0.0),
                   axis=2) != 0


def score_packed_block(qid_ref, gat_ref, qid_v_ref, gat_v_ref, x_ref, acc,
                       q_scr, c_scr, *, d: int):
    """Score a block's gathered rows against its queries from a resident
    packed table: ``score_gather_block``'s result without a row DMA.

    qid_ref: (1, block_b) SMEM      query row ids
    gat_ref: (block_b, G) SMEM      gathered (clipped) row ids
    qid_v_ref / gat_v_ref           the same ids as VMEM vectors (lanes)
    x_ref: (R, d*128) VMEM          ``pack_rows`` table
    acc: (block_b, G) VMEM          squared-distance accumulator
    q_scr: (sub_b, d*128) / c_scr: (sub_b, G, d*128) VMEM staging
    """
    block_b, G = acc.shape
    sub_b = q_scr.shape[0]

    def body(p, _):
        base = pl.multiple_of(p * sub_b, sub_b)

        def row(lr, _):
            r = base + lr
            q_scr[pl.ds(lr, 1)] = x_ref[pl.ds(qid_ref[0, r] >> ROW_SHIFT, 1)]
            for k in range(G):
                c_scr[lr, pl.ds(k, 1)] = x_ref[
                    pl.ds(gat_ref[r, k] >> ROW_SHIFT, 1)]
            return _

        jax.lax.fori_loop(0, sub_b, row, None)
        q_lane = qid_v_ref[pl.ds(base, sub_b)] & (LANES - 1)
        c_lane = gat_v_ref[pl.ds(base, sub_b)] & (LANES - 1)
        q = unpack_rows(q_scr[...], q_lane, d)              # (sub_b, 128)
        c = unpack_rows(c_scr[...], c_lane[:, :, None], d)  # (sub_b, G, 128)
        diff = q[:, None, :] - c
        acc[pl.ds(base, sub_b)] = jnp.sum(diff * diff, axis=-1)
        return _

    jax.lax.fori_loop(0, block_b // sub_b, body, None)


def _make_cand_kernel(*, sources, n_first, first_widths, have_chain,
                      have_extra, have_active, rescore, k_cur, n_rows,
                      m_size, block_m, sub_b, persistent_q, d, vmem_rows,
                      vmem_flags):
    """Build the kernel body for one static candidate-fused config."""
    from repro.core import knn as knn_lib   # deferred: core imports kernels

    slots, _, _ = _slot_plan(sources)
    koff = k_cur if rescore else 0

    def kernel(*refs):
        it = iter(refs)
        qid_ref = next(it)                          # (1, block_b) SMEM
        salt_ref = next(it)                         # (1, 1) SMEM
        first_s = [next(it) for _ in range(n_first)]
        chain_s = next(it) if have_chain else None
        extra_s = next(it) if have_extra else None
        curs_ref = next(it) if rescore else None    # clipped cur ids, SMEM
        cur_idx_ref = next(it)                      # (block_b, K) VMEM
        qid_v_ref = next(it)                        # (block_b, 1) VMEM
        curw_ref = next(it)                         # (block_b, K) VMEM
        first_v = [next(it) for _ in range(n_first)]
        chain_v = next(it) if have_chain else None
        extra_v = next(it) if have_extra else None
        # (R, 128) packed flags: ANY (dma) or VMEM (vmem)
        act_ref = next(it) if have_active else None
        # (N, M) lane-padded ANY (dma) or (R, d*128) packed VMEM (vmem)
        x_ref = next(it)
        idx_out, d_out, imp_out = next(it), next(it), next(it)
        acc = next(it)                              # (block_b, G) VMEM
        if vmem_rows:
            q_scr, c_scr, gat_v = next(it), next(it), next(it)
        else:
            q_scr, c_scr, q_sem, c_sem = (next(it), next(it), next(it),
                                          next(it))
        gat_smem = next(it)                         # (block_b, G) SMEM
        cand_vmem = next(it)                        # (block_b, C) VMEM
        if have_active and vmem_flags:
            flag_scr = next(it)                     # (rows, C, 128)
        elif have_active:
            act_rows, act_sem = next(it), next(it)  # (block_b, C, 128)

        j = pl.program_id(1)
        block_b = acc.shape[0]
        salt = salt_ref[0, 0]

        def sdraw(row, draw, bound):
            """Scalar counter draw (bit-identical to the vector path)."""
            h = knn_lib.hash3(salt, row, jnp.int32(draw))
            return (h & knn_lib._POS_MASK) % bound

        def act_copy(r, g):
            row = jax.lax.shift_right_logical(gat_smem[r, koff + g], 7)
            return pltpu.make_async_copy(act_ref.at[row], act_rows.at[r, g],
                                         act_sem)

        @pl.when(j == 0)
        def _generate():
            def fill_row(r, _):
                row = qid_ref[0, r]
                if rescore:
                    def cp_cur(k, _):
                        gat_smem[r, k] = curs_ref[r, k]
                        return _
                    jax.lax.fori_loop(0, k_cur, cp_cur, None)
                for ent in slots:             # static unroll
                    kind, g = ent["kind"], ent["g"]
                    if kind == "uniform":
                        v = sdraw(row, 2 * g, n_rows)
                    elif kind == "one_hop":
                        a = sdraw(row, 2 * g, first_widths[ent["f"]])
                        v = first_s[ent["f"]][r, a]
                    elif kind == "two_hop":
                        v = chain_s[r, ent["t"]]
                    else:                     # extra
                        v = extra_s[r, ent["e"]]
                    gat_smem[r, koff + g] = jnp.clip(v, 0, n_rows - 1)
                    if have_active and not vmem_flags:
                        act_copy(r, g).start()
                return _
            jax.lax.fori_loop(0, block_b, fill_row, None)

            # vector pass: the same draws on VPU lanes feed the merge's
            # dedup compares (raw ids, SENTINELs preserved)
            rows_v = qid_v_ref[...]                      # (block_b, 1)
            if vmem_rows and rescore:      # clipped ids: unpack lanes
                gat_v[:, :k_cur] = jnp.clip(cur_idx_ref[...], 0, n_rows - 1)
            g0 = 0
            for src in sources:
                kind, c = src[0], src[-1]
                sl = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) + g0
                if kind == "uniform":
                    blk = knn_lib.counter_randint(salt, rows_v, 2 * sl,
                                                  n_rows)
                elif kind == "one_hop":
                    tab = first_v[src[1]][...]
                    a = knn_lib.counter_randint(salt, rows_v, 2 * sl,
                                                tab.shape[1])
                    kk = jax.lax.broadcasted_iota(
                        jnp.int32, (1, 1, tab.shape[1]), 2)
                    blk = jnp.sum(jnp.where(a[:, :, None] == kk,
                                            tab[:, None, :], 0), axis=2)
                elif kind == "two_hop":
                    t0 = next(e["t"] for e in slots if e["g"] == g0)
                    blk = chain_v[:, t0:t0 + c]
                else:                                     # extra
                    e0 = next(e["e"] for e in slots if e["g"] == g0)
                    blk = extra_v[:, e0:e0 + c]
                cand_vmem[:, g0:g0 + c] = blk.astype(jnp.int32)
                if vmem_rows:
                    gat_v[:, koff + g0:koff + g0 + c] = jnp.clip(
                        blk.astype(jnp.int32), 0, n_rows - 1)
                g0 += c

        if vmem_rows:
            score_packed_block(qid_ref, gat_smem, qid_v_ref, gat_v, x_ref,
                               acc, q_scr, c_scr, d=d)
        else:
            score_gather_block(qid_ref, gat_smem, x_ref, acc, q_scr, c_scr,
                               q_sem, c_sem, m_size=m_size, block_m=block_m,
                               sub_b=sub_b, persistent_q=persistent_q)

        @pl.when(j == pl.num_programs(1) - 1)
        def _merge():
            if have_active and not vmem_flags:
                def drain(r, _):
                    for ent in slots:
                        act_copy(r, ent["g"]).wait()
                    return _
                jax.lax.fori_loop(0, block_b, drain, None)

            def flag_rows(sl):
                """(rows, C, 128) flag rows of the slice's candidates."""
                if not vmem_flags:
                    return act_rows[sl]

                def load(lr, _):
                    r = sl.start + lr
                    for ent in slots:
                        g = ent["g"]
                        flag_scr[lr, pl.ds(g, 1)] = act_ref[
                            pl.ds(gat_smem[r, koff + g] >> ROW_SHIFT, 1)]
                    return _
                jax.lax.fori_loop(0, sl.size, load, None)
                return flag_scr[...]

            def rows(sl):
                cand = cand_vmem[sl]
                if have_active:
                    ext_valid = pick_flags(flag_rows(sl), cand, n_rows)
                else:
                    # all-true, computed (a literal bool array would be a
                    # captured kernel constant)
                    ext_valid = cand == cand
                if rescore:
                    cur_d = jnp.where(curw_ref[sl] != 0, acc[sl, :k_cur],
                                      jnp.inf)
                    cand_d = acc[sl, k_cur:]
                else:
                    cur_d = curw_ref[sl]
                    cand_d = acc[sl]
                new_idx, new_d, improved = merge_select(
                    qid_v_ref[sl], cur_idx_ref[sl], cur_d, cand, cand_d,
                    ext_valid)
                idx_out[sl] = new_idx
                d_out[sl] = new_d
                imp_out[sl] = improved.astype(jnp.int32)[:, None]

            for_row_slices(block_b, rows)

    return kernel


def _chain_picks(salt, qid, sources, first_tables, second_tables):
    """(B, n_chain) values of the two-hop slots, in slot order."""
    from repro.core import knn as knn_lib   # deferred: core imports kernels

    rows_c = qid[:, None]
    parts, g0 = [], 0
    for src in sources:
        c = src[-1]
        if src[0] == "two_hop":
            slots = g0 + jnp.arange(c, dtype=jnp.int32)[None, :]
            parts.append(knn_lib.two_hop_picks(
                salt, rows_c, slots, first_tables[src[1]],
                second_tables[src[2]]))
        g0 += c
    return jnp.concatenate(parts, axis=1).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("sources", "rescore", "block_b", "block_m",
                              "sub_b", "persistent_q", "row_source",
                              "flag_source", "interpret"))
def knn_merge_cand_pallas(
    x: jnp.ndarray,
    qid: jnp.ndarray,
    cur_idx: jnp.ndarray,
    cur_w: jnp.ndarray,
    salt,
    first_tables=(),
    second_tables=(),
    extra=None,
    active=None,
    *,
    sources,
    rescore: bool,
    block_b: int = 128,
    block_m: int = 512,
    sub_b: int = None,
    persistent_q: bool = None,
    row_source: str = "dma",
    flag_source: str = "dma",
    interpret: bool = False,
):
    """Candidate-fused refinement: sample, score, dedup and merge in ONE
    launch (§Perf H17).

    Args mirror :func:`knn_merge_pallas` except that the (B, C) candidate
    operand is replaced by its *generator*: ``salt`` (int32 counter-RNG
    salt), ``sources`` (static layout, see ``knn_lib.counter_candidates``),
    ``first_tables`` (tuple of (B, Kf) resident slabs), ``second_tables``
    (tuple of (N2, K2) tables for the two-hop picks) and optional
    ``extra`` precomputed slots.  ``active`` is the global (N,) bool
    membership mask (None == all rows active): per-candidate flags are
    read in-kernel, matching ``active[clip(cand)]`` on the ref.
    ``row_source`` / ``flag_source`` (static 'dma' or 'vmem', see the
    block comment above) say where rows and flags are read from; every
    combination gives the same results.
    """
    assert row_source in ("dma", "vmem"), row_source
    assert flag_source in ("dma", "vmem"), flag_source
    N, d = x.shape
    vmem_rows = row_source == "vmem"
    x = pack_rows(x) if vmem_rows else pad_lanes(x)
    M = x.shape[1]
    B, K = cur_idx.shape
    # zero-width sources are legal in the grammar but contribute no
    # slots; drop them here so the static slot plan and the vector-pass
    # offsets only ever see populated sources (slot/draw numbering is
    # unchanged -- empty sources never advanced it)
    sources = tuple(s for s in sources if s[-1] > 0)
    slots, n_chain, n_extra = _slot_plan(sources)
    C = len(slots)
    assert C > 0, "cand-fused merge needs at least one candidate source"
    have_chain = n_chain > 0
    have_extra = n_extra > 0
    if have_extra:
        assert extra is not None and extra.shape == (B, n_extra), \
            (n_extra, None if extra is None else extra.shape)
    have_active = active is not None
    vmem_flags = have_active and flag_source == "vmem"
    G = C + (K if rescore else 0)

    qid = qid.astype(jnp.int32)
    cur_idx = cur_idx.astype(jnp.int32)
    salt = jnp.asarray(salt, jnp.int32).reshape(1, 1)
    first_tables = tuple(f.astype(jnp.int32) for f in first_tables)
    second_tables = tuple(s.astype(jnp.int32) for s in second_tables)
    cur_w = cur_w.astype(jnp.int32 if rescore else jnp.float32)
    if rescore:
        curs = jnp.clip(cur_idx, 0, N - 1)
    if have_chain:
        chain = _chain_picks(salt[0, 0], qid, sources, first_tables,
                             second_tables)
    if have_extra:
        extra = extra.astype(jnp.int32)
    if have_active:
        act = pack_rows(active[:, None])

    # a packed table is scored whole: one M chunk of the packed width
    block_b, block_m, sub_b, persistent_q, n_mchunks, q_scr_shape = \
        plan_row_gather(B, M, G, x.dtype.itemsize, block_b=block_b,
                        block_m=M if vmem_rows else block_m, sub_b=sub_b,
                        persistent_q=False if vmem_rows else persistent_q,
                        row_bytes=C * LANES * 4
                        if have_active and not vmem_flags else 0)
    Bp = _round_up(B, block_b)
    if Bp != B:
        pad = Bp - B
        qid = jnp.pad(qid, (0, pad))
        cur_idx = jnp.pad(cur_idx, ((0, pad), (0, 0)))
        cur_w = jnp.pad(cur_w, ((0, pad), (0, 0)))
        first_tables = tuple(jnp.pad(f, ((0, pad), (0, 0)))
                             for f in first_tables)
        if rescore:
            curs = jnp.pad(curs, ((0, pad), (0, 0)))
        if have_chain:
            chain = jnp.pad(chain, ((0, pad), (0, 0)))
        if have_extra:
            extra = jnp.pad(extra, ((0, pad), (0, 0)))

    def blk(width, space=None):
        kw = {} if space is None else {"memory_space": space}
        return pl.BlockSpec((block_b, width), lambda i, j: (i, 0), **kw)

    qid_s, qid_spec = smem_ids(qid, block_b)
    operands = [qid_s, salt]
    in_specs = [
        qid_spec,
        pl.BlockSpec((1, 1), lambda i, j: (0, 0), memory_space=pltpu.SMEM),
    ]
    # SMEM slabs (scalar reads -> DMA addresses), then their VMEM twins
    # (vector reads -> the merge's dedup operands)
    slabs = list(first_tables) + ([chain] if have_chain else []) \
        + ([extra] if have_extra else [])
    for t in slabs:
        operands.append(t)
        in_specs.append(blk(t.shape[1], pltpu.SMEM))
    if rescore:
        operands.append(curs)
        in_specs.append(blk(K, pltpu.SMEM))
    operands += [cur_idx, qid[:, None], cur_w]
    in_specs += [blk(K), blk(1), blk(K)]
    for t in slabs:
        operands.append(t)
        in_specs.append(blk(t.shape[1]))

    def table(t, resident):
        # a resident table is one block fetched once and held for the
        # launch; otherwise it stays in HBM for in-kernel row DMAs
        if resident:
            return pl.BlockSpec(t.shape, lambda i, j: (0, 0),
                                pipeline_mode=pl.Buffered(1))
        return pl.BlockSpec(memory_space=pl.ANY)

    if have_active:
        operands.append(act)
        in_specs.append(table(act, vmem_flags))
    operands.append(x)
    in_specs.append(table(x, vmem_rows))

    scratch = [pltpu.VMEM((block_b, G), jnp.float32)]
    if vmem_rows:
        scratch += [pltpu.VMEM((sub_b, M), x.dtype),
                    pltpu.VMEM((sub_b, G, M), x.dtype),
                    pltpu.VMEM((block_b, G), jnp.int32)]
    else:
        scratch += [pltpu.VMEM(q_scr_shape, x.dtype),
                    pltpu.VMEM((2, sub_b, G, block_m), x.dtype),
                    pltpu.SemaphoreType.DMA((n_mchunks,)),
                    pltpu.SemaphoreType.DMA((2,))]
    scratch += [pltpu.SMEM((block_b, G), jnp.int32),
                pltpu.VMEM((block_b, C), jnp.int32)]
    if vmem_flags:
        scratch.append(pltpu.VMEM((slice_rows(block_b), C, LANES),
                                  act.dtype))
    elif have_active:
        scratch += [pltpu.VMEM((block_b, C, LANES), act.dtype),
                    pltpu.SemaphoreType.DMA(())]
    limit = {"vmem_limit_bytes": VMEM_ROWS_LIMIT} \
        if vmem_rows or vmem_flags else {}

    kernel = _make_cand_kernel(
        sources=sources, n_first=len(first_tables),
        first_widths=tuple(f.shape[1] for f in first_tables),
        have_chain=have_chain, have_extra=have_extra,
        have_active=have_active, rescore=rescore, k_cur=K, n_rows=N,
        m_size=M, block_m=block_m, sub_b=sub_b, persistent_q=persistent_q,
        d=d, vmem_rows=vmem_rows, vmem_flags=vmem_flags)

    grid = (Bp // block_b, n_mchunks)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        name="knn_merge_cand",
        in_specs=in_specs,
        out_specs=[blk(K), blk(K), blk(1)],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, K), jnp.int32),
            jax.ShapeDtypeStruct((Bp, K), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        scratch_shapes=scratch,
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"), **limit),
        interpret=interpret,
    )(*operands)
    new_idx, new_d, imp = outs
    return new_idx[:B], new_d[:B], imp[:B, 0] != 0
