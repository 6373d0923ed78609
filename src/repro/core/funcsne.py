"""FUnc-SNE: fast, unconstrained neighbour embedding (paper Sec. 3).

One ``funcsne_step`` fuses, in a single fixed-shape XLA/TPU program:

  1. stochastic HD neighbour refinement (prob 0.05 + 0.95 E[N_new/N]),
     candidates drawn from HD/LD neighbours-of-neighbours + cross-space
     + uniform probes (the joint iterative KNN),
  2. flag-driven perplexity (sigma_i) refresh with warm restart,
  3. systematic LD neighbour refinement,
  4. variable-tail forces: attraction over the HD set, repulsion over the
     LD set (the paper's novel middle term of Eq. 6) + negative-sampling
     far field with an EMA'd Z estimator,
  5. t-SNE-style gains/momentum update of the embedding.

Hyperparameters that the paper exposes interactively (alpha, perplexity,
attraction/repulsion ratio, lr, exaggeration) are *traced scalars*
(``HParams``) so changing them never recompiles -- the headless equivalent
of the paper's instant-GUI-feedback property.

Driver surface (one traced step, three dispatch granularities):

  ``make_step(cfg)``
      jitted single-device ``step(st, X, hp) -> st``; one dispatch per
      iteration (interactive GUIs that must see every frame).
  ``make_chunked_step(cfg, T, schedule=, n_iter=, snapshot_every=)``
      jitted ``chunk(st, X, hp) -> (st, snaps, ChunkMetrics)``: T
      iterations inside ONE ``lax.scan`` device program (§Perf H15) --
      the hyperparameter schedule runs on device from the carried
      ``st.step``, snapshots land in a device-side ``(n_snap, n, d)``
      ring, and per-step scalars are EMA'd into one ChunkMetrics sync
      per chunk.  ``fit`` and ``launch/embed.py`` run on this.
  ``make_distributed_step(cfg, mesh, ..., chunk=None)``
      the same two contracts under ``shard_map``: ``chunk=None`` keeps
      the classic one-step program, ``chunk=T`` the scan-chunked one.
  ``fit(..., resilience=ResiliencePolicy(...), resume_from=dir)``
      the resilient outer loop on the chunked driver: the chunk scan
      folds health telemetry into :class:`ChunkMetrics` (finite fraction
      of Y over active rows, max |Y|, first bad step -- zero extra host
      syncs), a tripped probe rolls back to the last healthy chunk
      boundary and retries with backed-off lr/exaggeration (bounded,
      then ``EmbeddingDiverged``), the full state checkpoints through
      ``repro.checkpoint`` for bit-deterministic resume, Pallas launch
      failures can (opt-in) demote per kernel family to the XLA refs
      (``repro.kernels.fallback``), and ``repro.runtime.faults`` injects
      every one of those failures deterministically in tests/CI.

The step has one wiring.  Every per-step draw (refinement gate, HD and LD
candidates, negatives, reverse-edge fill) comes from the counter-based
hash RNG (§H17), so the step carries no threefry.  HD and LD refinement
are each one ``knn_merge`` launch that generates, scores, dedups and
merges its candidates (§H16/H17); the forces are one segmented
``ne_forces_gather`` launch (§H12/H13).  One fork remains, chosen from
what the step can observe: under feature-axis sharding (``ctx.feat``)
HD refinement needs the psum'd full distances, so it generates the
candidates with the jnp sampler, scores them with
``pairwise_sqdist_gather`` + psum and merges them in XLA.
``cfg.scatter_fused`` picks the force epilogue (§H14, off by default).
``cfg.backend``: 'auto' (pallas on TPU else xla; a device that fails to
initialise raises) | 'pallas' | 'interpret' | 'xla' (the pure-jnp refs).

Distribution: inside ``shard_map`` the embedding state
is replicated; each device owns a contiguous row slice per phase
(KNN phases: the ``points`` axes; force phase: points x feat axes) and the
slices are reassembled with tiled all-gathers / a single force psum.  The HD
feature dimension is sharded over the ``feat`` axis and squared distances
are psum'd -- tensor parallelism for the NE.  Passing ``ctx=AxisCtx()``
(no axes) yields the single-device program, so both paths share this code.

§Perf notes (H-series; inline comments reference these ids):
  H10a  force psum crosses the wire in bf16 (f32 local accumulation);
        negative-sampling noise dominates the bf16 rounding error.
  H10b  ld_d is never all-gathered: it is re-derived from Y at the next
        refinement, so cross-chip transport is pure waste.
  H11   squared HD distances cross the wire in bf16 (merge thresholds and
        the sigma solve tolerate ~0.4% relative error).
  H12   gather-fused kernels: ``pairwise_sqdist_gather`` /
        ``ne_forces_gather`` take *indices* and DMA only the needed rows
        inside the kernel (X/Y stay in HBM), instead of XLA materialising
        (n, C, M) / (n, K, d) gathered operands in HBM per launch and the
        kernel streaming them back a second time.  Applies to
        ``init_state``, HD candidate scoring under feature-axis sharding,
        and the force phase (the merge kernel reads its rows the same
        way).
  H13   single force launch: HD attraction + LD repulsion + negatives run
        as static segments of ONE ``ne_forces_gather`` call over the
        concatenated neighbour axis -- one read of Y and one launch where
        there were three of each; per-segment outputs avoid any
        concat/re-slice round-trip at the call site.
  H14   scatter-fused force epilogue: the symmetrisation (each directed
        edge acting on both endpoints) is accumulated *inside* the force
        kernel into per-segment (N, d) displacement-field partials, so
        the per-edge (n, K, d) force tensors and the ``.at[tgt].add``
        scatters that consumed them vanish -- the step's last per-edge
        HBM round-trip.  Off by default (``cfg.scatter_fused``): the
        edge-emitting epilogue + XLA scatters run instead.
  H15   scan-chunked driver: T iterations per dispatch via ``lax.scan``
        with a donated state carry -- host->device dispatch cost, the
        per-step hyperparameter upload (schedule evaluated from the
        carried ``st.step``), per-step ``device_get`` snapshots (device
        ring buffer) and per-step metric syncs (EMA'd ChunkMetrics) all
        amortise to 1/T.  Chunk boundaries are bit-exactly neutral
        (chunk(a) then chunk(b) == chunk(a+b)); a handful of
        ``optimization_barrier``\\ s pin scalar EMA/schedule rounding so
        the traced chunk tracks the eager host loop it replaced.
  H17   candidate-fused sampling: the candidate slots are derived
        *inside* the merge kernel from state it already stages: a
        counter-based hash RNG keyed on (step salt, global row, slot) --
        splittable and order/shard-invariant, with a bit-exact pure-jnp
        reference in ``core/knn.py`` -- plus chained element DMAs
        through the neighbour tables for the two-hop sources.  The
        refinement gate and the negatives draw from the same counter
        stream, so no threefry survives anywhere in the step HLO.
        Cached reverse edges (``rev_refresh``) ride in as precomputed
        "extra" slots.
  H16   merge-fused neighbour selection: after the gather kernel has the
        candidate distances in VMEM, the dedup (self / current-list /
        earlier-candidate / SENTINEL) and the sorted top-K insertion run
        *in-register* and only the new (n, K) idx/d lists + a per-row
        ``improved`` flag leave the kernel -- the (n, C) distance buffer,
        the (n, C, K)/(n, C, C) dedup broadcast tensors and
        ``merge_knn``'s ``lax.top_k`` sort vanish from the step HLO.
        Applies to HD refinement (stored sorted distances ride in) and LD
        refinement (current rows re-scored in the same sweep).  With the
        scan-chunked driver the removed epilogue would otherwise run T
        times per dispatch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import affinities
from repro.core import knn as knn_lib
from repro.core.knn import SENTINEL
from repro.core.resilience import EmbeddingDiverged, ResiliencePolicy
from repro.kernels import fallback
from repro.kernels.knn_merge.ops import knn_merge
from repro.kernels.ne_forces.ops import ne_forces_gather
from repro.kernels.pairwise_sqdist.ops import pairwise_sqdist_gather
from repro.runtime import faults


# --------------------------------------------------------------------------
# Configuration


@dataclasses.dataclass(frozen=True)
class FuncSNEConfig:
    """Static configuration (hashable -> jit static arg)."""
    n_points: int                 # capacity; dynamic datasets use `active`
    dim_hd: int
    dim_ld: int = 2
    k_hd: int = 32
    k_ld: int = 16
    # HD candidate sources per iteration (paper Sec. 3)
    c_hd_non: int = 4             # HD neighbours-of-neighbours
    c_hd_ld: int = 2              # LD neighbours proposed cross-space
    c_hd_ld_non: int = 2          # LD neighbours-of-neighbours cross-space
    c_hd_rand: int = 2            # uniform probes
    c_hd_rev: int = 0             # reverse edges (off by default; NND uses it)
    # LD candidate sources
    c_ld_non: int = 4
    c_ld_hd: int = 2              # HD neighbours as stable LD candidates
    c_ld_rand: int = 2
    n_negatives: int = 16
    sigma_refresh_every: int = 10
    min_refresh_prob: float = 0.05
    ema_decay: float = 0.9        # for E[N_new / N]
    z_ema_decay: float = 0.9
    backend: str = "auto"         # kernels backend
    # Locked to True (checked in __post_init__): the step has one wiring
    # and no phase reads these.  They stay only because bench/configs/*.json
    # still pass the keys; a benchmark change drops the keys, then the
    # fields go.
    gather_fused: bool = True
    # scatter-fused force epilogue (§Perf H14): symmetrisation edges are
    # accumulated in-kernel into (N, d) fields; False keeps the
    # edge-emitting kernel + XLA ``.at[].add`` scatters.
    # Off by default: on a TPU the lane-padded fields fit VMEM only in
    # N-chunks, and every chunk re-stages all rows and replays a serial
    # per-edge bin loop, so at n=65536 it would be the slowest phase.
    scatter_fused: bool = False
    merge_fused: bool = True      # locked, see gather_fused
    cand_fused: bool = True       # locked, see gather_fused
    # refresh cadence of the cached reverse-edge table (used when
    # c_hd_rev > 0): the n*K-edge argsort rebuild runs every rev_refresh
    # steps instead of at every HD refinement; 1 == a rebuild at every
    # refinement, bit-for-bit.
    rev_refresh: int = 10

    def __post_init__(self):
        for name in ("gather_fused", "merge_fused", "cand_fused"):
            if not getattr(self, name):
                raise ValueError(
                    f"{name}=False selected a step path that was removed; "
                    f"the field only accepts True")

    @property
    def c_hd(self) -> int:
        return (self.c_hd_non + self.c_hd_ld + self.c_hd_ld_non
                + self.c_hd_rand + self.c_hd_rev)

    @property
    def c_ld(self) -> int:
        return self.c_ld_non + self.c_ld_hd + self.c_ld_rand


class HParams(NamedTuple):
    """Traced hyperparameters -- change any of these without recompiling."""
    alpha: Any
    perplexity: Any
    lr: Any
    momentum: Any
    attraction: Any
    repulsion: Any
    exaggeration: Any


def default_hparams(n: int, *, alpha=1.0, perplexity=30.0, lr=None,
                    momentum=0.8, attraction=1.0, repulsion=1.0,
                    exaggeration=1.0) -> HParams:
    if lr is None:
        lr = max(50.0, n / 12.0)   # openTSNE-style default
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    return HParams(f32(alpha), f32(perplexity), f32(lr), f32(momentum),
                   f32(attraction), f32(repulsion), f32(exaggeration))


class AxisCtx(NamedTuple):
    """Mesh axis names; all None -> single-device execution."""
    points: Optional[tuple] = None    # axes sharding KNN-phase rows
    feat: Optional[str] = None        # axis sharding the HD feature dim

    @property
    def all_rows(self) -> Optional[tuple]:
        if self.points is None:
            return None
        return self.points + ((self.feat,) if self.feat else ())


class FuncSNEState(NamedTuple):
    Y: Any          # (N, d_ld)
    vel: Any        # (N, d_ld)
    gains: Any      # (N, d_ld)
    hd_idx: Any     # (N, k_hd) int32, sorted by hd_d ascending
    hd_d: Any       # (N, k_hd) f32 squared HD distances
    ld_idx: Any     # (N, k_ld) int32
    ld_d: Any       # (N, k_ld) f32 squared LD distances
    beta: Any       # (N,) 1/(2 sigma_i^2)
    new_flag: Any   # (N,) bool -- new HD neighbour since last sigma refresh
    active: Any     # (N,) bool -- dynamic-dataset membership
    ema_new_frac: Any   # () f32
    zhat: Any       # () f32 EMA'd Z estimator
    step: Any       # () i32
    rng: Any        # PRNG key
    rev_idx: Any = ()   # (N, c_hd_rev) cached reverse edges ((N, 0) when
    #                     unused; refreshed every cfg.rev_refresh steps)
    rev_step: Any = ()  # () i32 step of the last reverse-edge refresh
    #                     (refinement runs behind a stochastic gate, so
    #                     cadence is since-last-refresh, not step % k --
    #                     a gate-skipped refresh step must not be lost)


# Counter-RNG stream tags (§Perf H17): per-step salts are
# hash3(key_salt(st.rng), st.step, TAG), one disjoint stream per phase.
_TAG_GATE, _TAG_HD, _TAG_LD, _TAG_NEG, _TAG_REV = 1, 2, 3, 4, 5


# --------------------------------------------------------------------------
# Helpers


def _phase_rows(n: int, axes):
    """(start, n_local) of this device's contiguous row slice for a phase."""
    if axes is None:
        return jnp.int32(0), n
    n_shards = jax.lax.psum(1, axes)
    idx = jax.lax.axis_index(axes)
    n_local = n // n_shards
    return (idx * n_local).astype(jnp.int32), n_local


def _gather_rows(full, axes):
    """Reassemble per-device row slices into the full array."""
    if axes is None:
        return full
    return jax.lax.all_gather(full, axes, axis=0, tiled=True)


def _take(arr, idx):
    """Gather rows with SENTINEL-safe clipping."""
    return arr[jnp.clip(idx, 0, arr.shape[0] - 1)]


def _row_sqdist(X, ids, cand, ctx: AxisCtx, cfg: "FuncSNEConfig"):
    """Squared HD distances rows->candidates, psum over the feature axis.

    The kernel receives indices and DMAs rows of X in-kernel, so the
    (n_loc, C, M) gathered operand never hits HBM; each feature shard
    computes partial squared distances over its local M slice.
    """
    d = pairwise_sqdist_gather(X, ids, cand, backend=cfg.backend)
    if ctx.feat is not None:
        d = jax.lax.psum(d, ctx.feat)
    return d


def _phase_scope(scope: str):
    """Trace the decorated phase function under ``jax.named_scope(scope)``.

    The scope is HLO metadata only (``op_name=".../<scope>/..."`` on every
    instruction the phase emits, in ``lax.cond`` branch bodies too): it
    changes no op, fusion or layout, and lets a device trace attribute
    each op to its phase.  Applied to the function itself, so every entry
    point (``make_step``, ``make_chunked_step``,
    ``make_distributed_step``) carries it.
    """
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# --------------------------------------------------------------------------
# Phase 1: HD neighbour refinement


def _rev_update(cfg: FuncSNEConfig, st: FuncSNEState, fill):
    """Refresh the cached reverse-edge table once ``cfg.rev_refresh``
    steps have passed since the last rebuild: the argsort over all n*K
    directed edges leaves the per-iteration path.  The cadence is
    *since-last-refresh* (``st.rev_step``), not ``step % k``: refinement
    itself runs behind a stochastic gate, so an absolute-modulo schedule
    would silently drop every refresh whose step the gate skipped and
    leave staleness unbounded.  ``rev_refresh=1`` == a rebuild at
    every refinement, bit-for-bit -- any later refinement
    satisfies the >= 1 condition, the same ``fill`` protocol feeds
    ``reverse_neighbors``, and the cache is overwritten before use."""
    n = cfg.n_points
    rev, rstep = jax.lax.cond(
        st.step - st.rev_step >= cfg.rev_refresh,
        lambda: (knn_lib.reverse_neighbors(st.hd_idx, n, cfg.c_hd_rev,
                                           fill=fill), st.step),
        lambda: (st.rev_idx, st.rev_step))
    return st._replace(rev_idx=rev, rev_step=rstep)


@_phase_scope("funcsne.hd_refine")
def _hd_refine(cfg: FuncSNEConfig, st: FuncSNEState, X, rng, ctx: AxisCtx):
    n = cfg.n_points
    start, n_loc = _phase_rows(n, ctx.points)
    ids = start + jnp.arange(n_loc, dtype=jnp.int32)
    hd_l = jax.lax.dynamic_slice_in_dim(st.hd_idx, start, n_loc)
    hd_d_l = jax.lax.dynamic_slice_in_dim(st.hd_d, start, n_loc)
    ld_l = jax.lax.dynamic_slice_in_dim(st.ld_idx, start, n_loc)

    # §Perf H17: all draws from the counter RNG, keyed on *global* row ids
    # -- no per-shard fold needed, the stream is shard-invariant by
    # construction.
    base = knn_lib.as_salt(rng)
    salt = knn_lib.hash3(base, st.step, _TAG_HD)
    rev_l = None
    if cfg.c_hd_rev:
        fill = knn_lib.counter_fill(
            knn_lib.hash3(base, st.step, _TAG_REV), n, cfg.c_hd_rev)
        st = _rev_update(cfg, st, fill)
        rev_l = jax.lax.dynamic_slice_in_dim(st.rev_idx, start, n_loc)
    sources = (("two_hop", 0, 0, cfg.c_hd_non),
               ("one_hop", 1, cfg.c_hd_ld),
               ("two_hop", 1, 1, cfg.c_hd_ld_non),
               ("uniform", cfg.c_hd_rand),
               ("extra", cfg.c_hd_rev))
    firsts, seconds = (hd_l, ld_l), (st.hd_idx, st.ld_idx)

    if ctx.feat is None:
        # §Perf H16 + H17: one launch generates the candidates (counter
        # RNG + chained two-hop DMAs), scores, dedups and merges them --
        # no (n, C) candidate or distance round-trip, no dedup broadcast
        # tensors, no top_k in the step HLO.
        new_idx, new_d, improved = knn_merge(
            X, ids, hd_l, hd_d_l, rev_l, backend=cfg.backend,
            sources=sources, salt=salt, first_tables=firsts,
            second_tables=seconds, active=st.active)
    else:
        # Feature-axis sharding: each shard holds a slice of X's columns,
        # so the merge must wait for the psum'd full distances -- the same
        # candidates from the jnp sampler, merged in XLA.
        cand = knn_lib.counter_candidates(salt, ids, sources, firsts,
                                          seconds, n_total=n, extra=rev_l)
        valid = knn_lib.dedup_candidates(ids, hd_l, cand)
        valid &= _take(st.active, cand)
        cand_d = _row_sqdist(X, ids, cand, ctx, cfg)
        new_idx, new_d, improved = knn_lib.merge_knn(hd_l, hd_d_l, cand,
                                                     cand_d, valid)

    hd_idx = _gather_rows(new_idx, ctx.points)
    if ctx.points is None:
        hd_d = new_d
    else:
        # §Perf H11: squared HD distances cross the wire in bf16 (merge
        # thresholds and the sigma solve tolerate ~0.4% relative error)
        hd_d = _gather_rows(new_d.astype(jnp.bfloat16), ctx.points)
        hd_d = hd_d.astype(jnp.float32)
    improved_f = _gather_rows(improved, ctx.points)
    new_flag = st.new_flag | improved_f
    n_act = jnp.maximum(jnp.sum(st.active.astype(jnp.float32)), 1.0)
    frac = jnp.sum((improved_f & st.active).astype(jnp.float32)) / n_act
    ema = cfg.ema_decay * st.ema_new_frac + (1.0 - cfg.ema_decay) * frac
    return st._replace(hd_idx=hd_idx, hd_d=hd_d, new_flag=new_flag,
                       ema_new_frac=ema)


# --------------------------------------------------------------------------
# Phase 2: sigma (beta) refresh for flagged rows


@_phase_scope("funcsne.sigma_refresh")
def _sigma_refresh(cfg: FuncSNEConfig, st: FuncSNEState, hp: HParams,
                   ctx: AxisCtx):
    start, n_loc = _phase_rows(cfg.n_points, ctx.all_rows)
    hd_d_l = jax.lax.dynamic_slice_in_dim(st.hd_d, start, n_loc)
    hd_i_l = jax.lax.dynamic_slice_in_dim(st.hd_idx, start, n_loc)
    beta_l = jax.lax.dynamic_slice_in_dim(st.beta, start, n_loc)
    flag_l = jax.lax.dynamic_slice_in_dim(st.new_flag, start, n_loc)
    valid = jnp.isfinite(hd_d_l) & (hd_i_l != SENTINEL)
    valid &= _take(st.active, hd_i_l)
    solved = affinities.solve_beta(hd_d_l, hp.perplexity, valid=valid,
                                   beta0=beta_l, n_iter=24)
    beta_l = jnp.where(flag_l, solved, beta_l)
    beta = _gather_rows(beta_l, ctx.all_rows)
    n = cfg.n_points
    cleared = jnp.zeros((n,), bool)
    return st._replace(beta=beta, new_flag=cleared)


# --------------------------------------------------------------------------
# Phase 3: LD neighbour refinement (every iteration)


@_phase_scope("funcsne.ld_refine")
def _ld_refine(cfg: FuncSNEConfig, st: FuncSNEState, rng, ctx: AxisCtx):
    n = cfg.n_points
    start, n_loc = _phase_rows(n, ctx.all_rows)
    ids = start + jnp.arange(n_loc, dtype=jnp.int32)
    ld_l = jax.lax.dynamic_slice_in_dim(st.ld_idx, start, n_loc)
    hd_l = jax.lax.dynamic_slice_in_dim(st.hd_idx, start, n_loc)

    # §Perf H16 + H17: one launch generates the candidates (counter RNG
    # keyed on global row ids), gathers + re-scores current AND candidate
    # rows (the embedding moved since the last merge), dedups and merges
    # in-register -- the whole LD selection epilogue is gone from the step
    # HLO.
    salt = knn_lib.hash3(knn_lib.as_salt(rng), st.step, _TAG_LD)
    sources = (("two_hop", 0, 0, cfg.c_ld_non),
               ("one_hop", 1, cfg.c_ld_hd),
               ("uniform", cfg.c_ld_rand))
    cur_valid = (ld_l != SENTINEL) & _take(st.active, ld_l)
    new_idx, new_d, _ = knn_merge(
        st.Y, ids, ld_l, None, None, cur_valid=cur_valid,
        backend=cfg.backend, sources=sources, salt=salt,
        first_tables=(ld_l, hd_l), second_tables=(st.ld_idx,),
        active=st.active)
    ld_idx = _gather_rows(new_idx, ctx.all_rows)
    if ctx.all_rows is None:
        ld_d = new_d
    else:
        # §Perf H10b: ld_d is re-derived from Y at the next refinement
        # (the embedding moves every step), so gathering it across chips
        # is pure wire waste; keep a local placeholder instead.
        ld_d = jnp.zeros_like(st.ld_d)
    return st._replace(ld_idx=ld_idx, ld_d=ld_d)


# --------------------------------------------------------------------------
# Phase 4: forces + embedding update


@_phase_scope("funcsne.forces_update")
def _forces_update(cfg: FuncSNEConfig, st: FuncSNEState, hp: HParams, rng,
                   ctx: AxisCtx):
    n, d = cfg.n_points, cfg.dim_ld
    start, n_loc = _phase_rows(n, ctx.all_rows)
    ids = start + jnp.arange(n_loc, dtype=jnp.int32)

    hd_i = jax.lax.dynamic_slice_in_dim(st.hd_idx, start, n_loc)
    hd_d = jax.lax.dynamic_slice_in_dim(st.hd_d, start, n_loc)
    ld_i = jax.lax.dynamic_slice_in_dim(st.ld_idx, start, n_loc)
    beta_l = jax.lax.dynamic_slice_in_dim(st.beta, start, n_loc)
    act_l = jax.lax.dynamic_slice_in_dim(st.active, start, n_loc)
    n_act = jnp.maximum(jnp.sum(st.active.astype(jnp.float32)), 2.0)

    # ---- attraction over the HD set:  coef = p_{j|i} / (2N)  (Eq. 1)
    hd_valid = jnp.isfinite(hd_d) & (hd_i != SENTINEL)
    hd_valid &= _take(st.active, hd_i)
    p = affinities.p_rows(hd_d, beta_l, valid=hd_valid)
    coef_a = jnp.where(hd_valid & act_l[:, None], p, 0.0) / (2.0 * n_act)

    # ---- repulsion over the LD set (paper's novel middle term of Eq. 6)
    # coef 0.5: each directed edge acts on both endpoints below, so mutual
    # LD pairs would otherwise be double-counted.
    ld_valid = (ld_i != SENTINEL) & _take(st.active, ld_i)
    coef_r = 0.5 * (ld_valid & act_l[:, None]).astype(jnp.float32)

    # ---- far-field via negative sampling (third term of Eq. 6)
    # n_negatives=0 drops the far field entirely (static config): used by
    # the momentum-conservation tests, where every edge is symmetrised.
    have_neg = cfg.n_negatives > 0
    if have_neg:
        # §Perf H17: counter-RNG negatives keyed on global row ids --
        # shard-invariant, no threefry in the HLO
        salt = knn_lib.hash3(knn_lib.as_salt(rng), st.step, _TAG_NEG)
        draws = jnp.arange(cfg.n_negatives, dtype=jnp.int32)[None, :]
        neg = knn_lib.counter_randint(salt, ids[:, None], draws, n)
        neg = jnp.where(neg == ids[:, None], (neg + 1) % n, neg)
        coef_n = (_take(st.active, neg) & act_l[:, None]).astype(jnp.float32)
        scale_neg = jnp.maximum(n_act - 1.0 - cfg.k_ld, 1.0) / cfg.n_negatives
    else:
        scale_neg = jnp.float32(0.0)

    # §Perf H13: ONE launch over the concatenated neighbour axis (static
    # segments: HD attraction, LD repulsion, negatives); rows of Y are
    # read in-kernel, so no gathered (n, K, d) buffer reaches HBM.
    nbr_idx = jnp.concatenate([hd_i, ld_i] + ([neg] if have_neg else []),
                              axis=1)
    coef = jnp.concatenate([coef_a, coef_r]
                           + ([coef_n] if have_neg else []), axis=1)
    segments = (("attraction", cfg.k_hd), ("repulsion", cfg.k_ld)) \
        + ((("repulsion", cfg.n_negatives),) if have_neg else ())
    if cfg.scatter_fused:
        # §Perf H14: the kernel bins every edge force (and its symmetric
        # reaction, except for negatives) straight into per-segment
        # (n, d) fields -- no per-edge output exists.
        scats, wsums = ne_forces_gather(
            st.Y, ids, nbr_idx, coef, hp.alpha, segments=segments,
            scatter_fused=True,
            scatter_back=(True, True) + ((False,) if have_neg else ()),
            backend=cfg.backend)
    else:
        # negatives' edges are never scattered back -> skip their HBM write
        emit = (True, True) + ((False,) if have_neg else ())
        aggs, edges, wsums = ne_forces_gather(st.Y, ids, nbr_idx, coef,
                                              hp.alpha, segments=segments,
                                              emit_edges=emit,
                                              backend=cfg.backend)
        agg_a, agg_r = aggs[0], aggs[1]
        agg_n = aggs[2] if have_neg else 0.0
        edge_a, edge_r = edges[0], edges[1]
    wsum_r = wsums[1]
    wsum_n = wsums[2] if have_neg else jnp.float32(0.0)

    # ---- Z estimator:  Z ~= sum_i [ sum_{j in LD_i} w_ij + scale * mean_neg ]
    # (x2 undoes the 0.5 symmetrisation coefficient baked into coef_r)
    # The barriers pin the mul-then-add rounding: without them the CPU
    # backend FMA-contracts these scalar a*x+b*y chains *differently*
    # inside a while/scan body than in straight-line code, so the chunked
    # driver would drift 1 ulp per step from T sequential dispatches and
    # break the scan==sequential bit-parity contract.
    wsum_r_m, wsum_n_m = jax.lax.optimization_barrier(
        (wsum_r, wsum_n if have_neg else jnp.float32(0.0)))
    z_local = sum(jax.lax.optimization_barrier(
        (2.0 * jnp.sum(wsum_r_m), scale_neg * jnp.sum(wsum_n_m))))
    z_est = (jax.lax.psum(z_local, ctx.all_rows)
             if ctx.all_rows is not None else z_local)
    z_est = jnp.maximum(z_est, 1e-8)
    zhat = jnp.where(st.step == 0, z_est,
                     sum(jax.lax.optimization_barrier(
                         (cfg.z_ema_decay * st.zhat,
                          (1.0 - cfg.z_ema_decay) * z_est))))

    # ---- assemble the displacement field (one (N, d) buffer + one psum)
    attr_s = hp.attraction * hp.exaggeration
    rep_s = hp.repulsion / zhat
    if cfg.scatter_fused:
        # §Perf H14: the kernel already binned edge + reaction forces by
        # row; the epilogue is three AXPYs on (n, d) partials -- the
        # ``.at[].add`` scatters below (and the edge tensors feeding
        # them) no longer exist.
        buf = attr_s * scats[0] + rep_s * scats[1]
        if have_neg:
            buf = buf + (rep_s * scale_neg) * scats[2]
    else:
        buf = jnp.zeros((n, d), jnp.float32)
        if have_neg:
            agg_q = attr_s * agg_a + rep_s * (agg_r + scale_neg * agg_n)
        else:
            agg_q = attr_s * agg_a + rep_s * agg_r
        buf = buf.at[ids].add(agg_q)
        # scatter-free symmetrisation: each directed edge acts on both
        # endpoints
        tgt_a = jnp.clip(hd_i, 0, n - 1).reshape(-1)
        buf = buf.at[tgt_a].add(-(attr_s * edge_a).reshape(-1, d))
        tgt_r = jnp.clip(ld_i, 0, n - 1).reshape(-1)
        buf = buf.at[tgt_r].add(-(rep_s * edge_r).reshape(-1, d))
    if ctx.all_rows is not None:
        # §Perf H10a: accumulate locally in f32, cross the wire in bf16
        # (the far field is negative-sampled: force noise >> bf16 error)
        buf = jax.lax.psum(buf.astype(jnp.bfloat16), ctx.all_rows)
        buf = buf.astype(jnp.float32)
    dY = 4.0 * buf

    # ---- t-SNE gains + momentum (replicated update)
    act = st.active[:, None]
    same = jnp.sign(dY) == jnp.sign(st.vel)
    gains = jnp.where(same, st.gains + 0.2, st.gains * 0.8)
    # upper clip: with stochastic (negative-sampled) forces, unbounded gains
    # turn sampling noise into diffusive expansion of the embedding
    gains = jnp.clip(gains, 0.01, 10.0)
    vel = hp.momentum * st.vel + hp.lr * gains * dY
    vel = jnp.where(act, vel, 0.0)
    Y = st.Y + vel
    return st._replace(Y=Y, vel=vel, gains=jnp.where(act, gains, st.gains),
                       zhat=zhat)


# --------------------------------------------------------------------------
# Full step


def funcsne_step(cfg: FuncSNEConfig, st: FuncSNEState, X, hp: HParams,
                 ctx: AxisCtx = AxisCtx()) -> FuncSNEState:
    """One fused FUnc-SNE iteration (see module docstring)."""
    return _step_flags(cfg, st, X, hp, ctx)[0]


def _step_flags(cfg: FuncSNEConfig, st: FuncSNEState, X, hp: HParams,
                ctx: AxisCtx):
    """:func:`funcsne_step`, also returning whether this step's HD
    refinement gate (``do_hd``) and sigma-refresh condition
    (``do_sigma``) fired, as () bool -- the chunk scan counts them into
    :class:`ChunkMetrics` without a second draw.  Both are drawn from
    replicated state, so every shard of a mesh sees the same flags."""
    # stochastic HD refinement: p = 0.05 + 0.95 E[N_new/N]  (paper Sec. 3)
    p_ref = cfg.min_refresh_prob + (1.0 - cfg.min_refresh_prob) \
        * st.ema_new_frac
    # §Perf H17: the state key is only *read* (its raw bits fold into one
    # int32 base salt), every draw this step -- gate, candidates,
    # negatives, reverse-edge fill -- is a counter hash of
    # (salt, step, tag, row, slot): zero threefry ops in the HLO.
    base = knn_lib.key_salt(st.rng)
    u = knn_lib.counter_uniform01(knn_lib.hash3(base, st.step, _TAG_GATE))
    do_hd = u < jnp.clip(p_ref, 0.0, 1.0)
    st = jax.lax.cond(do_hd,
                      lambda s: _hd_refine(cfg, s, X, base, ctx),
                      lambda s: s, st)

    do_sigma = (st.step % cfg.sigma_refresh_every == 0) \
        & jnp.any(st.new_flag)
    st = jax.lax.cond(do_sigma,
                      lambda s: _sigma_refresh(cfg, s, hp, ctx),
                      lambda s: s, st)

    st = _ld_refine(cfg, st, base, ctx)
    st = _forces_update(cfg, st, hp, base, ctx)
    return st._replace(step=st.step + 1), do_hd, do_sigma


# --------------------------------------------------------------------------
# Initialisation & drivers


def pca_directions(X, d: int, n_iter: int = 24, rng=None):
    """Top-d PCA directions via subspace (power) iteration (no scipy)."""
    if rng is None:
        rng = jax.random.PRNGKey(0)
    Xc = X - jnp.mean(X, axis=0, keepdims=True)
    W = jax.random.normal(rng, (X.shape[1], d), X.dtype)

    def body(_, W):
        W = Xc.T @ (Xc @ W)
        q, _ = jnp.linalg.qr(W)
        return q

    return jax.lax.fori_loop(0, n_iter, body, jnp.linalg.qr(W)[0])


def validate_inputs(X, cfg: FuncSNEConfig, *, check_finite: bool = True):
    """Fail fast with a clear ``ValueError`` instead of NaN embeddings.

    A single non-finite row in ``X`` poisons the squared-distance pass,
    the sigma solve and eventually every force -- the resulting NaN
    embedding surfaces hundreds of iterations later with no pointer back
    here.  ``check_finite`` costs one O(n*M) reduction + one host sync,
    once per ``fit`` (never per step).
    """
    X = jnp.asarray(X)
    if X.ndim != 2:
        raise ValueError(
            f"X must be a 2-D (n, dim_hd) array, got shape {X.shape}")
    if X.dtype.kind not in "fiu":
        raise ValueError(
            f"X must be real-numeric (float/int), got dtype {X.dtype}")
    if X.shape != (cfg.n_points, cfg.dim_hd):
        raise ValueError(
            f"X shape {X.shape} does not match cfg (n_points="
            f"{cfg.n_points}, dim_hd={cfg.dim_hd})")
    n = cfg.n_points
    for name, k in (("k_hd", cfg.k_hd), ("k_ld", cfg.k_ld)):
        if k >= n:
            raise ValueError(
                f"cfg.{name}={k} must be < n_points={n}: a row cannot "
                f"have {k} distinct neighbours among {n - 1} other points")
    if check_finite and X.dtype.kind == "f":
        bad = jnp.sum(~jnp.all(jnp.isfinite(X), axis=1))
        if int(bad):
            raise ValueError(
                f"X contains {int(bad)} row(s) with non-finite (NaN/inf) "
                f"entries; clean or drop them before embedding")


def init_state(rng, X, cfg: FuncSNEConfig, *, init: str = "pca",
               active=None, Y0=None, perplexity=30.0,
               validate: bool = True) -> FuncSNEState:
    n, d = cfg.n_points, cfg.dim_ld
    if validate:
        validate_inputs(X, cfg)
    r_y, r_hd, r_ld, r_state = jax.random.split(rng, 4)
    if Y0 is not None:
        Y = jnp.asarray(Y0, jnp.float32)
    elif init == "pca":
        W = pca_directions(X, d, rng=r_y)
        Y = (X - jnp.mean(X, axis=0)) @ W
        Y = Y / jnp.maximum(jnp.std(Y), 1e-8) * 1e-2
    else:
        Y = jax.random.normal(r_y, (n, d)) * 1e-2
    Y = Y.astype(jnp.float32)
    if active is None:
        active = jnp.ones((n,), bool)

    ids = jnp.arange(n, dtype=jnp.int32)
    hd_idx = knn_lib.init_knn_idx(r_hd, n, n, cfg.k_hd)
    hd_d = pairwise_sqdist_gather(X, ids, hd_idx, backend=cfg.backend)
    hd_d = jnp.where(active[hd_idx] & active[:, None], hd_d, jnp.inf)
    order = jnp.argsort(hd_d, axis=1)
    hd_idx = jnp.take_along_axis(hd_idx, order, axis=1)
    hd_d = jnp.take_along_axis(hd_d, order, axis=1)

    ld_idx = knn_lib.init_knn_idx(r_ld, n, n, cfg.k_ld)
    ld_d = pairwise_sqdist_gather(Y, ids, ld_idx, backend=cfg.backend)
    ld_d = jnp.where(active[ld_idx] & active[:, None], ld_d, jnp.inf)

    beta = affinities.solve_beta(hd_d, perplexity, n_iter=24)
    return FuncSNEState(
        Y=Y, vel=jnp.zeros((n, d), jnp.float32),
        gains=jnp.ones((n, d), jnp.float32),
        hd_idx=hd_idx.astype(jnp.int32), hd_d=hd_d,
        ld_idx=ld_idx.astype(jnp.int32), ld_d=ld_d,
        beta=beta, new_flag=jnp.ones((n,), bool), active=active,
        ema_new_frac=jnp.float32(1.0), zhat=jnp.float32(1.0),
        step=jnp.int32(0), rng=r_state,
        # reverse-edge cache: rev_step starts one full period in the
        # past so the first refinement always refreshes
        rev_idx=jnp.zeros((n, cfg.c_hd_rev), jnp.int32),
        rev_step=jnp.int32(-cfg.rev_refresh))


def make_step(cfg: FuncSNEConfig):
    """Jitted single-device step; state is donated."""
    return jax.jit(functools.partial(funcsne_step, cfg), donate_argnums=(0,))


# --------------------------------------------------------------------------
# Scan-chunked on-device driver (§Perf H15)


class ChunkMetrics(NamedTuple):
    """Per-chunk driver telemetry -- ONE host sync per chunk, not per step.

    All fields are device scalars; a GUI/driver reads them once per chunk
    (the headless equivalent of the paper's per-frame status line).  The
    health fields (finite_frac / y_max_abs / bad_step) are the on-device
    half of the resilience layer: they are folded into the chunk scan
    alongside the displacement EMA, so fault *detection* costs zero extra
    host syncs -- the probe in ``ResiliencePolicy.check`` reads the same
    tuple the driver already drains once per chunk.
    """
    step: Any           # () i32  global iteration count after the chunk
    n_snapshots: Any    # () i32  ring slots written this chunk
    disp_ema: Any       # () f32  EMA over the chunk of mean |vel| (active)
    zhat: Any           # () f32  Z estimator at chunk end
    ema_new_frac: Any   # () f32  HD-refinement EMA at chunk end
    finite_frac: Any    # () f32  MIN over the chunk of the fraction of
    #                     finite Y entries among active rows (1.0=healthy)
    y_max_abs: Any      # () f32  MAX over the chunk of max |Y| over
    #                     active rows' finite entries (explosion probe)
    bad_step: Any       # () i32  first global step whose embedding held a
    #                     non-finite active entry; -1 = none this chunk
    hd_fires: Any       # () i32  steps of this chunk whose HD refinement
    #                     gate fired
    sigma_fires: Any    # () i32  steps of this chunk whose sigma refresh
    #                     ran (step on the cadence and a row flagged)


# decay of the per-chunk ChunkMetrics EMAs; ``fit`` needs the same
# constant to normalise thresholds by the chunk's EMA saturation factor
# (1 - decay**T), so the two must never drift apart
_METRICS_DECAY = 0.9


def _chunk_fn(cfg: FuncSNEConfig, T: int, *, schedule=None, n_iter=None,
              snapshot_every: int = 0, ctx: AxisCtx = AxisCtx(),
              metrics_decay: float = _METRICS_DECAY,
              health_metrics: bool = True, health_reduce: bool = True):
    """Traced chunk body: ``(st, X, hp) -> (st, snaps, ChunkMetrics)``.

    Runs ``T`` iterations of :func:`funcsne_step` inside ONE
    ``jax.lax.scan`` so a dispatch's fixed host->device cost is amortised
    over the whole chunk.  Everything the per-step host loop used to do on
    the host moves into the carry:

      * hyperparameter schedule: evaluated from the carried ``st.step``
        (``schedule(it, n_iter, hp)`` with traced ``it``) -- no per-step
        scalar uploads; ``schedule=None`` applies ``hp`` unchanged, which
        makes the chunk bit-identical to ``T`` sequential ``make_step``
        calls;
      * snapshots: a device-side ``(n_snap, n, d)`` ring-buffer carry slot
        captures ``Y`` whenever ``st.step % snapshot_every == 0`` (the
        same instants the host loop device_get'd); the host drains
        ``snaps[:metrics.n_snapshots]`` once per chunk;
      * metrics: per-step scalars are EMA'd into :class:`ChunkMetrics` so
        the driver/GUI syncs one tuple per chunk; the steps whose HD gate
        and sigma refresh fired are counted into the same tuple;
      * health telemetry: the finite-fraction of ``Y`` (min over the
        chunk), the max |Y| (max over the chunk) and the first step with
        a non-finite active entry fold into the same carry
        (``health_metrics=False`` elides the computation entirely -- the
        A/B knob behind the ``fig8_health_*`` bench rows).  The scalars
        ride in the one ChunkMetrics sync, so the resilience layer's
        fault detection adds no host round-trips.

    Mesh semantics (``health_reduce``, default True): on a mesh each
    shard probes ONLY its own row slice of ``Y`` (the rows whose updates
    it computed) and the per-shard scalars are reduced across
    ``ctx.all_rows`` once per chunk -- ``min`` over ``finite_frac``,
    ``max`` over ``y_max_abs``, earliest ``bad_step`` -- so a NaN
    confined to ONE shard's replica trips the *global* probe.  The
    reduction is three scalar collectives per chunk (not per step) and
    zero extra host syncs.  ``health_reduce=False`` keeps the legacy
    shard-blind per-replica computation: every shard probes its full
    local copy of ``Y`` and the coordinator reads shard 0's value only
    -- a device-local corruption on any other shard (a bad HBM row, a
    miscompiled kernel, an injected ``faults.NaNChunk(shard=...)``) is
    committed silently.  Kept as the positive-control anchor for the
    regression tests; never use it in production.
    """
    assert T >= 1, T
    if schedule is not None and n_iter is None:
        raise ValueError("schedule requires a static n_iter horizon")
    n, d = cfg.n_points, cfg.dim_ld
    # worst-case dues per chunk at any chunk<->snapshot alignment
    n_snap = (T // snapshot_every + 1) if snapshot_every else 0
    # mesh-reduced health: each shard probes its own row slice, the
    # scalars pmin/pmax across the mesh after the scan
    health_axes = ctx.all_rows if health_reduce else None

    def chunk(st: FuncSNEState, X, hp: HParams):
        snaps0 = jnp.zeros((n_snap, n, d), jnp.float32)
        health0 = (jnp.float32(1.0), jnp.float32(0.0), jnp.int32(-1))

        def body(carry, _):
            st, snaps, k, disp, health, fires = carry
            hp_t = schedule(st.step, n_iter, hp) if schedule else hp
            st, do_hd, do_sigma = _step_flags(cfg, st, X, hp_t, ctx)
            fires = (fires[0] + do_hd.astype(jnp.int32),
                     fires[1] + do_sigma.astype(jnp.int32))
            act_col = st.active[:, None].astype(jnp.float32)
            n_act = jnp.maximum(jnp.sum(st.active.astype(jnp.float32)), 1.0)
            act_disp = jnp.sum(jnp.abs(st.vel) * act_col) / (n_act * d)
            disp = metrics_decay * disp + (1.0 - metrics_decay) * act_disp
            if health_metrics:
                # O(n*d) elementwise reads of Y -- noise next to the
                # O(n*K*d) force phase, and entirely inside the scan:
                # zero extra host syncs, zero extra dispatches
                ff_min, ymax, bad = health
                if health_axes is not None:
                    # probe ONLY this shard's row slice of its replica:
                    # the rows whose updates this device computed.  A
                    # corruption local to one device is visible in its
                    # own slice before any collective can launder (or
                    # propagate) it -- the pmin/pmax after the scan
                    # makes that local observation global.
                    h_start, h_loc = _phase_rows(n, health_axes)
                    Y_h = jax.lax.dynamic_slice_in_dim(st.Y, h_start, h_loc)
                    a_h = jax.lax.dynamic_slice_in_dim(st.active, h_start,
                                                       h_loc)
                else:
                    Y_h, a_h = st.Y, st.active
                a_col = a_h[:, None].astype(jnp.float32)
                na_h = jnp.sum(a_h.astype(jnp.float32))
                finite = jnp.isfinite(Y_h)
                ff = jnp.sum(finite.astype(jnp.float32) * a_col) \
                    / jnp.maximum(na_h * d, 1.0)
                # a shard with no active rows is vacuously healthy (it
                # must not pmin a 0/…=0 fraction into the global probe)
                ff = jnp.where(na_h > 0, ff, jnp.float32(1.0))
                step_max = jnp.max(jnp.where(
                    finite & (a_col > 0), jnp.abs(Y_h), 0.0))
                bad = jnp.where((bad < 0) & (ff < 1.0), st.step - 1, bad)
                health = (jnp.minimum(ff_min, ff),
                          jnp.maximum(ymax, step_max), bad)
            if n_snap:
                due = (st.step % snapshot_every) == 0
                snaps = jax.lax.cond(
                    due,
                    lambda s: jax.lax.dynamic_update_index_in_dim(
                        s, st.Y, jnp.clip(k, 0, n_snap - 1), 0),
                    lambda s: s, snaps)
                k = k + due.astype(jnp.int32)
            return (st, snaps, k, disp, health, fires), None

        (st, snaps, k, disp, health, fires), _ = jax.lax.scan(
            body, (st, snaps0, jnp.int32(0), jnp.float32(0.0), health0,
                   (jnp.int32(0), jnp.int32(0))),
            None, length=T)
        ff_min, ymax, bad = health
        if health_metrics and health_axes is not None:
            # one reduction per CHUNK (min/max folds commute with the
            # per-step folds above, so reducing after the scan equals
            # reducing every step): three scalar collectives, zero extra
            # host syncs -- one bad shard now trips the GLOBAL probe.
            ff_min = jax.lax.pmin(ff_min, health_axes)
            ymax = jax.lax.pmax(ymax, health_axes)
            # earliest trip across shards; -1 (none) encodes as +inf-like
            no_bad = jnp.int32(jnp.iinfo(jnp.int32).max)
            bad = jax.lax.pmin(jnp.where(bad < 0, no_bad, bad), health_axes)
            bad = jnp.where(bad == no_bad, jnp.int32(-1), bad)
        metrics = ChunkMetrics(step=st.step, n_snapshots=k, disp_ema=disp,
                               zhat=st.zhat, ema_new_frac=st.ema_new_frac,
                               finite_frac=ff_min, y_max_abs=ymax,
                               bad_step=bad, hd_fires=fires[0],
                               sigma_fires=fires[1])
        return st, snaps, metrics

    return chunk


def make_chunked_step(cfg: FuncSNEConfig, T: int, *, schedule=None,
                      n_iter=None, snapshot_every: int = 0,
                      health_metrics: bool = True):
    """Jitted ``T``-iteration device program; state is donated.

    Returns ``chunk(st, X, hp) -> (st, snaps, ChunkMetrics)``.  One
    dispatch runs the whole chunk: schedule, snapshot ring, metrics and
    health telemetry all live on device (see :func:`_chunk_fn`), so the
    per-iteration host cost is the per-chunk cost / ``T``.
    """
    return jax.jit(_chunk_fn(cfg, T, schedule=schedule, n_iter=n_iter,
                             snapshot_every=snapshot_every,
                             health_metrics=health_metrics),
                   donate_argnums=(0,))


def make_distributed_step(cfg: FuncSNEConfig, mesh, *,
                          points_axes=("data",), feat_axis="model",
                          chunk: int = None, schedule=None, n_iter=None,
                          snapshot_every: int = 0,
                          health_metrics: bool = True,
                          health_reduce: bool = True):
    """shard_map'd step for a production mesh (see module docstring).

    ``chunk=None`` keeps the classic one-step contract
    ``step(st, X, hp) -> st``.  ``chunk=T`` returns the scan-chunked
    driver under the same mesh: ``step(st, X, hp) -> (st, snaps,
    ChunkMetrics)`` with the per-chunk collectives identical to ``T``
    sequential distributed steps -- the chunk body is the same traced
    ``funcsne_step``, so the psum/all-gather schedule per iteration is
    unchanged and only the dispatch + host-sync cost is amortised.

    The chunked form's health telemetry is mesh-reduced by default
    (``health_reduce=True``): each shard probes its own row slice and
    ``finite_frac`` / ``y_max_abs`` / ``bad_step`` are pmin/pmax'd
    across the mesh once per chunk, so the ChunkMetrics any host reads
    reflect EVERY shard -- a NaN confined to one device's replica trips
    the global rollback.  ``health_reduce=False`` restores the legacy
    shard-blind per-replica probe (positive-control anchor for tests
    only; see :func:`_chunk_fn`).
    """
    ctx = AxisCtx(points=tuple(points_axes), feat=feat_axis)
    state_specs = FuncSNEState(*([P()] * len(FuncSNEState._fields)))
    in_specs = (state_specs, P(None, feat_axis),
                HParams(*([P()] * len(HParams._fields))))

    if chunk is None:
        def step(st, X, hp):
            return funcsne_step(cfg, st, X, hp, ctx)

        fn = compat.shard_map(step, mesh=mesh, in_specs=in_specs,
                              out_specs=state_specs, check_vma=False)
        return jax.jit(fn, donate_argnums=(0,)), ctx

    body = _chunk_fn(cfg, chunk, schedule=schedule, n_iter=n_iter,
                     snapshot_every=snapshot_every, ctx=ctx,
                     health_metrics=health_metrics,
                     health_reduce=health_reduce)
    out_specs = (state_specs, P(),
                 ChunkMetrics(*([P()] * len(ChunkMetrics._fields))))
    fn = compat.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(0,)), ctx


def rescale_embedding(st: FuncSNEState, factor: float = 0.01):
    """The paper's 'implosion button': rescale Y so gradients matter again."""
    return st._replace(Y=st.Y * factor, vel=st.vel * 0.0)


def add_points(st: FuncSNEState, ids, rng) -> FuncSNEState:
    """Activate rows (dynamic datasets). Caller updates the X buffer first;
    HD distances refresh lazily through the iterative KNN (flags set)."""
    ids = jnp.asarray(ids, jnp.int32)
    n = st.active.shape[0]
    active = st.active.at[ids].set(True)
    fresh = (ids[:, None] + 1 + knn_lib.init_knn_idx(
        rng, ids.shape[0], n - 1, st.hd_idx.shape[1])) % n
    hd_idx = st.hd_idx.at[ids].set(fresh.astype(jnp.int32))
    hd_d = st.hd_d.at[ids].set(jnp.inf)
    new_flag = st.new_flag.at[ids].set(True)
    return st._replace(active=active, hd_idx=hd_idx, hd_d=hd_d,
                       new_flag=new_flag)


def remove_points(st: FuncSNEState, ids) -> FuncSNEState:
    ids = jnp.asarray(ids, jnp.int32)
    return st._replace(active=st.active.at[ids].set(False),
                       new_flag=st.new_flag.at[ids].set(False))


def _span(name: str):
    """Host span ``funcsne.<name>`` on the profiler's trace, on the same
    clock as the device planes.  ``fit`` and ``fit_elastic`` open a
    handful per chunk, never per step; with no profiler running one
    costs well under a microsecond.  Capture them with
    ``jax.profiler.trace(dir)`` around ``fit``."""
    return jax.profiler.TraceAnnotation("funcsne." + name)


def _copy_state(st: FuncSNEState) -> FuncSNEState:
    return jax.tree.map(lambda a: jnp.array(a, copy=True), st)


def _scaled_hp(hp: HParams, lr_scale: float, ex_scale: float) -> HParams:
    """Retry backoff applied to the traced hyperparameters.

    Identity at scale 1.0 (no new arrays), so a run that never trips a
    health probe is bit-identical to one without a policy; the schedule
    composes on top (it multiplies ``hp.lr``), so backoff scales the
    whole annealing curve rather than fighting it.
    """
    if lr_scale == 1.0 and ex_scale == 1.0:
        return hp
    return hp._replace(
        lr=hp.lr * jnp.float32(lr_scale),
        exaggeration=hp.exaggeration * jnp.float32(ex_scale))


class AuditResult(NamedTuple):
    """Violation counts from :func:`audit_state` -- all () int32, all
    zero for a healthy state."""
    hd_oob: Any         # hd_idx entries outside [0, n) (mod SENTINEL)
    ld_oob: Any         # ld_idx entries outside [0, n) (mod SENTINEL)
    rev_oob: Any        # rev_idx entries outside [0, n) (mod SENTINEL)
    hd_dup: Any         # per-row duplicate hd neighbours (mod SENTINEL)
    ld_dup: Any         # per-row duplicate ld neighbours (mod SENTINEL)
    hd_sentinel: Any    # SENTINEL hd slots whose distance is not +inf
    y_nonfinite: Any    # non-finite Y entries on active rows
    x_nonfinite: Any    # non-finite X entries on active rows (0 if no X)


@functools.lru_cache(maxsize=None)
def _audit_fn(cfg: FuncSNEConfig, with_x: bool):
    n = cfg.n_points

    def _oob(idx):
        if not hasattr(idx, "ndim") or idx.ndim != 2 or idx.shape[1] == 0:
            return jnp.int32(0)
        bad = (idx != SENTINEL) & ((idx < 0) | (idx >= n))
        return jnp.sum(bad.astype(jnp.int32))

    def _dups(idx):
        # per-row duplicates via sort + adjacent-compare: O(K log K) per
        # row instead of the (K, K) broadcast; SENTINEL padding sorts to
        # the end, so equal-adjacent SENTINELs are masked out
        if not hasattr(idx, "ndim") or idx.ndim != 2 or idx.shape[1] < 2:
            return jnp.int32(0)
        s = jnp.sort(idx, axis=1)
        eq = (s[:, 1:] == s[:, :-1]) & (s[:, 1:] != SENTINEL)
        return jnp.sum(eq.astype(jnp.int32))

    def audit(st, X):
        act_col = st.active[:, None]
        # SENTINEL hd slots must carry +inf distance: the merge kernels
        # key validity off the distance, so a finite distance on a
        # SENTINEL slot resurrects a phantom neighbour.  (ld_d is a
        # zeros placeholder on the mesh path and add_points seeds valid
        # idx with +inf distance, so only hd and only this direction.)
        hd_bad_sent = (st.hd_idx == SENTINEL) & ~jnp.isinf(st.hd_d)
        res = AuditResult(
            hd_oob=_oob(st.hd_idx), ld_oob=_oob(st.ld_idx),
            rev_oob=_oob(st.rev_idx),
            hd_dup=_dups(st.hd_idx), ld_dup=_dups(st.ld_idx),
            hd_sentinel=jnp.sum(hd_bad_sent.astype(jnp.int32)),
            y_nonfinite=jnp.sum(
                (~jnp.isfinite(st.Y) & act_col).astype(jnp.int32)),
            x_nonfinite=jnp.sum(
                (~jnp.isfinite(X) & act_col).astype(jnp.int32))
            if with_x else jnp.int32(0))
        return res

    if with_x:
        return jax.jit(audit)
    return jax.jit(lambda st: audit(st, None))


def audit_state(st: FuncSNEState, cfg: FuncSNEConfig,
                X=None) -> AuditResult:
    """Cheap on-device invariant audit of a :class:`FuncSNEState`:
    KNN / reverse-edge indices in ``[0, n)`` (modulo SENTINEL), per-row
    duplicate-free neighbour lists, SENTINEL slots distance-consistent,
    and finite Y (and X, when given) on active rows.

    Every check is a fused reduction over state already on device -- one
    pass over the index tables, no gathers, no host round-trip until the
    caller reads the counts -- so it is cheap enough to run at chunk
    boundaries (``ResiliencePolicy(audit_every=)``).  It exists for the
    corruption class the finite-fraction health probes are blind to:
    a poisoned index table is made of perfectly finite integers, and the
    embedding it slowly drags out of shape stays finite too.

    Returns an :class:`AuditResult` of () int32 violation counts (all
    zero = healthy); jit-compiled once per (cfg, X-given) and cached.
    Works unchanged on mesh-replicated state (the reductions compile to
    the shard-local sum + an AllReduce).
    """
    fn = _audit_fn(cfg, X is not None)
    return fn(st, X) if X is not None else fn(st)


def fit(X, *, cfg: FuncSNEConfig = None, n_iter: int = 750, rng=None,
        hparams: HParams = None,
        schedule: Callable[[int, int, HParams], HParams] = None,
        init: str = "pca", snapshot_every: int = 0,
        callback: Callable[[int, FuncSNEState], None] = None,
        chunk_size: int = None, early_stop: float = None,
        auto_rescale: float = None,
        resilience: "ResiliencePolicy" = None, resume_from=None,
        state: FuncSNEState = None, validate: bool = True):
    """End-to-end driver on the scan-chunked step. Returns (state, snapshots).

    ``chunk_size`` iterations run per device dispatch (§Perf H15); the host
    syncs once per chunk to drain the snapshot ring.  Default: 50, or 1
    when a per-iteration ``callback`` is supplied (the callback contract
    needs the state after every step).  Schedule, snapshots and metrics
    are computed on device.  Results are bit-invariant to ``chunk_size``;
    vs the per-step host loop this replaces, parity is to fp32 codegen
    tolerance (contract pinned in tests/test_chunked_driver.py).

    ``early_stop`` (off by default) is the first :class:`ChunkMetrics`
    consumer: after each chunk the driver reads the EMA'd mean per-active
    displacement ``metrics.disp_ema`` -- already on the host, it is THE
    one sync per chunk -- and stops once it falls below the threshold
    (the embedding has converged; the remaining chunks would only stir
    negative-sampling noise).  The returned ``state.step`` tells the
    caller how many iterations actually ran.  The per-chunk EMA restarts
    from 0 each chunk and saturates at ``(1 - 0.9^T)`` of the
    steady-state per-step displacement, so the driver *normalises* it by
    that factor before comparing: thresholds are calibrated in
    steady-state per-step displacement units and are chunk-size
    independent.  The host-loop fallback compares the identical quantity
    (its per-step ``act_disp`` equals the normalised T=1 EMA), a parity
    pinned in tests/test_chunked_driver.py.

    ``auto_rescale`` (off by default) is the second ChunkMetrics
    consumer -- the paper's 'implosion button' driven by telemetry: when
    the (normalised, see above) ``metrics.disp_ema`` collapses below the
    threshold while iterations remain, the embedding has grown so large
    that gradient steps no longer move points relative to its scale, so
    the driver applies :func:`rescale_embedding` (shrink Y by 100x, zero
    the velocity) and keeps optimising instead of silently freezing.
    When both are set, ``early_stop`` is checked first (a stop wins over
    a rescale).

    ``resilience`` (a :class:`~repro.core.resilience.ResiliencePolicy`)
    arms the fault-tolerance layer: after every chunk the health fields
    of :class:`ChunkMetrics` (computed inside the scan -- no extra host
    syncs) are checked; a tripped probe rolls the state back to the last
    healthy chunk boundary and retries with exponentially backed-off
    lr/exaggeration, raising :class:`EmbeddingDiverged` once
    ``max_retries`` consecutive retries fail.  With
    ``policy.checkpoint_dir`` set, the full state is snapshotted through
    :class:`~repro.checkpoint.Checkpointer` every ``checkpoint_every``
    healthy chunks and ``fit(resume_from=dir)`` continues a killed run
    bit-identically to the uninterrupted one (chunk boundaries are
    bit-neutral, and the state carries its own RNG key and counter-RNG
    salt inputs).  ``policy.sticky_fallback`` enables guarded Pallas
    launches (``repro.kernels.fallback``): a raising kernel family is
    demoted to its XLA reference for the rest of the run instead of
    crashing it.  A :class:`~repro.runtime.straggler.StepTimeMonitor`
    watches chunk wall times as the hang/straggler watchdog.  A clean
    run under a policy is bit-identical to ``resilience=None`` (one
    extra on-device state copy per chunk is the only cost -- the chunk
    program donates its input, so rollback needs an anchor).

    Distributed-resilience matrix -- which policy knobs are mesh-aware.
    This ``fit`` drives a single process; the multi-host elastic loop on
    the same policy is :func:`repro.runtime.coordinator.fit_elastic`:

      ``min_finite_frac`` / ``max_abs_y``
          mesh-aware: under ``make_distributed_step(chunk=T)`` the
          telemetry is pmin/pmax-reduced across every shard before any
          host reads it (``health_reduce=True``), so one bad shard
          trips the global rollback.
      rollback / ``lr_backoff`` / ``max_retries``
          mesh-aware: the anchor copy is replicated on the mesh and the
          retry re-dispatches the same chunk program on all shards.
      ``checkpoint_dir`` / ``checkpoint_every`` / ``keep_last``
          mesh-aware: the coordinator writes per-host shard files
          (``Checkpointer.save(host_shard_filter=...)``, merged on
          restore) so checkpoint I/O scales with hosts; this ``fit``
          writes the single-host layout.
      ``resume_from``
          mesh-aware AND elastic: ``Checkpointer.restore(shardings=)``
          re-lays a checkpoint onto whatever mesh survives.
      ``sticky_fallback``
          process-local: the demotion registry is per process; each
          host demotes (and logs) independently.
      ``hang_timeout`` / ``straggler_z``
          coordinator-local: chunk wall time is observed where the
          dispatch happens.

    ``state`` continues an existing :class:`FuncSNEState` (dynamic
    sessions: ``add_points``/``remove_points`` between ``fit`` calls)
    instead of initialising from ``X``; ``n_iter`` then counts the
    *additional* iterations.  NB schedules are evaluated from the global
    ``st.step`` on device -- pass an identity schedule (or one keyed on
    absolute steps) when continuing.

    A ``schedule`` is evaluated with a *traced* ``it`` inside the chunk;
    one that needs a Python ``int`` (host control flow on ``it``) is
    detected up front and falls back to the per-step host loop (which
    supports neither ``resilience`` nor ``resume_from`` -- a ValueError
    says so rather than silently dropping the policy).
    """
    X = jnp.asarray(X, jnp.float32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if cfg is None:
        cfg = FuncSNEConfig(n_points=X.shape[0], dim_hd=X.shape[1])
    if validate:
        validate_inputs(X, cfg)
    if hparams is None:
        hparams = default_hparams(cfg.n_points)
    if schedule is None:
        schedule = default_schedule
    if chunk_size is None:
        chunk_size = 1 if callback is not None else min(50, max(1, n_iter))
    try:        # host-only schedules (Python control flow on it) -> host loop
        jax.eval_shape(lambda it: schedule(it, n_iter, hparams),
                       jax.ShapeDtypeStruct((), jnp.int32))
    except jax.errors.ConcretizationTypeError:
        if resilience is not None or resume_from is not None \
                or state is not None:
            raise ValueError(
                "resilience / resume_from / state require a traceable "
                "schedule (the per-step host-loop fallback does not "
                "support them); use a schedule evaluable with a traced "
                "`it`")
        return _fit_host_loop(X, cfg, n_iter, rng, hparams, schedule, init,
                              snapshot_every, callback, early_stop,
                              auto_rescale)
    if state is not None:
        st = state
    else:
        st = init_state(rng, X, cfg, init=init,
                        perplexity=hparams.perplexity, validate=False)

    policy = resilience
    ck = monitor = None
    start_it = 0
    lr_scale = ex_scale = 1.0
    if policy is not None:
        if policy.checkpoint_dir is not None:
            from repro.checkpoint import Checkpointer
            ck = Checkpointer(policy.checkpoint_dir,
                              keep_last=policy.keep_last)
        from repro.runtime.straggler import StepTimeMonitor
        monitor = StepTimeMonitor(z_thresh=policy.straggler_z,
                                  hang_timeout=policy.hang_timeout,
                                  warmup_steps=policy.straggler_warmup)
    if resume_from is not None:
        from repro.checkpoint import Checkpointer, cfg_compat
        rck = ck if (ck is not None
                     and str(ck.dir) == str(resume_from)) else \
            Checkpointer(resume_from)
        # fallback-chain restore: a damaged newest boundary (torn write,
        # bit flip, lost shard) degrades to the previous verified one
        # instead of crashing or silently loading garbage; a cfg
        # mismatch raises CheckpointIncompatible (never falls back)
        tree, meta, fbs = rck.restore_verified(
            st, expect_compat=cfg_compat(cfg))
        for fb in fbs:
            if policy is not None:
                policy.log("checkpoint_fallback", **fb)
            else:
                warnings.warn(
                    f"[checkpoint] skipping damaged boundary step "
                    f"{fb['step']}: {fb['reason']}", RuntimeWarning)
        st = jax.tree.map(jnp.asarray, tree)
        start_it = int(meta["step"])
        lr_scale = float(meta.get("lr_scale", 1.0))
        ex_scale = float(meta.get("ex_scale", 1.0))

    snapshots = []
    chunks = {}         # T -> compiled program (final ragged chunk reuses it)
    it = start_it
    retries = 0
    n_healthy = 0       # healthy chunks since start (checkpoint cadence)
    fb_seen = fallback.n_events()
    guard = fallback.enabled(policy.sticky_fallback) \
        if policy is not None else contextlib.nullcontext()
    with contextlib.ExitStack() as stack:
        stack.enter_context(guard)
        if ck is not None:
            # every exit path -- EmbeddingDiverged, Preempted, a raising
            # callback -- joins the in-flight async write so the last
            # boundary is committed on disk for resume; close() warns on
            # an unobserved write error instead of masking the in-flight
            # exception (the happy path surfaces it via wait() below)
            stack.callback(ck.close)
        while it < n_iter:
            with _span("chunk"):
                T = min(chunk_size, n_iter - it)
                if T not in chunks:
                    chunks[T] = make_chunked_step(
                        cfg, T, schedule=schedule, n_iter=n_iter,
                        snapshot_every=snapshot_every)
                hp_run = _scaled_hp(hparams, lr_scale, ex_scale)
                if policy is not None or faults.current() is not None:
                    # the chunk program donates its input; the live `st` is
                    # the rollback anchor, so dispatch a copy.  Scripted
                    # faults poison the *copy*: the anchor stays clean, as it
                    # would for a divergence that happens inside the chunk.
                    st_in = faults.corrupt_state(_copy_state(st), it)
                else:
                    st_in = st
                t0 = time.time()
                with _span("dispatch"):
                    st_out, snaps, metrics = chunks[T](st_in, X, hp_run)
                alarm = None
                if policy is not None:
                    with _span("sync"):     # THE one host sync per chunk
                        m = jax.device_get(metrics)
                    alarm = monitor.observe(time.time() - t0)
                    if alarm is not None:
                        policy.log("straggler", step=it, alarm=alarm)
                    for e in fallback.events(fb_seen):
                        policy.log(**e)
                    fb_seen = fallback.n_events()
                    reason = policy.check(m)
                    if reason is None and policy.audit_every \
                            and (n_healthy + 1) % policy.audit_every == 0:
                        # chunk-boundary invariant audit: catches index
                        # corruption the finite-fraction probes are blind
                        # to; a violation feeds the SAME rollback path
                        with _span("audit"):
                            aud = jax.device_get(audit_state(st_out, cfg, X))
                        reason = policy.audit_check(aud)
                        if reason is not None:
                            policy.log("audit_violation", step=it,
                                       reason=reason)
                    if reason is not None:
                        if retries >= policy.max_retries:
                            policy.log("giving_up", step=it, reason=reason,
                                       retries=retries)
                            raise EmbeddingDiverged(it, reason, retries,
                                                    policy.events)
                        retries += 1
                        lr_scale *= policy.lr_backoff
                        ex_scale *= policy.exaggeration_backoff
                        policy.log("rollback", step=it, reason=reason,
                                   retry=retries, lr_scale=lr_scale,
                                   ex_scale=ex_scale)
                        continue    # `st` still holds the last healthy state
                    retries = 0
                else:
                    m = metrics
                st = st_out
                if snapshot_every:
                    with _span("snapshots"):
                        taken = int(m.n_snapshots)
                        if taken:
                            snapshots.extend(
                                list(jax.device_get(snaps[:taken])))
                if callback is not None:
                    callback(it + T - 1, st)
                it += T
                if policy is not None:
                    n_healthy += 1
                    if ck is not None:
                        from repro.checkpoint import cfg_compat
                        meta = {"lr_scale": lr_scale, "ex_scale": ex_scale,
                                "compat": cfg_compat(cfg)}
                        saved = n_healthy % policy.checkpoint_every == 0
                        if saved:
                            with _span("checkpoint"):
                                ck.save(it, st, metadata=meta)
                        if alarm is not None:
                            # hang/straggler escalation: commit THIS
                            # boundary before the next dispatch
                            # (straggler.py's contract) so a subsequent
                            # kill loses at most one chunk
                            with _span("checkpoint"):
                                if saved:
                                    ck.wait()   # land the in-flight write
                                else:
                                    ck.save(it, st, metadata=meta,
                                            blocking=True)
                            policy.log("early_checkpoint", step=it,
                                       alarm=alarm)
                # scripted damage to the newest COMMITTED checkpoint (the
                # hook waits for the in-flight write): exercises the
                # verified-restore fallback chain on resume
                faults.maybe_corrupt_checkpoint(it, ck)
                # simulated kill between chunks; the ExitStack's ck.close()
                # is the preemption grace period that lets the in-flight
                # checkpoint write land, so the just-saved boundary is
                # committed for resume
                faults.maybe_preempt(it)
                # normalise the per-chunk EMA by its saturation factor so the
                # threshold reads in steady-state per-step displacement units
                # whatever the chunk size (host loop parity: T=1 factor is
                # exactly the 0.1 single-step weight)
                if early_stop is not None or auto_rescale is not None:
                    disp = float(m.disp_ema) / (1.0 - _METRICS_DECAY ** T)
                    if early_stop is not None and disp < early_stop:
                        break
                    if auto_rescale is not None and it < n_iter \
                            and disp < auto_rescale:
                        # the paper's implosion button, driven by telemetry:
                        # the layout froze relative to its own scale --
                        # shrink it so gradients matter again and keep going
                        st = rescale_embedding(st)
        if ck is not None:
            ck.wait()   # surface async write failures BEFORE returning:
            #             the final checkpoint of a run must not vanish
            #             silently (close() above only warns)
    return st, snapshots


def _fit_host_loop(X, cfg, n_iter, rng, hparams, schedule, init,
                   snapshot_every, callback, early_stop=None,
                   auto_rescale=None):
    """Pre-H15 per-step host loop: kept for schedules that must see a
    Python ``it`` (``fit`` detects those and routes here)."""
    st = init_state(rng, X, cfg, init=init, perplexity=hparams.perplexity)
    step = make_step(cfg)
    snapshots = []
    for it in range(n_iter):
        st = step(st, X, schedule(it, n_iter, hparams))
        if snapshot_every and (it + 1) % snapshot_every == 0:
            snapshots.append(jax.device_get(st.Y))
        if callback is not None:
            callback(it, st)
        if early_stop is not None or auto_rescale is not None:
            # the same quantity `fit` derives from ChunkMetrics: its
            # per-chunk disp_ema normalised by the (1 - 0.9^T) saturation
            # factor is, at T=1, exactly this per-step displacement --
            # thresholds read in the same units on both drivers (parity
            # pinned in tests/test_chunked_driver.py)
            n_act = max(float(jnp.sum(st.active.astype(jnp.float32))), 1.0)
            act_disp = float(jnp.sum(
                jnp.abs(st.vel) * st.active[:, None].astype(jnp.float32))) \
                / (n_act * cfg.dim_ld)
            if early_stop is not None and act_disp < early_stop:
                break
            if auto_rescale is not None and it + 1 < n_iter \
                    and act_disp < auto_rescale:
                st = rescale_embedding(st)
    return st, snapshots


def default_schedule(it, n_iter: int, hp: HParams) -> HParams:
    """Early exaggeration, then a linear lr decay (UMAP-style).

    The paper runs a *continual* optimisation where the user counteracts the
    ever-expanding-embedding regime interactively (attraction ratio /
    'implosion' button).  For a batch ``fit`` the equivalent is annealing the
    learning rate so negative-sampling noise stops diffusing the layout.

    ``it`` may be a *traced* i32 scalar (``n_iter`` stays static): the
    chunked driver evaluates the schedule on-device from the carried
    ``st.step``, so no per-iteration host scalar upload exists.  All
    arithmetic is pinned to i32/f32 jnp ops so a host call with a Python
    ``it`` produces bit-identical hyperparameters to the traced evaluation.
    """
    ee_until = max(1, n_iter // 4)
    it = jnp.asarray(it, jnp.int32)
    ex = jnp.where(it < ee_until, 12.0, 1.0) * hp.exaggeration
    mom = jnp.where(it < ee_until, 0.5, hp.momentum)
    # the barriers pin traced == eager rounding: without them jit rewrites
    # the constant division into a reciprocal multiply and FMA-contracts
    # the 1 - 0.9*frac chain, so the chunked driver's on-device schedule
    # would drift 1 ulp from the host loop's eager evaluation
    denom = jax.lax.optimization_barrier(
        jnp.float32(max(1, n_iter - ee_until)))
    frac = jnp.maximum(jnp.float32(0.0), (it - ee_until) / denom)
    lr = hp.lr * (1.0 - jax.lax.optimization_barrier(0.9 * frac))
    return hp._replace(exaggeration=ex, momentum=mom, lr=lr)
