#!/usr/bin/env python3
"""On-chip smoke test of the default FUnc-SNE path.

  python chip_smoke.py               # one TPU chip: kernel parity + fit
  python chip_smoke.py --four-chips  # fit_elastic on 4 chips vs one chip

Phase 1 runs each Pallas kernel family of the default step through its
``ops`` entry point on the device and compares it with the pure-jnp
reference (``backend="xla"``) at the shapes of phase 2; the edge-mode
force kernel runs through both of its row sources, and each line names
the one it took.  Phase 2 runs
``funcsne.fit`` with the default ``FuncSNEConfig`` on
``hierarchical_cells(n=65536, dim=50)`` into 2-D (500 iterations in
chunks of 50, fixed seed) with kernel fallback off, then scores the HD
neighbour lists and the embedding against exact KNN computed in blocks
on a fixed sample of 2048 rows.  ``--four-chips`` runs only
``fit_elastic`` over four chips and the one-chip ``fit`` it is compared
with, on the same data for 200 iterations each.

Lines before the last are informational (backends, compile seconds,
iterations/s are not claims).  The last line of stdout is
``{"ok": true, "device": {...}}``.  Any failed check exits non-zero
before that line; so does a run without a TPU or away from this
repository's ``src/``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

N, DIM_HD, DIM_LD = 65536, 50, 2
N_ITER, CHUNK, SEED = 500, 50, 0
# --four-chips runs both sides for fewer iterations: the comparison needs
# the same run on each side, and a four-chip second costs four
N_ITER_FOUR = 200
# thresholds on the sampled criteria, from a CPU rehearsal of phase 2
# (xla backend): n=65536 gave recall 0.568 and AUC 0.0040; n=16384 after
# 1500 iterations 0.893 and 0.0223.  Random lists give recall ~0.0006
# and a random embedding AUC ~-5e-5: the local (K <= 64) structure of
# this data is isotropic 50-D noise, so the AUC is small for any 2-D map.
MIN_RECALL = 0.4        # HD KNN recall@32 of st.hd_idx
MIN_RNX_AUC = 0.002     # R_NX AUC of Y against X
FOUR_CHIP_BAND = 0.05   # |four-chip - one-chip| on both criteria
# phase-1 float tolerance: max |got - ref| <= RTOL * max |ref|
RTOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Collects a phase's pass/fail lines; the phase fails as a whole."""

    def __init__(self, phase: str):
        self.phase, self.failed = phase, []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"[{self.phase}] {'PASS' if ok else 'FAIL'} {name} {detail}")
        if not ok:
            self.failed.append(name)

    def close(self, np, name, got, want):
        """Same non-finite entries (+inf marks an invalid slot), and the
        finite ones within RTOL of the reference's largest magnitude."""
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        fin = np.isfinite(want)
        ok = got.shape == want.shape and np.array_equal(
            np.where(fin, 0.0, want), np.where(fin, 0.0, got))
        err = float(np.max(np.abs(got[fin] - want[fin]))) if ok \
            else float("inf")
        scale = float(np.max(np.abs(want[fin])))
        self(name, ok and err <= RTOL * scale,
             f"max_err={err:.3g} scale={scale:.3g}")

    def equal(self, np, name, got, want):
        got, want = np.asarray(got), np.asarray(want)
        bad = int(np.sum(got != want)) if got.shape == want.shape else -1
        self(name, bad == 0, f"mismatches={bad}")

    def end(self) -> None:
        if self.failed:
            log(f"[{self.phase}] FAILED: {', '.join(self.failed)}")
            raise SystemExit(1)


def _quantised(np, a):
    """Multiples of 1/4 in [-8, 8]: squared distances are exact in f32
    in any summation order, so discrete merge outputs must agree."""
    return (np.clip(np.round(np.asarray(a) * 4.0), -32, 32) / 4.0) \
        .astype(np.float32)


def kernel_parity(backend: str, n: int = N, seed: int = SEED) -> None:
    """Phase 1: every kernel family of the default step, ``backend`` vs
    the XLA reference, at phase-2 shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import funcsne
    from repro.core import knn as knn_lib
    from repro.data import synthetic
    from repro.kernels.knn_merge.ops import knn_merge
    from repro.kernels.ne_forces.kernel import ne_forces_gather_pallas
    from repro.kernels.ne_forces.ops import ne_forces_gather, row_source
    from repro.kernels.pairwise_sqdist.ops import pairwise_sqdist_gather

    t_start = time.time()
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=DIM_HD)
    chk = Checks("parity")
    rng = np.random.default_rng(seed)
    X = synthetic.hierarchical_cells(n=n, dim=DIM_HD, seed=seed)[0]
    Xq = jnp.asarray(_quantised(np, X))
    qid = jnp.arange(n, dtype=jnp.int32)
    active = jnp.asarray(rng.random(n) > 0.1)
    hd_idx = knn_lib.init_knn_idx(jax.random.PRNGKey(1), n, n, cfg.k_hd)
    ld_idx = knn_lib.init_knn_idx(jax.random.PRNGKey(2), n, n, cfg.k_ld)
    hd_d = pairwise_sqdist_gather(Xq, qid, hd_idx, backend="xla")
    order = jnp.argsort(hd_d, axis=1)
    hd_idx = jnp.take_along_axis(hd_idx, order, axis=1)
    hd_d = jnp.take_along_axis(hd_d, order, axis=1)
    salt = jnp.int32(12345)
    log(f"[parity] inputs ready: {time.time() - t_start:.2f}s")

    def timed(name, fn):
        t0 = time.time()
        out = jax.block_until_ready(fn())
        log(f"[parity] {name}: {time.time() - t0:.2f}s (compile included)")
        return out

    def ref(name, fn):
        return timed(f"{name} (xla reference)", fn)

    cand = jnp.asarray(rng.integers(0, n, (n, cfg.c_hd)), jnp.int32)
    got = timed("pairwise_sqdist_gather", lambda: pairwise_sqdist_gather(
        jnp.asarray(X), qid, cand, backend=backend))
    want = ref("pairwise_sqdist_gather", lambda: pairwise_sqdist_gather(
        jnp.asarray(X), qid, cand, backend="xla"))
    chk.close(np, "pairwise_sqdist_gather M=50", got, want)

    def merge_checks(name, got, want):
        chk.equal(np, f"{name} idx", got[0], want[0])
        chk.close(np, f"{name} d", got[1], want[1])
        chk.equal(np, f"{name} improved", got[2], want[2])

    cand_active = active[cand]
    kw = dict(cand_active=cand_active)
    merge_checks("knn_merge plain M=50", timed(
        "knn_merge plain", lambda: knn_merge(Xq, qid, hd_idx, hd_d, cand,
                                             backend=backend, **kw)),
        ref("knn_merge plain", lambda: knn_merge(
            Xq, qid, hd_idx, hd_d, cand, backend="xla", **kw)))

    hd_sources = (("two_hop", 0, 0, cfg.c_hd_non),
                  ("one_hop", 1, cfg.c_hd_ld),
                  ("two_hop", 1, 1, cfg.c_hd_ld_non),
                  ("uniform", cfg.c_hd_rand))
    kw = dict(sources=hd_sources, salt=salt, first_tables=(hd_idx, ld_idx),
              second_tables=(hd_idx, ld_idx), active=active)
    merge_checks("knn_merge cand-fused HD M=50", timed(
        "knn_merge cand-fused HD", lambda: knn_merge(
            Xq, qid, hd_idx, hd_d, backend=backend, **kw)),
        ref("knn_merge cand-fused HD", lambda: knn_merge(
            Xq, qid, hd_idx, hd_d, backend="xla", **kw)))

    Yq = jnp.asarray(_quantised(np, rng.normal(size=(n, DIM_LD)) * 3.0))
    ld_sources = (("two_hop", 0, 0, cfg.c_ld_non),
                  ("one_hop", 1, cfg.c_ld_hd),
                  ("uniform", cfg.c_ld_rand))
    kw = dict(cur_valid=active[ld_idx], sources=ld_sources, salt=salt,
              first_tables=(ld_idx, hd_idx), second_tables=(ld_idx,),
              active=active)
    merge_checks(f"knn_merge cand-fused LD d={DIM_LD}", timed(
        "knn_merge cand-fused LD", lambda: knn_merge(
            Yq, qid, ld_idx, None, backend=backend, **kw)),
        ref("knn_merge cand-fused LD", lambda: knn_merge(
            Yq, qid, ld_idx, None, backend="xla", **kw)))

    neg = jnp.asarray(rng.integers(0, n, (n, cfg.n_negatives)), jnp.int32)
    nbr = jnp.concatenate([hd_idx, ld_idx, neg], axis=1)
    coef = jnp.asarray(rng.random(nbr.shape, np.float32))
    segments = (("attraction", cfg.k_hd), ("repulsion", cfg.k_ld),
                ("repulsion", cfg.n_negatives))
    for d in (2, 32):
        Y = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        kw = dict(segments=segments, emit_edges=(True, True, False))
        want = ref(f"ne_forces_gather edge d={d}", lambda: ne_forces_gather(
            Y, qid, nbr, coef, 1.0, backend="xla", **kw))
        # the row source ops picks for (n, d); at d=2 also the DMA rows,
        # through the kernel's static argument
        runs = [(row_source(n, d), lambda: ne_forces_gather(
            Y, qid, nbr, coef, 1.0, backend=backend, **kw))]
        if d == 2 and backend == "pallas":
            runs.append(("dma", lambda: ne_forces_gather_pallas(
                Y, qid, nbr, coef, 1.0, row_source="dma", **kw)))
        for rows, run in runs:
            name = f"ne_forces_gather edge d={d} rows={rows}"
            got = timed(name, run)
            for part, gs, ws in zip(("agg", "edge", "wsum"), got, want):
                for s, (g, w) in enumerate(zip(gs, ws)):
                    if w is not None:
                        chk.close(np, f"{name} {part}[{s}]", g, w)
        kw = dict(segments=segments, scatter_fused=True,
                  scatter_back=(True, True, False))
        got = timed(f"ne_forces_gather scatter d={d}",
                    lambda: ne_forces_gather(Y, qid, nbr, coef, 1.0,
                                             backend=backend, **kw))
        want = ref(f"ne_forces_gather scatter d={d}",
                   lambda: ne_forces_gather(Y, qid, nbr, coef, 1.0,
                                            backend="xla", **kw))
        for part, gs, ws in zip(("field", "wsum"), got, want):
            for s, (g, w) in enumerate(zip(gs, ws)):
                chk.close(np, f"ne_forces_gather scatter d={d} {part}[{s}]",
                          g, w)
    chk.end()


def _quality(st, X):
    from repro.core.quality import embedding_quality, knn_recall
    return float(knn_recall(st.hd_idx, X)), float(embedding_quality(X, st.Y))


def _random_auc(X):
    import jax

    from repro.core.quality import embedding_quality
    y = jax.random.normal(jax.random.PRNGKey(SEED), (X.shape[0], DIM_LD))
    return float(embedding_quality(X, y))


def _step_kernels(cfg, st, X, n_iter, chunk):
    """Mosaic custom calls in the compiled chunk program ``fit`` ran (the
    same program again, so the compile cache answers it)."""
    import collections
    import re

    from repro.core import funcsne
    prog = funcsne.make_chunked_step(cfg, chunk,
                                     schedule=funcsne.default_schedule,
                                     n_iter=n_iter)
    text = prog.lower(st, X, funcsne.default_hparams(cfg.n_points)) \
        .compile().as_text()
    return dict(collections.Counter(re.findall(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', text)))


def _fit(cfg, X, n_iter, chunk, label, devices=None):
    """One resilient run with kernel fallback off; logs timing lines and
    returns the final state."""
    import jax

    from repro.core import funcsne
    from repro.core.resilience import ResiliencePolicy

    policy = ResiliencePolicy(sticky_fallback=False)
    stamps = []
    t0 = time.time()
    if devices is None:
        st, _ = funcsne.fit(X, cfg=cfg, n_iter=n_iter, chunk_size=chunk,
                            rng=jax.random.PRNGKey(SEED), resilience=policy,
                            callback=lambda it, s: stamps.append(
                                time.time()))
    else:
        from repro.runtime.coordinator import fit_elastic
        st = fit_elastic(X, cfg=cfg, n_iter=n_iter, chunk_size=chunk,
                         rng=jax.random.PRNGKey(SEED), devices=devices,
                         resilience=policy)
    jax.block_until_ready(st.Y)
    t1 = time.time()
    if stamps:
        log(f"[{label}] first chunk incl. init + compile: "
            f"{stamps[0] - t0:.1f}s")
        if len(stamps) > 1:
            rate = (len(stamps) - 1) * chunk / (stamps[-1] - stamps[0])
            log(f"[{label}] steady: {rate:.1f} it/s over "
                f"{len(stamps) - 1} chunks")
    log(f"[{label}] wall: {t1 - t0:.1f}s for {n_iter} iterations; "
        f"policy events: {[e['kind'] for e in policy.events]}")
    return st


def _no_fallback(chk) -> None:
    from repro.kernels import fallback
    chk("no kernel demotion", not fallback.demotions(),
        str(fallback.demotions()))
    chk("no fallback events", not fallback.events(), str(fallback.events()))


def end_to_end(backend: str = "auto", n: int = N, n_iter: int = N_ITER,
               chunk: int = CHUNK):
    """Phase 2: the default config through ``fit``; returns its
    (recall, auc)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import funcsne
    from repro.data import synthetic
    from repro.kernels import backend as kernel_backend

    chk = Checks("fit")
    X = jnp.asarray(synthetic.hierarchical_cells(n=n, dim=DIM_HD,
                                                 seed=SEED)[0])
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=DIM_HD, dim_ld=DIM_LD,
                                backend=backend)
    flags = {f: getattr(cfg, f) for f in ("gather_fused", "scatter_fused",
                                          "merge_fused", "cand_fused")}
    log(f"[fit] n={n} dim_hd={DIM_HD} dim_ld={DIM_LD} iters={n_iter} "
        f"chunk={chunk} flags={flags}")
    resolved = kernel_backend.resolve(cfg.backend)
    log("[fit] backend per family: " + json.dumps(
        {f: resolved for f in ("pairwise_sqdist", "knn_merge",
                               "ne_forces")}))
    st = _fit(cfg, X, n_iter, chunk, "fit")
    if resolved == "pallas":
        kernels = _step_kernels(cfg, st, X, n_iter, chunk)
        log(f"[fit] Mosaic kernels in the chunk program: {kernels}")
        chk("chunk program runs the candidate-fused merge kernel for HD "
            "and LD refinement", kernels.get("knn_merge_cand") == 2)
        chk("chunk program runs the force kernel",
            any(k.startswith("ne_forces") for k in kernels))
    _no_fallback(chk)
    chk("Y finite", bool(np.isfinite(np.asarray(st.Y)).all()))
    recall, auc = _quality(st, X)
    log(f"[fit] random 2-D embedding R_NX AUC: "
        f"{_random_auc(X):.6f} (what MIN_RNX_AUC must exceed)")
    chk(f"HD KNN recall@{cfg.k_hd} (2048 sampled rows) >= {MIN_RECALL}",
        recall >= MIN_RECALL, f"recall={recall:.4f}")
    chk(f"R_NX AUC (2048 sampled rows) >= {MIN_RNX_AUC}",
        auc >= MIN_RNX_AUC, f"auc={auc:.4f}")
    chk.end()
    return recall, auc


def four_chips(backend: str = "auto", n: int = N,
               n_iter: int = N_ITER_FOUR, chunk: int = CHUNK,
               devices=None) -> None:
    """``fit_elastic`` over four devices against the one-chip ``fit``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import funcsne
    from repro.data import synthetic

    chk = Checks("four-chips")
    devices = list(jax.devices() if devices is None else devices)[:4]
    X = jnp.asarray(synthetic.hierarchical_cells(n=n, dim=DIM_HD,
                                                 seed=SEED)[0])
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=DIM_HD, dim_ld=DIM_LD,
                                backend=backend)
    r1, a1 = _quality(_fit(cfg, X, n_iter, chunk, "one-chip"), X)
    log(f"[one-chip] recall={r1:.4f} auc={a1:.4f}")
    st = _fit(cfg, X, n_iter, chunk, "four-chips", devices=devices)
    mesh_devs = sorted(st.Y.sharding.device_set, key=lambda d: d.id)
    chk("mesh spans 4 distinct devices", len(mesh_devs) == 4,
        str([str(d) for d in mesh_devs]))
    chk("mesh devices share the first device's platform",
        all(d.platform == devices[0].platform for d in mesh_devs))
    for d in mesh_devs:
        stats = d.memory_stats() or {}
        used = int(stats.get("bytes_in_use", 0))
        chk(f"device {d.id} reports bytes in use", used > 0,
            f"bytes_in_use={used}")
    _no_fallback(chk)
    chk("Y finite", bool(np.isfinite(np.asarray(st.Y)).all()))
    r4, a4 = _quality(st, X)
    chk(f"recall within {FOUR_CHIP_BAND} of one chip",
        abs(r4 - r1) <= FOUR_CHIP_BAND, f"four={r4:.4f} one={r1:.4f}")
    chk(f"R_NX AUC within {FOUR_CHIP_BAND} of one chip",
        abs(a4 - a1) <= FOUR_CHIP_BAND, f"four={a4:.4f} one={a1:.4f}")
    chk.end()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only fit_elastic over 4 chips and the "
                         "one-chip fit it is compared with")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable(ROOT)}")
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        four_chips()
    else:
        kernel_parity("pallas")
        end_to_end()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
