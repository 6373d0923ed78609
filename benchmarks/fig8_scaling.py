"""Paper Fig. 8: wall time vs dataset size at fixed dim (32).

Verifies the O(N) per-iteration claim: time/iter should grow ~linearly in
N.  ``fig8_linearity`` is the measured slope ratio over the ideal linear
ratio -- 1.0 means exactly linear, >1 superlinear -- computed from the
n-sweep endpoints.  Also compares the always-refine-HD variant (paper's
dashed line) against the default probabilistic refresh.

The chunked-driver rows time the scan-chunked step (§Perf H15) at the
sweep's largest size: ``fig8_chunked_T1`` dispatches every iteration (the
per-dispatch baseline the host loop used to pay), ``fig8_chunked_T50``
runs 50 iterations per dispatch; the ratio row is the amortisation win.
The two are timed *paired* (interleaved, best-of-trials) so shared-host
load hits both equally.

Run directly (``python -m benchmarks.fig8_scaling --smoke --json f.json``)
this module is its own harness: unlike ``benchmarks.run`` it does NOT
swallow exceptions, so CI uses ``--smoke`` as a driver-level regression
gate that actually fails the workflow.
"""
import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.common import row, timed
from repro.core import funcsne
from repro.data.synthetic import blobs


def _copy(st):
    return jax.tree.map(lambda a: jnp.array(a, copy=True), st)


def _chunked_rows(n, Xj, iters, chunk_sizes, trials=5):
    """Per-iteration us for each chunk size, paired/interleaved."""
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=Xj.shape[1])
    hp = funcsne.default_hparams(n)
    st0 = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg)

    runners = {}
    for T in chunk_sizes:
        chunk = funcsne.make_chunked_step(cfg, T)
        n_chunks = max(1, iters // T)

        def run(chunk=chunk, n_chunks=n_chunks, T=T):
            st = _copy(st0)               # the program donates its input
            for _ in range(n_chunks):
                st, _, _ = chunk(st, Xj, hp)
            jax.block_until_ready(st.Y)
            return n_chunks * T

        run()                             # compile outside the clock
        runners[T] = run

    best = {T: float("inf") for T in chunk_sizes}
    for t in range(trials):
        order = chunk_sizes if t % 2 == 0 else tuple(reversed(chunk_sizes))
        for T in order:
            steps, dt = timed(runners[T])
            best[T] = min(best[T], dt * 1e6 / steps)
    rows = [row(f"fig8_chunked_T{T}_n{n}", best[T],
                f"{max(1, iters // T)}x{T}-step dispatches")
            for T in chunk_sizes]
    if len(chunk_sizes) >= 2:
        t1, tb = chunk_sizes[0], chunk_sizes[-1]
        ratio = best[t1] / max(best[tb], 1e-9)
        rows.append(row(f"fig8_chunked_amortisation_n{n}", ratio,
                        f"T{t1}_us/T{tb}_us={ratio:.3f} (ratio, not us)"))
    return rows


def _health_rows(n, Xj, iters, T, trials=5):
    """Full-chunk A/B of the in-scan health telemetry (resilience layer):
    ``health_metrics=True`` (finite-fraction / max-|Y| / first-bad-step
    scalars folded into the chunk scan) vs ``False`` (the pre-resilience
    ChunkMetrics).  Paired/interleaved best-of like the chunked rows; the
    acceptance bar is <= 5% overhead on the full step."""
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=Xj.shape[1])
    hp = funcsne.default_hparams(n)
    st0 = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg)
    n_chunks = max(1, iters // T)

    runners = {}
    for health in (False, True):
        chunk = funcsne.make_chunked_step(cfg, T, health_metrics=health)

        def run(chunk=chunk):
            st = _copy(st0)               # the program donates its input
            for _ in range(n_chunks):
                st, _, _ = chunk(st, Xj, hp)
            jax.block_until_ready(st.Y)
            return n_chunks * T

        run()                             # compile outside the clock
        runners[health] = run

    best = {h: float("inf") for h in runners}
    for t in range(trials):
        order = (False, True) if t % 2 == 0 else (True, False)
        for h in order:
            steps, dt = timed(runners[h])
            best[h] = min(best[h], dt * 1e6 / steps)
    ratio = best[True] / max(best[False], 1e-9)
    return [
        row(f"fig8_health_off_n{n}", best[False],
            f"T{T} chunks, no health telemetry"),
        row(f"fig8_health_on_n{n}", best[True],
            f"T{T} chunks, in-scan health telemetry"),
        row(f"fig8_health_overhead_n{n}", ratio,
            f"on_us/off_us={ratio:.3f} (ratio, not us; bar <=1.05)"),
    ]


def _audit_rows(n, Xj, iters, T, trials=5):
    """Full-chunk A/B of the chunk-boundary state auditor (resilience
    layer): the driver loop with ``audit_state`` + its host read after
    EVERY chunk (``audit_every=1``, the worst case) vs the plain loop.
    The audit is one fused pass over the index tables with no gathers,
    so the acceptance bar is <=1% per chunk at production chunk sizes.
    Paired/interleaved best-of like the chunked rows."""
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=Xj.shape[1])
    hp = funcsne.default_hparams(n)
    st0 = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg)
    n_chunks = max(1, iters // T)
    chunk = funcsne.make_chunked_step(cfg, T)

    def run_plain():
        st = _copy(st0)                   # the program donates its input
        for _ in range(n_chunks):
            st, _, _ = chunk(st, Xj, hp)
        jax.block_until_ready(st.Y)
        return n_chunks * T

    def run_audited():
        st = _copy(st0)
        for _ in range(n_chunks):
            st, _, _ = chunk(st, Xj, hp)
            jax.device_get(funcsne.audit_state(st, cfg, Xj))
        jax.block_until_ready(st.Y)
        return n_chunks * T

    runners = {False: run_plain, True: run_audited}
    for r in runners.values():
        r()                               # compile outside the clock
    best = {h: float("inf") for h in runners}
    for t in range(trials):
        order = (False, True) if t % 2 == 0 else (True, False)
        for h in order:
            steps, dt = timed(runners[h])
            best[h] = min(best[h], dt * 1e6 / steps)
    ratio = best[True] / max(best[False], 1e-9)
    return [
        row(f"fig8_audit_off_n{n}", best[False],
            f"T{T} chunks, no boundary audit"),
        row(f"fig8_audit_on_n{n}", best[True],
            f"T{T} chunks, audit_every=1 boundary audit"),
        row(f"fig8_audit_overhead_n{n}", ratio,
            f"on_us/off_us={ratio:.3f} (ratio, not us; bar <=1.01)"),
    ]


def run(sizes=(512, 1024, 2048, 4096), iters=120, chunk_sizes=(1, 50)):
    rows = []
    per_iter = {}
    for n in sizes:
        X, _ = blobs(n=n, dim=32, n_centers=8, center_std=6.0, seed=0)
        Xj = jnp.asarray(X)
        for always, tag in ((False, "default"), (True, "always_refine")):
            cfg = funcsne.FuncSNEConfig(
                n_points=n, dim_hd=32,
                min_refresh_prob=1.0 if always else 0.05)
            hp = funcsne.default_hparams(n)
            st = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg)
            step = funcsne.make_step(cfg)
            st = step(st, Xj, hp)           # compile
            jax.block_until_ready(st.Y)

            def loop(st=st):
                s = st
                for _ in range(iters):
                    s = step(s, Xj, hp)
                jax.block_until_ready(s.Y)
                return s

            _, dt = timed(loop)
            us = dt * 1e6 / iters
            per_iter[(tag, n)] = us
            rows.append(row(f"fig8_n{n}_{tag}", us, f"n={n}"))
    slope = (per_iter[("default", sizes[-1])]
             / max(per_iter[("default", sizes[0])], 1e-9))
    ideal = sizes[-1] / sizes[0]
    rows.append(row("fig8_linearity", slope / ideal,
                    f"t({sizes[-1]})/t({sizes[0]})={slope:.2f};"
                    f"ideal={ideal:.1f};score=slope/ideal (1.0=linear)"))

    # chunked driver at the largest size: per-dispatch vs 50-per-dispatch
    n = sizes[-1]
    X, _ = blobs(n=n, dim=32, n_centers=8, center_std=6.0, seed=0)
    rows += _chunked_rows(n, jnp.asarray(X), iters, tuple(chunk_sizes))

    # health-telemetry A/B (resilience layer): the on-device probes must
    # stay in the noise next to the force phase
    rows += _health_rows(n, jnp.asarray(X), iters, chunk_sizes[-1])

    # chunk-boundary auditor A/B (trusted recovery): worst-case
    # audit_every=1 must stay <=1% next to a full chunk dispatch
    rows += _audit_rows(n, jnp.asarray(X), iters, chunk_sizes[-1])
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep: CI driver-level regression gate")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write {name: us_per_call} JSON to PATH")
    args = ap.parse_args()
    kwargs = dict(sizes=(256, 512), iters=16, chunk_sizes=(1, 8)) \
        if args.smoke else {}
    results = {}
    print("name,us_per_call,derived")
    for r in run(**kwargs):
        print(r, flush=True)
        name, us = str(r).split(",")[:2]
        results[name] = float(us)
    if args.json:
        out = Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(results, indent=2, sort_keys=True) + "\n")
        print(f"# wrote {len(results)} results to {args.json}", flush=True)


if __name__ == "__main__":
    main()
