"""End-to-end FUnc-SNE embedding launcher (the paper's workload).

  PYTHONPATH=src python -m repro.launch.embed --n 5000 --dataset cells \
      --alpha 1.0 --iters 1500 --dim-ld 2 --chunk 50

Runs on the scan-chunked driver: ``--chunk T`` iterations execute per
device dispatch (T=1 reproduces the per-step dispatch baseline).  A full
warmup chunk runs before the clock starts, so the reported steps/sec
excludes compile time and is the paper-style speed number.  Prints the
R_NX AUC over a fixed sample of 2048 query rows (exact KNN in blocks, so
it works at any n) and (optionally) writes the embedding to .npy.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import funcsne
from repro.core.quality import embedding_quality
from repro.data import synthetic
from repro.launch import compile_cache


def load_dataset(name: str, n: int, seed: int = 0):
    if name == "blobs":
        return synthetic.blobs(n=n, n_centers=8, center_std=6.0, seed=seed)
    if name == "cells":
        X, major, _ = synthetic.hierarchical_cells(n=n, seed=seed)
        return X, major
    if name == "coil":
        return synthetic.coil_rings(n_objects=max(4, n // 72),
                                    n_per_object=72, seed=seed)
    if name == "mnist-like":
        return synthetic.mnist_like(n=n, seed=seed)
    raise ValueError(name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cells",
                    choices=["blobs", "cells", "coil", "mnist-like"])
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--iters", type=int, default=1500,
                    help="rounded to a multiple of --chunk")
    ap.add_argument("--chunk", type=int, default=50,
                    help="iterations per device dispatch (1 = per-step "
                         "dispatch baseline)")
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--perplexity", type=float, default=20.0)
    ap.add_argument("--dim-ld", type=int, default=2)
    ap.add_argument("--out", default=None)
    ap.add_argument("--devices", type=int, default=1,
                    help=">1 routes through the elastic coordinator "
                         "(repro.runtime.coordinator.fit_elastic) on a "
                         "mesh over that many devices")
    ap.add_argument("--hosts", type=int, default=1,
                    help="simulated hosts (contiguous device blocks); "
                         "per-host checkpoint shard files when "
                         "--checkpoint-dir is set")
    ap.add_argument("--model", type=int, default=1,
                    help="requested model-axis width (remesh picks the "
                         "largest feasible width <= this)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="arm checkpoint/rollback resilience; required "
                         "to survive host loss")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --checkpoint-dir via the verified "
                         "fallback chain (damaged boundaries are "
                         "skipped with a checkpoint_fallback event)")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="run the chunk-boundary state auditor every N "
                         "healthy chunks (0 = off); a violation rolls "
                         "back like any health-probe trip")
    ap.add_argument("--num-processes", type=int, default=1,
                    help=">1 joins a real multi-process pod: every "
                         "process runs this command with the same "
                         "--coordinator and a distinct --process-id")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank in the pod "
                         "(required when --num-processes > 1)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0's distributed "
                         "coordinator (required when "
                         "--num-processes > 1)")
    args = ap.parse_args()
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    compile_cache.enable(Path(__file__).resolve().parents[3])

    multiprocess = args.num_processes > 1
    if multiprocess:
        if args.process_id is None or args.coordinator is None:
            ap.error("--num-processes > 1 requires --process-id "
                     "and --coordinator")
        if args.hosts != 1:
            ap.error("--hosts simulates a pod on one process; a real "
                     "multi-process pod must keep --hosts 1")
        # must run before any JAX device use: join the pod, then the
        # elastic path below spans every process's devices
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.num_processes,
                                   process_id=args.process_id)

    X, labels = load_dataset(args.dataset, args.n)
    Xj = jnp.asarray(X, jnp.float32)
    n = X.shape[0]
    T = max(1, min(args.chunk, args.iters))
    n_chunks = max(1, args.iters // T)
    iters = n_chunks * T                 # schedule horizon == steps run
    cfg = funcsne.FuncSNEConfig(n_points=n, dim_hd=X.shape[1],
                                dim_ld=args.dim_ld)
    hp = funcsne.default_hparams(n, alpha=args.alpha,
                                 perplexity=args.perplexity)

    if args.devices > 1 or multiprocess:
        # distributed path: the elastic coordinator owns the loop
        # (mesh-reduced health probes, per-host checkpoint shards,
        # remesh-and-resume on host loss)
        from repro.core.resilience import ResiliencePolicy
        from repro.runtime.coordinator import fit_elastic
        policy = ResiliencePolicy(checkpoint_dir=args.checkpoint_dir,
                                  audit_every=args.audit_every) \
            if args.checkpoint_dir or args.audit_every else None
        if multiprocess:
            # the pod's mesh spans every process's devices; each
            # process checkpoints only its own row shard
            devices = jax.devices()
        else:
            devices = jax.devices()[:args.devices]
        first = jax.process_index() == 0
        t0 = time.time()
        st = fit_elastic(Xj, cfg=cfg, n_iter=iters, chunk_size=T,
                         hparams=hp, n_hosts=args.hosts,
                         model=args.model, devices=devices,
                         resilience=policy,
                         resume_from=args.checkpoint_dir
                         if args.resume else None)
        jax.block_until_ready(st.Y)
        dt = time.time() - t0
        Y = np.asarray(jax.device_get(st.Y))
        if first:
            q = float(embedding_quality(jnp.asarray(X), jnp.asarray(Y)))
            print(f"[embed] {args.dataset} n={n} iters={iters} chunk={T} "
                  f"devices={len(devices)} hosts={args.hosts} "
                  f"processes={args.num_processes}: {dt:.1f}s "
                  f"(compile included), R_NX AUC={q:.3f}")
            if args.out:
                np.save(args.out, Y)
                print(f"[embed] wrote {args.out}")
        return

    if args.checkpoint_dir or args.audit_every:
        # resilient single-device path: funcsne.fit owns the loop
        # (checkpoints, verified resume, rollback, optional audit)
        from repro.core.resilience import ResiliencePolicy
        policy = ResiliencePolicy(checkpoint_dir=args.checkpoint_dir,
                                  audit_every=args.audit_every)
        t0 = time.time()
        st, _ = funcsne.fit(Xj, cfg=cfg, n_iter=iters, chunk_size=T,
                            hparams=hp, resilience=policy,
                            resume_from=args.checkpoint_dir
                            if args.resume else None)
        jax.block_until_ready(st.Y)
        dt = time.time() - t0
        Y = np.asarray(jax.device_get(st.Y))
        q = float(embedding_quality(jnp.asarray(X), jnp.asarray(Y)))
        resumed = [e for e in policy.events
                   if e["kind"] == "checkpoint_fallback"]
        note = f", {len(resumed)} damaged boundary(ies) skipped" \
            if resumed else ""
        print(f"[embed] {args.dataset} n={n} iters={iters} chunk={T} "
              f"alpha={args.alpha}: {dt:.1f}s (compile included), "
              f"R_NX AUC={q:.3f}{note}")
        if args.out:
            np.save(args.out, Y)
            print(f"[embed] wrote {args.out}")
        return

    st = funcsne.init_state(jax.random.PRNGKey(0), Xj, cfg,
                            perplexity=hp.perplexity)
    chunk = funcsne.make_chunked_step(cfg, T,
                                      schedule=funcsne.default_schedule,
                                      n_iter=iters)

    # warmup chunk on a throwaway state copy (the program donates its
    # input): compile time never enters the clock below
    warm = jax.tree.map(lambda a: jnp.array(a, copy=True), st)
    warm, _, m = chunk(warm, Xj, hp)
    jax.block_until_ready(m.step)

    t0 = time.time()
    for _ in range(n_chunks):
        st, _, metrics = chunk(st, Xj, hp)
    jax.block_until_ready(st.Y)
    dt = time.time() - t0

    Y = np.asarray(jax.device_get(st.Y))
    q = float(embedding_quality(jnp.asarray(X), jnp.asarray(Y)))
    print(f"[embed] {args.dataset} n={n} iters={iters} chunk={T} "
          f"alpha={args.alpha}: {dt:.1f}s "
          f"({iters / dt:.0f} it/s, compile excluded), R_NX AUC={q:.3f}")
    if args.out:
        np.save(args.out, Y)
        print(f"[embed] wrote {args.out}")


if __name__ == "__main__":
    main()
