"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Each module's ``run()``
reproduces the measurement behind the corresponding paper artifact at
CPU-feasible scale; the roofline table comes from the dry-run
(repro.launch.dryrun), not from here.

``--json PATH`` additionally writes the machine-readable results
(``{name: us_per_call}``).  They are CPU and interpret-mode numbers;
the on-chip benchmark is ``bench/``.

  PYTHONPATH=src python -m benchmarks.run [--only fig6,fig8] [--fast]
                                          [--json PATH]
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

MODULES = ["fig4_feedback_loop", "fig6_rnx_quality", "fig7_knn_vs_nnd",
           "fig8_scaling", "table2_one_shot", "fig3_alpha_fragmentation",
           "bench_kernels"]

FAST_KW = {
    "fig4_feedback_loop": dict(n=600, iters=120, probe_every=60),
    "fig6_rnx_quality": dict(n=600, iters=250),
    "fig7_knn_vs_nnd": dict(n=800, iters=200),
    "fig8_scaling": dict(sizes=(512, 1024, 2048), iters=60),
    "table2_one_shot": dict(n=800, iters=300),
    "fig3_alpha_fragmentation": dict(n=700, warmup=250, per_level=150),
    "bench_kernels": dict(ns=(1024, 4096), repeats=5),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module prefixes")
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write {name: us_per_call} JSON to PATH")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])

    selected = MODULES
    if args.only:
        keys = args.only.split(",")
        selected = [m for m in MODULES if any(m.startswith(k) for k in keys)]

    results = {}
    print("name,us_per_call,derived")
    for mod_name in selected:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}",
                             fromlist=["run"])
            kwargs = FAST_KW.get(mod_name, {}) if args.fast else {}
            for r in mod.run(**kwargs):
                print(r, flush=True)
                try:
                    name, us = str(r).split(",")[:2]
                    results[name] = float(us)
                except ValueError:
                    pass
            print(f"# {mod_name} done in {time.time() - t0:.1f}s",
                  flush=True)
        except Exception:
            print(f"# {mod_name} FAILED:", flush=True)
            traceback.print_exc()

    if args.json:
        out = Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(results, indent=2, sort_keys=True) + "\n")
        print(f"# wrote {len(results)} results to {args.json}", flush=True)


if __name__ == "__main__":
    main()
