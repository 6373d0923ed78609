#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its deployment,
traffic mix, limits, per-layer readers and kernel op/byte models are
files under ``bench/`` found by name (see ``bench/common.py``).

Set-up (``setup_s``) is everything from the start of this script to the
start of the window: imports, data made on the device from the seed,
the initial state, and one warm run of every program the window runs.
The window then runs for ``--seconds``.  With ``--trace 1`` the window
is traced by the profiler and the per-layer metrics replace the
end-to-end ones.  After the window the output check runs (see
``bench/check.py``) and prints each compared number beside its limit,
as the last lines of standard error and as the last key of the result;
the check's seconds (``check_s``) and their split by part
(``check_split``: device steps, copies to the host, ``bytes_of``, and
each part of the reference) go to standard error before them.
``--control 1`` also prints the numbers of the check's control, from
which, with the program's own, the limits in
``bench/limits/<cell>.json`` are set.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (which adds
``program_bytes``: arguments plus temporaries of the window's compiled
program, since the allocator's peak leaves them out).  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402

# monitoring event emitted once per program lowered for compilation,
# whether or not the persistent cache then answers it
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also print the numbers of the check's control "
                         "(the reference in bfloat16 in the program's "
                         "place) on standard error; benchmark runs leave "
                         "it off")
    return ap.parse_args(argv)


class Run:
    """What a per-layer reader may look at after a traced window."""

    def __init__(self, spec, trace, summary, result, peaks):
        self.config = spec["config"]
        self.chips = int(spec["cell"]["chips"])
        self.trace, self.summary = trace, summary
        self.iterations = result["iterations"]
        self.peaks = peaks

    def roofline(self, kernel: str):
        return common.load_module(common.BENCH / "roofline" / f"{kernel}.py")


def err(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def memory_peak(jax, devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def per_layer(spec, run) -> dict:
    out = {}
    for m in spec["per_layer"]:
        reader = common.load_module(common.BENCH / "metrics"
                                    / f"{m['name']}.py")
        value = reader.read(run)
        if value is None:
            continue
        if not isinstance(value, dict):
            value = {"value": value}
        out[m["name"]] = {**value, "unit": m["unit"]}
    return out


def main(argv=None, *, require_tpu: bool = True, overrides=None) -> int:
    args = parse(argv)
    try:
        spec = common.find_cell(args.workload)
    except (common.CellError, OSError, KeyError, ValueError) as e:
        err(str(e))
        return 2
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        err(f"no program under test: {src / 'repro'} is missing")
        return 2
    for part, values in (overrides or {}).items():
        spec[part].update(values)
    sys.path.insert(0, str(src))

    import jax
    common.enable_cache(jax)
    chips = int(spec["cell"]["chips"])
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            err(f"JAX found no TPU (platform {devices[0].platform})")
            return 3
        if len(devices) < chips:
            err(f"the cell needs {chips} chips, JAX found {len(devices)}")
            return 3
    devices = devices[:chips]
    kind = devices[0].device_kind
    try:
        peaks = common.lookup_peaks(kind)
    except KeyError as e:
        if require_tpu:
            err(str(e))
            return 4
        peaks = None

    from repro.core import funcsne

    from bench import check, generator
    from bench import trace as trace_lib

    annotate = jax.profiler.TraceAnnotation if args.trace \
        else (lambda name: contextlib.nullcontext())
    cell = generator.make(jax, funcsne, spec, args.seed, annotate)
    cell.setup()

    compiles = []
    listener = lambda event, *a, **k: compiles.append(event) \
        if event == COMPILE_EVENT else None  # noqa: E731
    jax.monitoring.register_event_duration_secs_listener(listener)
    tmp = tempfile.TemporaryDirectory() if args.trace else None
    if tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    cell.window(args.seconds)
    if tmp:
        jax.profiler.stop_trace()
    jax.monitoring.unregister_event_duration_listener(listener)
    result = cell.result
    mem = memory_peak(jax, devices)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    metrics = {}
    breakdown = None
    if tmp:
        paths = list(Path(tmp.name).rglob("*.xplane.pb"))
        if len(paths) != 1:
            err(f"expected one trace file, found {len(paths)}")
            return 5
        tr = trace_lib.Trace.from_file(paths[0], len(devices))
        tmp.cleanup()
        summary = tr.summary()
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        metrics = per_layer(spec, Run(spec, tr, summary, result, peaks))
    else:
        metrics = {m["name"]: {"value": result["metrics"][m["name"]],
                               "unit": m["unit"]}
                   for m in spec["end_to_end"]
                   if m["name"] in result["metrics"]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    # the check: steps of the window's program from its end state
    t_check = time.perf_counter()
    split = {}
    with check.timed(split, "readback"):
        X = jax.device_get(cell.X)
    pre, posts = cell.check_steps(split)
    device["program_bytes"] = cell.program_bytes
    numbers, info = check.readings(pre, posts, X, cell.fs, split)
    check_s = time.perf_counter() - t_check
    numbers.update(result.get("numbers", {}))
    correct, table = check.judge(numbers, spec["limits"])
    if args.control:
        ctl, _ = check.readings(pre, check.control_posts(pre, posts, X,
                                                         cell.fs),
                                X, cell.fs)
        err("control " + json.dumps(ctl))
    missing = [m["name"] for m in spec["end_to_end"]
               if not args.trace and m["name"] not in metrics]
    if missing:
        err(f"no reading of {missing}: the window did not reach it")
        correct = False

    err(f"setup_s={setup_s:.3f} window_s={result['window_s']:.3f} "
        f"check_s={check_s:.3f} check_split="
        f"{json.dumps({k: round(v, 3) for k, v in split.items()})} "
        f"attempted={result['attempted']} "
        f"compiles_in_window="
        f"{len(compiles)} check={json.dumps(info)} "
        f"info={json.dumps(result.get('info', {}))}")
    for name, row in table.items():
        err(f"check {name} = {row['value']} (limit {row['limit']})")
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compiles_in_window"] = len(compiles)
    line["checks"] = table
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
