"""Shared plumbing of the benchmark: cell lookup, compile cache, devices.

Everything a cell is made of is found by name: the workload entry and
the metric lists in ``BENCHMARK.json``, the deployment in
``bench/configs/<config>.json``, the traffic mix in
``bench/traffic/<traffic>.json``, the limits of the output check in
``bench/limits/<workload>.json``, one reader per per-layer metric in
``bench/metrics/<metric>.py`` and one op/byte model per kernel in
``bench/roofline/<kernel>.py``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# fixed path inside the checkout: the directory is part of the cache key
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class CellError(RuntimeError):
    """The benchmark's files do not describe the requested cell."""


def find_cell(name: str, root: Path = ROOT) -> dict:
    """Everything the harness needs to run workload ``name``."""
    spec_path = root / "BENCHMARK.json"
    if not spec_path.is_file():
        raise CellError(f"no {spec_path.name} under {root}")
    spec = load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    bench = root / "bench"
    return {
        "spec": spec,
        "cell": cell,
        "config": load_json(root / entry["file"]),
        "traffic": load_json(bench / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(bench / "limits" / f"{name}.json")["limits"],
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def enable_cache(jax) -> str:
    """Persistent compilation cache at a fixed path in the checkout.

    Every compiled program is kept, however short its compile, so the
    small eager programs of the program's initialisation come from the
    cache too.
    """
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size limit, so no eviction bookkeeping: the cache holds one
    # cell's few programs
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)


def lookup_peaks(kind: str, path: Path = BENCH / "peaks.json") -> dict:
    """Published peaks of ``kind``; a device missing from the table is an
    error, never a default."""
    table = load_json(path)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {path.name}; "
                       f"known: {sorted(table)}")
    return table[kind]


def load_module(path: Path):
    """Import a file by path (metric and roofline files carry dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_plugin_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    if spec is None or spec.loader is None:
        raise CellError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
