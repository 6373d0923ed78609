#!/usr/bin/env python3
"""Compile each cell's programs at their real size for a described v5e.

  JAX_PLATFORMS=cpu python bench/compile_check.py [--workload <cell> ...]

No chip is needed: the TPU compiler compiles for a described
``v5e:2x2`` topology, one chip of it, or as many as a ``mesh_chunked``
cell asks for.  For every program the window (and its set-up) runs,
this prints the Mosaic kernels and the collectives in it and
``memory_analysis()``: argument, output and temporary bytes on each
chip.  A program that does not fit, or a kernel Mosaic refuses, fails
here as it would on the chip.  Kernels are compiled as the chip runs
them (``backend="pallas"``; on a TPU ``"auto"`` resolves to it).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import trace  # noqa: E402


def kernels(compiled) -> list:
    return sorted(set(re.findall(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', compiled.as_text())))


def collectives(compiled) -> list:
    ops = {trace.opcode(line) for line in compiled.as_text().splitlines()}
    return sorted(op for op in ops if trace.COLLECTIVE.match(op))


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")}


def programs(jax, funcsne, spec: dict, devices):
    """(name, jitted fn, abstract args) of each program of the cell, on
    the first of ``devices`` or, for a mesh, on as many as it asks for."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench import generator
    from bench.reference import knn as knn_ref

    c, tr = spec["config"], spec["traffic"]
    fs = dict(c["funcsne"], backend="pallas")
    cfg = funcsne.FuncSNEConfig(n_points=c["n"], dim_hd=c["dim_hd"],
                                dim_ld=c["dim_ld"], **fs)

    def place(tree, sharding=SingleDeviceSharding(devices[0])):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((c["n"], c["dim_hd"]), jnp.float32)
    init = jax.jit(lambda k, X: funcsne.init_state(k, X, cfg,
                                                   validate=False))
    st = jax.eval_shape(init, key, x)
    hp = jax.eval_shape(lambda: funcsne.HParams(**{
        k: jnp.float32(v) for k, v in generator.base_hparams(c["n"],
                                                           {}).items()}))
    if tr["kind"] == "mesh_chunked":
        prog, init, x_sharding, st_sharding = generator.mesh_program(
            jax, funcsne, cfg, tr, list(devices),
            int(spec["cell"]["chips"]))
        return [("init_state", init,
                 (place(key, st_sharding), place(x, x_sharding))),
                ("make_distributed_step", prog,
                 (place(st, st_sharding), place(x, x_sharding),
                  place(hp, st_sharding)))]
    out = [("init_state", init, place((key, x)))]
    if tr["kind"] == "chunked":
        prog = funcsne.make_chunked_step(
            cfg, int(tr["iters_per_dispatch"]),
            schedule=funcsne.default_schedule, n_iter=int(tr["n_iter"]))
        out.append(("make_chunked_step", prog, place((st, x, hp))))
    else:
        out.append(("make_step", funcsne.make_step(cfg),
                    place((st, x, hp))))
        s = int(tr["recall_sample"])
        rows = jax.ShapeDtypeStruct((s,), jnp.int32)
        out.append(("exact_knn_rows", jax.jit(
            lambda X, r: knn_ref.exact_knn_rows(X, r, cfg.k_hd)),
            place((x, rows))))
        true = jax.ShapeDtypeStruct((s, cfg.k_hd), jnp.int32)
        out.append(("recall", knn_ref.recall,
                    place((st.hd_idx, rows, true))))
    return out


def main(argv=None) -> int:
    from bench import common
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    names = args.workload or [w["name"] for w in common.load_json(
        ROOT / "BENCHMARK.json")["workloads"]]

    import jax
    from jax.experimental import topologies

    from repro.core import funcsne

    # a described-chip compile cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    report = {}
    for name in names:
        spec = common.find_cell(name)
        for prog_name, fn, args_ in programs(jax, funcsne, spec,
                                             topo.devices):
            compiled = fn.lower(*args_).compile()
            row = {"kernels": kernels(compiled),
                   "collectives": collectives(compiled), **memory(compiled)}
            report[f"{name}/{prog_name}"] = row
            print(f"{name}/{prog_name}: {json.dumps(row)}", flush=True)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
