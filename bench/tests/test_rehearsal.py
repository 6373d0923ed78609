"""CPU rehearsal of each cell at a tiny size through ``bench/run.py``'s
own code path, and the refusals: no TPU, an unknown device kind, a
checkout without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import common, run

TINY = {"atlas-262k.batch": 1024, "mnist-70k.frames": 1000}
SEED = 2 ** 31 + 12345


def _run(capsys, workload, trace=0, seconds=2.0):
    rc = run.main(["--workload", workload, "--seed", str(SEED),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  require_tpu=False,
                  overrides={"config": {"n": TINY[workload]}})
    out = capsys.readouterr()
    return rc, out


@pytest.mark.parametrize("workload", sorted(TINY))
def test_cell_rehearsal(capsys, workload):
    rc, out = _run(capsys, workload)
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    spec = common.find_cell(workload)
    want = {m["name"] for m in spec["end_to_end"]}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["compiles_in_window"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == set(spec["limits"])
    assert out.err.strip().splitlines()[-1].startswith("bench: check ")


def test_traced_rehearsal_reports_host_metric(capsys):
    rc, out = _run(capsys, "mnist-70k.frames", trace=1)
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["device"]["window_s"] > 0
    assert "breakdown" in line
    assert line["metrics"]["host_ms_per_frame.frames"]["value"] > 0


def test_refuses_to_measure_without_tpu(capsys):
    rc = run.main(["--workload", "atlas-262k.batch", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        common.lookup_peaks("TPU v0 imaginary")
    assert common.lookup_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_checkout_without_program_fails(tmp_path, capsys, monkeypatch):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(run, "ROOT", tmp_path)
    rc = run.main(["--workload", "atlas-262k.batch", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], require_tpu=False)
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""


def test_unknown_workload_fails(capsys):
    rc = run.main(["--workload", "no-such.cell", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], require_tpu=False)
    assert rc != 0 and capsys.readouterr().out == ""


@pytest.mark.parametrize("workload", sorted(TINY))
def test_program_seed_fixes_the_stream_not_the_data(workload):
    import jax
    from repro.core import funcsne

    from bench import generator
    spec = common.find_cell(workload)
    spec["config"]["n"] = TINY[workload]
    assert "program_seed" in spec["traffic"]
    a, b = (generator.make(jax, funcsne, spec, s, None)
            for s in (SEED, SEED + 1))
    assert (jax.random.key_data(a.init_key)
            == jax.random.key_data(b.init_key)).all()
    assert not (a.X == b.X).all()


# A four-chip cell on the mesh kind, added to a copy of the checkout as a
# later PR adds one: a configuration, a limits file, per-layer readers and
# entries in BENCHMARK.json, with no edit to a file the benchmark has.
MESH_CELL = "atlas-tiny.batch4"
MESH_READERS = {
    "hd_refine_ms_per_fire.batch4":
        "from bench import phases\n\n\ndef read(run):\n"
        "    evs = phases.run_events(run, 'batch4',\n"
        "                            holes=run.trace.spans_named('init'))\n"
        "    return phases.per_run_ms(evs, 'hd_refine')\n",
    "collective_ms_per_iter.batch4":
        "from bench import trace\n\n\ndef read(run):\n"
        "    count, iv = run.trace.collectives(0)\n"
        "    if not count or not run.iterations:\n"
        "        return None\n"
        "    return 1e-6 * trace.length(iv) / run.iterations\n"}
# The checks the program's mesh path fails at every size, against the
# configuration's float32: it all-gathers the HD distances and psums the
# force field in bfloat16, and keeps no LD distances (PERF.md, section 7).
# Every other check holds.
MESH_DEPARTURES = {"hd_d_err", "hd_merge_faults", "force_err", "ld_d_err"}
# Runs ``run.main`` on the mesh cell of the checkout in argv[1], untraced
# and traced, then the cell's readers on a trace whose events are the
# instructions of the mesh program the readers compile (a CPU trace has
# no device op events); prints what it found as one JSON line.
MESH_DRIVER = r"""
import contextlib, io, json, sys
sys.path.insert(0, sys.argv[1])
from bench import common, phases, run
from bench import trace as trace_lib

workload, argv = sys.argv[2], sys.argv[3:]
out = {}
for traced in ("0", "1"):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", workload, *argv, "--trace", traced],
                      require_tpu=False)
    out[traced] = json.loads(buf.getvalue().strip().splitlines()[-1]) \
        if rc == 0 else rc
spec = common.find_cell(workload)
program = phases.window_program(json.dumps(spec["config"], sort_keys=True),
                                spec["cell"]["traffic"],
                                spec["cell"]["chips"])
ms = 1_000_000
ops = [(f"%{n} = {shape} {op}(f32[] %a)", i * ms, (i + 1) * ms)
       for i, (n, shape, op) in enumerate(program)]
tr = trace_lib.Trace({0: ops}, [("bench.window", 0, len(ops) * ms)])
count, iv = tr.collectives(0)


class Run:
    config, chips, trace = spec["config"], spec["cell"]["chips"], tr
    summary, iterations = tr.summary(), 1


out["phases"] = sorted({p for p, _, _ in phases.events(tr, program)} - {None})
out["collectives"] = [count, trace_lib.length(iv)]
out["readers"] = run.per_layer(spec, Run)
print(json.dumps(out))
"""


def _mesh_checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(common.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(common.ROOT / "src")
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    config = common.load_json(common.BENCH / "configs" / "atlas-262k.json")
    config.update(name="atlas-tiny", n=1024)
    (root / "bench" / "configs" / "atlas-tiny.json").write_text(
        json.dumps(config))
    shutil.copy(common.BENCH / "limits" / "atlas-262k.batch.json",
                root / "bench" / "limits" / f"{MESH_CELL}.json")
    for name, code in MESH_READERS.items():
        (root / "bench" / "metrics" / f"{name}.py").write_text(code)
        spec["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower",
             "source": "device_trace", "layer": "chunk program",
             "moves": "iters_per_s", "workloads": [MESH_CELL]})
    spec["configs"].append({"name": "atlas-tiny", "source": "test",
                            "file": "bench/configs/atlas-tiny.json",
                            "reduced": ["n"], "why": "test"})
    spec["workloads"].append({"name": MESH_CELL, "config": "atlas-tiny",
                              "traffic": "batch4", "chips": 4,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "iters_per_s":
            m["workloads"].append(MESH_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _drive(root, devices: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"))
    return subprocess.run(
        [sys.executable, "-c", MESH_DRIVER, str(root), MESH_CELL, "--seed",
         str(SEED), "--seconds", "2"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


def test_mesh_cell_rehearsal(tmp_path):
    """The mesh kind on four virtual CPU devices through ``run.main``."""
    proc = _drive(_mesh_checkout(tmp_path), 4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    line, traced = out["0"], out["1"]
    for ln in (line, traced):
        assert ln["device"]["count"] == 4
        assert ln["compiles_in_window"] == 0
        assert ln["attempted"] > 0 and ln["failed"] == 0
        failing = {k for k, v in ln["checks"].items()
                   if not v["value"] <= v["limit"]}
        assert failing <= MESH_DEPARTURES, ln["checks"]
        assert ln["correct"] is (not failing)
    assert set(line["metrics"]) == {"iters_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert traced["device"]["window_s"] > 0 and "breakdown" in traced
    assert out["phases"] == sorted(
        ("hd_refine", "sigma_refresh", "ld_refine", "forces_update"))
    assert out["collectives"][0] > 0 and out["collectives"][1] > 0
    assert set(out["readers"]) == set(MESH_READERS)
    assert all(m["value"] > 0 for m in out["readers"].values())


def test_mesh_cell_refuses_fewer_chips(tmp_path):
    proc = _drive(_mesh_checkout(tmp_path), 2)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "uses 2 of the 4 chips the cell asks for" in proc.stderr
