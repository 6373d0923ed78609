"""CPU rehearsal of each cell at a tiny size through ``bench/run.py``'s
own code path, and the refusals: no TPU, an unknown device kind, a
checkout without the program."""
import json
import shutil

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
