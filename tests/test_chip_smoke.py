"""``chip_smoke.py`` off the chip: its phases rehearsed on the CPU at a
tiny size (kernel parity in interpret mode, ``fit`` on the XLA
references), and its refusals without a TPU or outside the repository."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def test_kernel_parity_phase_passes_in_interpret_mode():
    chip_smoke.kernel_parity("interpret", n=256)


def test_fit_phase_clears_its_thresholds_on_xla():
    recall, auc = chip_smoke.end_to_end("xla", n=2048, n_iter=200, chunk=50)
    assert recall >= chip_smoke.MIN_RECALL and auc >= chip_smoke.MIN_RNX_AUC


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu(tmp_path):
    r = _run(ROOT / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_refuses_outside_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
