"""``chip_smoke.py`` and the library body it runs.

The body runs here at a tiny size, with the kernels in interpret mode;
the script itself must refuse to report success anywhere but on a TPU.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.launch.smoke import SmokeCheckError, run_fedleo_smoke

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _tiny_smoke(**overrides):
    sizes = dict(train_samples=1600, test_samples=400, widths=(8, 16),
                 hidden=32, rounds=2, sim_epochs=8, learning_rate=0.05,
                 seed=0, log=lambda line: None)
    sizes.update(overrides)
    return run_fedleo_smoke(**sizes)


def test_smoke_body_at_tiny_size():
    r = _tiny_smoke()
    assert len(r.round_wall_s) == len(r.round_accuracy) == 2
    assert r.round_compiles[1] == 0       # a steady round compiles nothing
    assert r.compile_s > 0.0
    assert not r.mosaic_kernel            # interpret mode on the CPU
    assert r.aggregation_max_err <= 1e-6
    assert r.final_accuracy > max(0.1, r.initial_accuracy)


def test_smoke_body_fails_a_model_that_does_not_learn():
    with pytest.raises(SmokeCheckError, match="final accuracy"):
        _tiny_smoke(rounds=1, sim_epochs=1, learning_rate=0.0)


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _has_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_script_fails_on_the_cpu_backend():
    proc = _run(SCRIPT, SCRIPT.parent)
    assert proc.returncode != 0
    assert not _has_result_line(proc.stdout)
    assert "no TPU" in proc.stderr


def test_script_fails_outside_a_checkout(tmp_path):
    script = tmp_path / SCRIPT.name
    shutil.copy(SCRIPT, script)
    proc = _run(script, tmp_path)
    assert proc.returncode != 0
    assert not _has_result_line(proc.stdout)
