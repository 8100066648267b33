"""Two tiny rounds through a cell's whole run on the CPU, the chip check
skipped: sound, and with each fault the cell can have planted under the
timed path."""
import json
import os
import subprocess
import sys

import pytest

from bench import cell as cells
from bench import faults
from bench.tests.conftest import CPU_CHIP, ROOT, tiny_cell

CELLS = ["cnn-mnist.paper-5x8.fedleo", "unet-deepglobe.paper-5x8.fedleo"]


def _run(cell, seed=4_294_967_311, trace=False):
    # the window closes after its first round; the reference round makes two
    return cells.run(cell, seed, 0.0, trace, t_start=0.0, chip=CPU_CHIP,
                     log=lambda _: None)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_well_formed(workload):
    result = _run(tiny_cell(workload))
    assert list(result)[-1] == "checks"
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    assert set(result["checks"]) == set(tiny_cell(workload).limits)
    json.dumps(result)


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_per_layer_metrics(workload):
    result = _run(tiny_cell(workload), trace=True)
    # the CPU trace has no TPU plane: the device metrics stay silent
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert "plan_host_ms" in result["metrics"]
    assert "train_step_ms" not in result["metrics"]
    assert "step_mfu" not in result["metrics"]
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(fault, workload, monkeypatch):
    base = cells.traced_task_class()
    monkeypatch.setattr(cells, "traced_task_class",
                        lambda: faults.task_class(fault, base))
    result = _run(tiny_cell(workload))
    assert result["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_window_counts_stop_at_its_end(workload):
    # a window of one round, shorter than the first episode, which the run
    # then finishes: the readers get the window's steps, the same per round
    # as the three rounds counted from the window's start to the episode's end
    cell = tiny_cell(workload)
    counters = cells.Counters()
    built = cells.build(cell, 4_294_967_311, counters)
    episodes = cells.Episodes(built, cell.traffic, record=0)
    episodes.round()
    with cells.CompileMonitor() as monitor:
        window = cells.measure(episodes, 0.0, monitor, counters)
    cells.finish_first_episode(episodes)
    assert window.rounds == 1
    assert len(episodes.first_hours) == cell.traffic["sim_rounds"] == 4
    assert counters.train_steps == 3 * window.counters.train_steps > 0
    assert counters.train_samples == 3 * window.counters.train_samples


@pytest.mark.parametrize("workload", CELLS)
def test_worse_schedule_is_not_correct(workload):
    cell = faults.worse_schedule(tiny_cell(workload))
    result = _run(cell)
    assert result["correct"] is False
    assert result["checks"]["schedule_gap"]["value"] > 0.1
    others = [c for k, c in result["checks"].items() if k != "schedule_gap"]
    assert all(c["value"] <= c["limit"] for c in others)


@pytest.mark.parametrize("workload", CELLS)
def test_no_tpu_exits_2_without_a_result(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("workload", CELLS)
def test_without_the_program_exits_nonzero(workload, tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    subprocess.run(["cp", "-r", str(ROOT / "bench"), str(tmp_path)], check=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
