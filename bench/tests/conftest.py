import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import pytest  # noqa: E402

TINY = {
    "cnn": dict(train_samples=40 * 40, test_samples=128, eval_samples=128,
                executed_epochs=1),
    "unet": dict(input_shape=[16, 16, 3], base=40, depth=2,
                 train_samples=40 * 32, test_samples=32, eval_samples=32),
}

# the first episode's round ends at the tiny size, in simulated hours: the
# clock charges each client's training by its sample count, so smaller
# clients end rounds a little earlier than the cell's recorded schedule
TINY_SCHEDULE_HOURS = {
    "cnn-mnist.paper-5x8.fedleo": [5.647564627064001, 14.128819960259674,
                                   19.984354350084956, 28.46715576552315],
    "unet-deepglobe.paper-5x8.fedleo": [5.64837810210749, 14.129630660781729,
                                        19.985170643157115, 28.4679696674247],
}

# what bench.cell.find_chip would return, for a run on this machine's CPU
CPU_CHIP = {"platform": "cpu", "kind": "cpu", "count": 1,
            "peak": {"bf16_flops_per_s": 1e12}}


def tiny_cell(workload: str):
    """The cell as BENCHMARK.json defines it, at a size the CPU holds: fewer
    samples and one executed epoch (the U-Net also at small widths and
    tiles), and the schedule those sizes give; mix and limits unchanged."""
    from bench import cell as cells

    cell = cells.load_cell(workload)
    cell.config.update(TINY[cell.config["model"]])
    cell.schedule_hours = TINY_SCHEDULE_HOURS[workload]
    return cell


@pytest.fixture(autouse=True)
def _compile_cache_off():
    """Runs here compile for the CPU: keep them out of the checkout's
    persistent compilation cache."""
    import jax

    from repro.launch import compile_cache

    saved = compile_cache.enable_compile_cache
    compile_cache.enable_compile_cache = lambda: ""
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    compile_cache.enable_compile_cache = saved
