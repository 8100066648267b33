"""The program's spans in a trace and the numbers read from them, on a small
trace whose answers are worked out by hand."""
import json
import pathlib
import types

import pytest

from bench import programspans as ps
from bench import tracereduce as tr
from bench.metrics import aggregate_ms

# window 0..1000 ns, two rounds.  Device busy [0, 30), [100, 400),
# [600, 700); idle [30, 100), [400, 600), [700, 1000).
TRACE = {
    "device": [
        ["XLA Ops", "fusion.1", 100, 200],
        ["XLA Ops", "fusion.2", 250, 150],
        ["XLA Ops", "custom-call.3", 600, 100],
        ["XLA Ops", "early", -50, 80],
        ["XLA Modules", "jit__local_train_one(1)", 100, 300],
        ["XLA Modules", "jit_aggregate_pytree(2)", 600, 100],
        ["XLA Modules", "jit__local_train_one(1)", 1100, 100],
    ],
    "host": [
        ["bench.window", 0, 1000],
        ["bench.round", 0, 500],
        ["bench.round", 500, 500],
        ["bench.round", 1000, 300],
    ],
    "program": [
        ["repro.round", 0, 500, {}],
        ["repro.plan", 20, 40, {}],          # idle on [30, 60)
        ["repro.commit", 60, 10, {}],        # idle throughout
        ["repro.local_train", 70, 20, {"steps": 10, "samples": 2560}],
        ["repro.wait", 300, 70, {}],
        ["repro.plan", 380, 80, {}],         # half idle: [400, 460)
        ["repro.commit", 460, 10, {}],       # idle throughout
        ["repro.round", 500, 500, {}],
        ["repro.local_train", 510, 20, {"steps": 10, "samples": 2560}],
        ["repro.aggregate", 590, 20, {"bytes": 500}],
        ["repro.wait", 800, 150, {}],
        # after the window: a round run on to end the episode
        ["repro.round", 1000, 300, {}],
        ["repro.plan", 1010, 50, {}],
        ["repro.local_train", 1070, 20, {"steps": 10, "samples": 2560}],
        ["repro.aggregate", 1095, 5, {"bytes": 999}],
        ["repro.wait", 1200, 50, {}],
    ],
}


def test_rounds_are_the_round_spans_that_start_in_the_window():
    assert ps.rounds(TRACE) == 2


def test_schedule_host_ms():
    # (40 + 10 + 80 + 10) ns over 2 rounds
    assert ps.schedule_host_ms(TRACE) == 70 / 1e6


def test_idle_schedule_ms_counts_only_the_idle_part_of_a_span():
    # [30, 60) + [60, 70) + [400, 460) + [460, 470), over 2 rounds
    assert ps.idle_schedule_ms(TRACE) == (30 + 10 + 60 + 10) / 2 / 1e6


def test_eval_sync_ms():
    assert ps.eval_sync_ms(TRACE) == (70 + 150) / 2 / 1e6


def test_local_step_ms_ignores_spans_after_the_window():
    # the window's 300 device ns over its 20 steps; the third round's span
    # and module event both start after the window ends
    assert ps.local_step_ms(TRACE) == 300 / 20 / 1e6


def test_local_step_mfu_counts_the_window_samples():
    # 3 x 1,000 FLOPs x 5,120 samples in 300 device ns, over a 1e15 peak
    assert ps.local_step_mfu(TRACE, 1000, 1e15) == pytest.approx(5.12)


def test_aggregate_gbps_reads_bytes_over_the_module_time():
    # 500 bytes in the window's 100 ns of aggregate_pytree; the span
    # after the window adds nothing
    assert ps.aggregate_gbps(TRACE) == 5.0


def test_aggregate_ms_reads_the_jitted_module():
    view = types.SimpleNamespace(trace=TRACE,
                                 window=types.SimpleNamespace(rounds=2))
    assert aggregate_ms.read(view) == 100 / 2 / 1e6


def test_self_ms_leaves_out_nested_spans():
    # round 1: 500 less plan 40, commit 10, train 20, wait 70, plan 80,
    # commit 10; round 2: 500 less train 20, aggregate 20, wait 150
    assert ps.self_ms(TRACE) == {
        "repro.round": (270 + 310) / 2 / 1e6,
        "repro.plan": 120 / 2 / 1e6, "repro.commit": 20 / 2 / 1e6,
        "repro.local_train": 40 / 2 / 1e6, "repro.aggregate": 20 / 2 / 1e6,
        "repro.wait": 220 / 2 / 1e6}


def test_idle_gaps_named_by_program_spans():
    gaps = ps.idle_gaps(TRACE)
    # [700, 1000) mid 850: repro.wait; [400, 600) mid 500: the second
    # round starts at 500, with its bench span (a tie goes to the later
    # name); [30, 100) mid 65: repro.commit
    assert gaps == [["repro.wait", 300e-9], ["repro.round", 200e-9],
                    ["repro.commit", 70e-9]]


def test_a_trace_without_program_spans_reads_nothing():
    """The recorded chip trace predates the program's spans and its
    jitted aggregation."""
    trace = json.loads((pathlib.Path(__file__).parent / "data"
                        / "trace-cnn-mnist.json").read_text())
    assert "program" not in trace
    assert tr.window_ns(trace) == 27_986_712
    nums = ps.read(trace, 1000, 1e15)
    assert nums.pop("rounds") == 0
    assert nums.pop("self_ms") == {}
    # named by the benchmark's spans, as the accepted breakdown reads
    assert nums.pop("idle_gaps") == tr.idle_gaps(trace)
    assert set(nums.values()) == {None}
    view = types.SimpleNamespace(trace=trace,
                                 window=types.SimpleNamespace(rounds=1))
    assert aggregate_ms.read(view) is None


def test_load_reads_spans_and_stats(tmp_path):
    import jax

    from repro.obs import span

    with jax.profiler.trace(str(tmp_path)):
        with span("round"):
            with span("local_train", steps=3, samples=96):
                pass
    got = ps.load(str(tmp_path))
    assert [(e[0], e[3]) for e in got] == [
        ("repro.round", {}),
        ("repro.local_train", {"steps": 3, "samples": 96})]
    (r, t) = got
    assert r[1] <= t[1] and t[1] + t[2] <= r[1] + r[2]


def test_traced_cell_run_reads_the_program_spans(tmp_path):
    """A tiny run of the cell on the CPU with the profiler on around its
    window: the window's rounds hold the program's spans.  The CPU trace
    has no TPU plane, so the device reads stay silent and every host span
    falls in idle time."""
    from bench.tests.conftest import CPU_CHIP, tiny_cell

    result, trace = ps.run(tiny_cell("cnn-mnist.paper-5x8.fedleo"),
                           4_294_967_311, 0.0, 0.0, str(tmp_path),
                           chip=CPU_CHIP, log=lambda _: None)
    assert result["correct"] is True
    nums = result["program"]
    assert nums["rounds"] == result["attempted"] == 1
    assert nums["local_step_ms"] is nums["local_step_mfu"] is None
    assert nums["aggregate_gbps"] is None
    assert nums["schedule_host_ms"] > 0 and nums["eval_sync_ms"] > 0
    assert nums["idle_schedule_ms"] == nums["schedule_host_ms"]
    names = ["repro." + n for n in ("round", "group", "plan", "commit",
                                    "local_train", "aggregate", "evaluate",
                                    "wait")]
    assert {e[0] for e in ps.spans(trace, names)} == set(names)
    steps = [e[3]["steps"] for e in ps.spans(trace, ["repro.local_train"])]
    assert steps == [1] * 5        # one executed epoch of 1 batch, 5 groups
    # the first episode's last round ran after the window, traced
    after = [e for e in trace["program"] if e[0] == "repro.round"]
    assert len(after) > nums["rounds"]
