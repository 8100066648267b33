"""The reduction from a trace to the per-layer numbers, on small traces whose
answers are worked out by hand."""
from bench import tracereduce as tr

# window 0..1000 ns; ops at 100-300, 250-400 (overlapping), 600-700, and
# one that starts before the window; host spans nested inside rounds
TRACE = {
    "device": [
        ["XLA Ops", "fusion.1", 100, 200],
        ["XLA Ops", "fusion.2", 250, 150],
        ["XLA Ops", "custom-call.3", 600, 100],
        ["XLA Ops", "early", -50, 80],
        ["XLA Modules", "jit__local_train_one(1)", 100, 300],
        ["XLA Modules", "jit_aggregate_flat(2)", 600, 100],
    ],
    "host": [
        ["bench.window", 0, 1000],
        ["bench.round", 0, 500],
        ["bench.local_train", 10, 40],
        ["bench.evaluate", 300, 150],
        ["bench.round", 500, 500],
        ["bench.local_train", 510, 20],
        ["bench.aggregate", 720, 30],
        ["bench.evaluate", 800, 150],
    ],
}


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    # [0, 30) from the early op, [100, 400), [600, 700)
    assert tr.busy_intervals(TRACE) == [(0, 30), (100, 400), (600, 700)]
    assert tr.busy_ns(TRACE) == 30 + 300 + 100
    assert tr.window_ns(TRACE) == 1000


def test_events_by_name_on_a_line():
    assert tr.events_ns(TRACE, tr.MODULES_LINE, "_local_train_one") == (300, 1)
    assert tr.events_ns(TRACE, tr.OPS_LINE, "custom-call") == (100, 1)
    assert tr.events_ns(TRACE, tr.OPS_LINE, "fusion") == (350, 2)
    assert tr.events_ns(TRACE, tr.OPS_LINE, "early") == (0, 0)
    assert tr.events_ns(TRACE, tr.OPS_LINE, "nothing") == (0, 0)


def test_top_ops_sums_by_name_in_seconds():
    top = tr.top_ops(TRACE, 2)
    assert top == [["fusion.1", 200e-9], ["fusion.2", 150e-9]]


def test_idle_gaps_named_by_the_innermost_host_span():
    gaps = tr.idle_gaps(TRACE)
    # gaps: [30,100) 70 ns, [400,600) 200 ns, [700,1000) 300 ns
    assert [g[1] for g in gaps] == [300e-9, 200e-9, 70e-9]
    assert [g[0] for g in gaps] == ["bench.evaluate", "bench.round",
                                    "bench.round"]


def test_host_outside_train_and_eval():
    ns, rounds = tr.host_outside_ns(
        TRACE, "bench.round", ("bench.local_train", "bench.evaluate"))
    assert rounds == 2
    assert ns == (500 - 40 - 150) + (500 - 20 - 150)


def test_recorded_chip_trace():
    """35 ms of a traced window on a TPU v5e (a traced run of
    cnn-mnist.paper-5x8.fedleo): the end of a round, with a ground-station
    aggregation and an evaluation.  The window span is set to the slice's
    bounds."""
    import json
    import pathlib

    trace = json.loads((pathlib.Path(__file__).parent / "data"
                        / "trace-cnn-mnist.json").read_text())
    busy, window = tr.busy_ns(trace), tr.window_ns(trace)
    assert window == 27_986_712
    assert 0 < busy <= window
    gaps = tr.idle_gaps(trace, n=10**6)
    assert abs(sum(g[1] for g in gaps) * 1e9 - (window - busy)) < len(gaps)
    assert tr.events_ns(trace, tr.OPS_LINE, "aggregate_flat") == (8796, 1)
    assert tr.events_ns(trace, tr.MODULES_LINE, "aggregate_flat") == (30683, 1)
    top = tr.top_ops(trace)
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)
