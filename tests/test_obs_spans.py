"""repro.obs.span: the program's profiler spans in one FedLEO round.

One round runs under ``jax.profiler.trace`` on the CPU; the test reads
the spans back from the ``.xplane.pb`` and checks their nesting, their
stats, and that the profiler changes no result.
"""
import pathlib

import jax
import numpy as np
import pytest

from repro.core import FedLEO, FederatedTask, SimConfig, TrainHyperparams
from repro.data import make_classification_dataset, partition_iid
from repro.models.cnn import apply_cnn, init_cnn
from repro.obs import span
from repro.optim import get_optimizer
from repro.orbits.constellation import ConstellationConfig

_CFG = ConstellationConfig(num_planes=2, sats_per_plane=4)
_SIM = SimConfig(constellation=_CFG, horizon_hours=48.0, use_kernel=True)
_EPOCHS = 2


def _task(samples_per_client=20, clients=None):
    test = make_classification_dataset("mnist-like", num_samples=64, seed=7)
    if clients is None:
        n = _CFG.num_planes * _CFG.sats_per_plane * samples_per_client
        ds = make_classification_dataset("mnist-like", num_samples=n, seed=0)
        clients = partition_iid(ds, _CFG.num_planes, _CFG.sats_per_plane)
    return FederatedTask(
        init_fn=lambda r: init_cnn(r, (28, 28, 1), 10, widths=(4,),
                                   hidden=16),
        apply_fn=apply_cnn, clients=clients, test_set=test,
        optimizer=get_optimizer("sgd", 0.05),
        hp=TrainHyperparams(local_epochs=100, learning_rate=0.05,
                            batch_size=16),
        sim_epochs=_EPOCHS,
    )


def _read_spans(trace_dir):
    """[(name, start_ns, end_ns, stats, parent name or None)] of the
    ``repro.*`` host events; a span's parent is the innermost span on the
    same thread line that holds it."""
    from jax.profiler import ProfileData

    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(
                ((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                  dict(ev.stats))
                 for ev in line.events if ev.name.startswith("repro.")),
                key=lambda e: (e[1], -e[2]))
            for i, (name, s, e, stats) in enumerate(evs):
                holders = [h for h in evs[:i] if h[1] <= s and e <= h[2]]
                parent = holders[-1][0] if holders else None
                out.append((name, s, e, stats, parent))
    return sorted(out, key=lambda r: r[1])


def _one_round(trace_dir=None):
    strat = FedLEO(_task(), _SIM)
    if trace_dir is None:
        t = strat.run_round(0.0)
    else:
        with jax.profiler.trace(str(trace_dir)):
            t = strat.run_round(0.0)
            jax.block_until_ready(strat.global_params)
    assert t is not None
    return strat


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = tmp_path_factory.mktemp("profile")
    strat = _one_round(d)
    return strat, _read_spans(d)


def test_span_names_its_event():
    with span("aggregate", bytes=3) as s:
        pass
    assert isinstance(s, jax.profiler.TraceAnnotation)


def test_round_spans_nest_as_the_round_runs(traced):
    _, spans = traced
    groups = _CFG.num_planes
    got = [(name, parent) for name, _, _, _, parent in spans]
    per_group = [("repro.group", "repro.round"), ("repro.plan", "repro.group"),
                 ("repro.commit", "repro.group"),
                 ("repro.local_train", "repro.group"),
                 ("repro.aggregate", "repro.group")]
    assert got == ([("repro.round", None)] + per_group * groups
                   + [("repro.aggregate", "repro.round"),
                      ("repro.evaluate", "repro.round"),
                      ("repro.wait", "repro.evaluate")])
    # only the stats that bench/programspans.py reads
    stats = {name: set(st) for name, _, _, st, _ in spans if st}
    assert stats == {"repro.local_train": {"steps", "samples"},
                     "repro.aggregate": {"bytes"}}


def test_local_train_counts_steps_and_samples(traced):
    strat, spans = traced
    task = strat.task
    want = []
    for plane in range(_CFG.num_planes):
        ids = task.clients_on_plane(plane)
        batches = {task.executed_batches(c) for c in ids}
        (n_batches, bsz), = batches          # equal clients: one shape
        steps = _EPOCHS * n_batches
        want.append({"steps": steps, "samples": steps * bsz * len(ids)})
    got = [st for name, _, _, st, _ in spans if name == "repro.local_train"]
    assert got == want
    assert want[0] == {"steps": 2, "samples": 2 * 16 * 4}


def test_aggregate_spans_carry_the_bytes_they_read(traced):
    strat, spans = traced
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(strat.global_params))
    got = [st["bytes"] for name, _, _, st, _ in spans
           if name == "repro.aggregate"]
    k = _CFG.sats_per_plane
    assert got == [k * param_bytes] * _CFG.num_planes + [
        _CFG.num_planes * param_bytes]


def test_traced_round_is_bit_identical(traced):
    strat, _ = traced
    plain = _one_round()
    a = jax.tree_util.tree_leaves(strat.global_params)
    b = jax.tree_util.tree_leaves(plain.global_params)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert strat.history[0].metrics == plain.history[0].metrics
    assert strat.history[0].events == plain.history[0].events


def test_ragged_clients_count_the_padded_stack(tmp_path):
    """The vmapped call trains every client on the stack's padded length,
    so a client smaller than a batch counts the largest client's steps."""
    from repro.data.partition import ClientData
    from repro.data.synthetic import Dataset

    ds = make_classification_dataset("mnist-like", num_samples=40, seed=0)
    sizes = [8, 34]                   # 8 < batch 16; 34 // 16 = 2 batches
    clients, off = [], 0
    for i, m in enumerate(sizes):
        data = Dataset(ds.x[off:off + m], ds.y[off:off + m], ds.num_classes)
        clients.append(ClientData(plane=0, slot=i, data=data))
        off += m
    task = _task(clients=clients)
    assert task.executed_batches(0) == (1, 8)
    assert task.executed_batches(1) == (2, 16)
    with jax.profiler.trace(str(tmp_path)):
        out = task.local_train(task.global_params, [0],
                               jax.random.PRNGKey(0))
        jax.block_until_ready(out)
    (st,) = [st for name, _, _, st, _ in _read_spans(tmp_path)
             if name == "repro.local_train"]
    assert st == {"steps": _EPOCHS * 2, "samples": _EPOCHS * 2 * 16}
