"""One FedLEO run through the normal entry points, checked end to end.

``run_fedleo_smoke`` builds the paper's §V-A setup: the paper-5x8
Walker-delta constellation, the Rolla ground station, the deep CNN,
MNIST-like data split non-IID over the 40 satellites, and Table I
hyperparameters.  It runs ``rounds`` synchronous FedLEO rounds, with
real local training and the Pallas sink/GS aggregation, and then checks:

  * the aggregation is the Mosaic kernel wherever the backend compiles
    kernels (``tpu_custom_call`` in its compiled text);
  * the kernel aggregation of real trained client params matches the
    float32 reference ``weighted_average(..., use_kernel=False)``;
  * the global params stay finite, and test accuracy after the last
    round beats both chance and the initial model.

A failed check raises ``SmokeCheckError``.  The sizes are arguments:
``chip_smoke.py`` passes the full ones, the CPU test tiny ones.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.configs.constellations import (
    CONSTELLATION_PRESETS,
    GROUND_STATION_PRESETS,
)
from repro.core import FedLEO, FederatedTask, SimConfig, TrainHyperparams
from repro.core.aggregation import weighted_average
from repro.data import make_classification_dataset, partition_noniid_by_orbit
from repro.kernels import interpret_mode
from repro.models.cnn import apply_cnn, init_cnn
from repro.optim import get_optimizer

AGGREGATION_TOL = 1e-6       # kernel vs float32 reference, max abs error
CHANCE_ACCURACY = 0.1        # 10 classes

_COMPILE_EVENT_PREFIX = "/jax/core/compile/"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeCheckError(RuntimeError):
    """A check of the smoke run failed."""


@dataclasses.dataclass(frozen=True)
class SmokeResult:
    compile_s: float                  # trace + lower + compile, all rounds
    round_wall_s: Tuple[float, ...]
    round_compiles: Tuple[int, ...]   # backend compiles inside each round
    round_accuracy: Tuple[float, ...]
    initial_accuracy: float           # whole test set
    final_accuracy: float             # whole test set
    aggregation_max_err: float
    mosaic_kernel: bool


class _CompileMonitor:
    """Sums JAX's own compile-phase durations while it is entered."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.backend_compiles = 0

    def _on_duration(self, event: str, secs: float, **_: Any) -> None:
        if event.startswith(_COMPILE_EVENT_PREFIX):
            self.seconds += secs
        if event == _BACKEND_COMPILE_EVENT:
            self.backend_compiles += 1

    def __enter__(self) -> "_CompileMonitor":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckError(what)


def run_fedleo_smoke(
    *,
    train_samples: int,
    test_samples: int,
    widths: Tuple[int, ...],
    hidden: int,
    rounds: int,
    sim_epochs: int,
    learning_rate: float = TrainHyperparams.learning_rate,
    seed: int = 0,
    log: Callable[[str], None] = print,
) -> SmokeResult:
    """Run and check ``rounds`` FedLEO rounds; all data comes from ``seed``.

    ``sim_epochs`` local epochs are executed per round; the simulated
    clock always charges Table I's I = 100 (``FederatedTask``).
    """
    hp = TrainHyperparams(learning_rate=learning_rate, batch_size=32)
    constellation = CONSTELLATION_PRESETS["paper-5x8"]
    train = make_classification_dataset(
        "mnist-like", num_samples=train_samples, seed=seed
    )
    test = make_classification_dataset(
        "mnist-like", num_samples=test_samples, seed=seed + 1
    )
    clients = partition_noniid_by_orbit(
        train, constellation.num_planes, constellation.sats_per_plane,
        seed=seed,
    )
    task = FederatedTask(
        init_fn=functools.partial(init_cnn, widths=widths, hidden=hidden),
        apply_fn=apply_cnn,
        clients=clients,
        test_set=test,
        optimizer=get_optimizer("sgd", hp.learning_rate),
        hp=hp,
        rng=jax.random.PRNGKey(seed),
        sim_epochs=sim_epochs,
    )
    sim = SimConfig(
        constellation=constellation,
        ground_station=GROUND_STATION_PRESETS["rolla"],
        horizon_hours=72.0,
        use_kernel=True,
        seed=seed,
    )
    num_params = sum(
        int(p.size) for p in jax.tree_util.tree_leaves(task.global_params)
    )
    log(f"model: CNN widths={tuple(widths)} hidden={hidden}, "
        f"{num_params} params; {len(clients)} clients, {train_samples} "
        f"train / {test_samples} test samples")
    log(f"training: sim_epochs={sim_epochs} executed per round "
        f"(clock charges I={hp.local_epochs}), batch {hp.batch_size}, "
        f"lr {hp.learning_rate}")
    initial_accuracy = task.evaluate(
        task.global_params, max_samples=test_samples
    )["accuracy"]
    log(f"initial accuracy (test set): {initial_accuracy}")

    # FLStrategy.run is this loop plus finish(); it is unrolled here so
    # that each round is timed to block_until_ready
    strategy = FedLEO(task, sim)
    walls, compiles, accs = [], [], []
    t = 0.0
    with _CompileMonitor() as monitor:
        for r in range(1, rounds + 1):
            n_before = monitor.backend_compiles
            t0 = time.perf_counter()
            t_next = strategy.run_round(t)
            jax.block_until_ready(strategy.global_params)
            walls.append(time.perf_counter() - t0)
            _check(t_next is not None,
                   f"round {r} found no feasible schedule in the horizon")
            t = t_next
            compiles.append(monitor.backend_compiles - n_before)
            accs.append(strategy.history[-1].metrics["accuracy"])
            log(f"round {r}: wall {walls[-1]} s, {compiles[-1]} backend "
                f"compiles, sim time {t / 3600.0} h, accuracy (first "
                f"1024 test samples) {accs[-1]}")
    strategy.finish(t)
    log(f"compile seconds (trace + lower + compile, all rounds): "
        f"{monitor.seconds}")

    # the sink aggregation (eq. 9) of one plane's freshly trained clients
    plane = task.clients_on_plane(0)
    stacked = task.local_train(
        strategy.global_params, plane, jax.random.PRNGKey(seed + 2)
    )
    counts = jnp.asarray([task.num_samples(c) for c in plane], jnp.float32)
    compiled = jax.jit(
        functools.partial(weighted_average, use_kernel=True)
    ).lower(stacked, counts).compile()
    mosaic_kernel = "tpu_custom_call" in compiled.as_text()
    log(f"aggregation over K={len(plane)} clients: Mosaic kernel "
        f"(tpu_custom_call) {mosaic_kernel}, interpret mode "
        f"{interpret_mode()}")
    _check(mosaic_kernel or interpret_mode(),
           "the aggregation compiled without its Mosaic kernel")
    kernel_out = compiled(stacked, counts)
    reference = weighted_average(stacked, counts, use_kernel=False)
    max_err = max(
        float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(kernel_out),
            jax.tree_util.tree_leaves(reference),
        )
    )
    log(f"aggregation max abs error, kernel vs float32 reference: {max_err}")
    _check(max_err <= AGGREGATION_TOL,
           f"kernel aggregation is {max_err} off the reference "
           f"(limit {AGGREGATION_TOL})")

    finite = all(
        bool(jnp.all(jnp.isfinite(p)))
        for p in jax.tree_util.tree_leaves(strategy.global_params)
    )
    _check(finite, "global params are not finite")
    final_accuracy = task.evaluate(
        strategy.global_params, max_samples=test_samples
    )["accuracy"]
    log(f"final accuracy (test set) after round {rounds}: {final_accuracy}")
    _check(final_accuracy > max(CHANCE_ACCURACY, initial_accuracy),
           f"final accuracy {final_accuracy} does not beat chance "
           f"({CHANCE_ACCURACY}) and the initial model ({initial_accuracy})")
    return SmokeResult(
        compile_s=monitor.seconds,
        round_wall_s=tuple(walls),
        round_compiles=tuple(compiles),
        round_accuracy=tuple(accs),
        initial_accuracy=initial_accuracy,
        final_accuracy=final_accuracy,
        aggregation_max_err=max_err,
        mosaic_kernel=mosaic_kernel,
    )
