"""One cell of BENCHMARK.json: set-up from the seed, the measured window,
the traced run's per-layer metrics, and the comparison with the reference.

A cell names a configuration (``bench/configs/<name>.json`` with its module
``<name>.py``) and a traffic mix (``bench/traffic/<name>.json``), whose
``strategy`` has its reference round in ``bench/strategies/<strategy>.py``.
How many rounds the reference replays, the first episode's schedule and
the limits of the compared numbers are in ``bench/limits/<cell>.json``; each per-layer metric is read
by ``bench/metrics/<metric>.py``.  All are found by name: a cell, a mix or a
metric is added by adding files.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

_COMPILE_EVENT_PREFIX = "/jax/core/compile/"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No accelerator the benchmark can measure on."""


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    model: Any                  # the configuration's module
    traffic: dict
    strategy_ref: Any           # the strategy's reference round
    limits: Dict[str, float]
    reference_rounds: int       # rounds the reference replays
    schedule_hours: List[float]  # end of each round of the first episode
    per_layer: List[str]        # per-layer metrics this cell reports


def load_cell(workload: str) -> Cell:
    spec = load_json(ROOT / "BENCHMARK.json")
    (w,) = [w for w in spec["workloads"] if w["name"] == workload]
    (c,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    checks = load_json(BENCH / "limits" / f"{workload}.json")
    return Cell(
        name=workload,
        chips=w["chips"],
        config=load_json(ROOT / c["file"]),
        model=load_module((ROOT / c["file"]).with_suffix(".py")),
        traffic=traffic,
        strategy_ref=load_module(
            BENCH / "strategies" / f"{traffic['strategy']}.py"),
        limits=checks["limits"],
        reference_rounds=checks["reference_rounds"],
        schedule_hours=checks["schedule_hours"],
        per_layer=[m["name"] for m in spec["per_layer"]],
    )


def find_chip(chips: int) -> dict:
    """The device the run measures on: a TPU with at least ``chips`` chips
    whose ``device_kind`` the peaks table holds.  Anything else raises."""
    import jax

    devs = jax.devices()
    peaks = load_json(BENCH / "peaks.json")
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's backend is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if devs[0].device_kind not in peaks:
        raise NoChip(f"device_kind {devs[0].device_kind!r} is not in "
                     "bench/peaks.json")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "peak": peaks[devs[0].device_kind]}


class CompileMonitor:
    """Counts backend compiles from JAX's own monitoring events while it is
    entered (as ``repro.launch.smoke`` does)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.backend_compiles = 0

    def _on_duration(self, event: str, secs: float, **_: Any) -> None:
        if event.startswith(_COMPILE_EVENT_PREFIX):
            self.seconds += secs
        if event == _BACKEND_COMPILE_EVENT:
            self.backend_compiles += 1

    def __enter__(self) -> "CompileMonitor":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)


@dataclasses.dataclass
class Counters:
    """What the benchmark's own spans count, reset at the window's start;
    ``measure`` hands the readers a copy taken at its end."""

    train_steps: int = 0            # sequential SGD steps of local training
    train_samples: int = 0          # samples those steps trained, all clients

    def reset(self) -> None:
        self.train_steps = self.train_samples = 0


def traced_task_class():
    """``FederatedTask`` with the benchmark's spans around local training
    and evaluation, counting the steps and samples it trains."""
    import jax
    from repro.core.fltask import FederatedTask

    class TracedTask(FederatedTask):
        counters: Counters

        def local_train(self, params, client_ids, rng):
            ids = list(client_ids)
            with jax.profiler.TraceAnnotation("bench.local_train"):
                out = super().local_train(params, ids, rng)
            m = self._x_stack.shape[1]
            b = min(self.hp.batch_size, m)
            steps = self.sim_epochs * max(1, m // b)
            self.counters.train_steps += steps
            self.counters.train_samples += len(ids) * steps * b
            return out

        def evaluate(self, params, max_samples: int = 1024):
            with jax.profiler.TraceAnnotation("bench.evaluate"):
                return super().evaluate(params, max_samples)

    return TracedTask


def split_seed(seed: int) -> dict:
    """Independent 32-bit seeds for data, weights and the strategy."""
    words = np.random.SeedSequence(seed).generate_state(3)
    return {"data": int(words[0]), "params": int(words[1]),
            "sim": int(words[2] >> 1)}


def strategy_class(name: str):
    from repro.core import baselines, fedleo

    for mod in (fedleo, baselines):
        if hasattr(mod, name):
            return getattr(mod, name)
    raise KeyError(f"no strategy {name!r} in repro.core")


@dataclasses.dataclass
class Built:
    task: Any
    sim: Any
    env: Any                     # the base session: predictor shared by episodes
    clients: list                # [(x, y)] per client, host arrays
    test: tuple                  # (x, y) host arrays
    seeds: dict


def build(cell: Cell, seed: int, counters: Counters, task_cls=None) -> Built:
    """Data, weights, task and scheduling session, all from ``seed``."""
    import jax
    from repro.comms.environment import CommsEnvironment
    from repro.configs.constellations import make_sim_config
    from repro.core import TrainHyperparams
    from repro.data.partition import ClientData
    from repro.data.synthetic import Dataset
    from repro.optim import get_optimizer

    cfg, tr = cell.config, cell.traffic
    seeds = split_seed(seed)
    sim = make_sim_config(tr["constellation"], tr["ground_stations"],
                          topology=tr["topology"],
                          rb_contention=tr["rb_contention"],
                          seed=seeds["sim"], **tr["sim"])
    L = sim.constellation.num_planes
    K = sim.constellation.sats_per_plane
    clients, test = cell.model.make_data(
        cfg, jax.random.PRNGKey(seeds["data"]), L, K)
    params = jax.jit(lambda k: cell.model.init_params(cfg, k))(
        jax.random.PRNGKey(seeds["params"]))
    apply_fn, loss_fn = cell.model.program_model(cfg)
    task_cls = task_cls or traced_task_class()
    nc = cfg["num_classes"]
    task = task_cls(
        init_fn=lambda _: params,
        apply_fn=apply_fn,
        loss_fn=loss_fn,
        clients=[ClientData(plane=i // K, slot=i % K, data=Dataset(x, y, nc))
                 for i, (x, y) in enumerate(clients)],
        test_set=Dataset(test[0], test[1], nc),
        optimizer=get_optimizer(cfg["optimizer"], cfg["learning_rate"]),
        hp=TrainHyperparams(local_epochs=cfg["local_epochs"],
                            learning_rate=cfg["learning_rate"],
                            batch_size=cfg["batch_size"]),
        sim_epochs=cfg["executed_epochs"],
    )
    task.counters = counters
    return Built(task=task, sim=sim, env=CommsEnvironment.from_sim(sim),
                 clients=clients, test=test, seeds=seeds)


@dataclasses.dataclass
class Record:
    """One of the first rounds of the first episode, for the reference."""

    params: Any
    loss: float
    events: dict


class Episodes:
    """Rounds in episodes: ``sim_rounds`` rounds from t = 0, then a fresh
    strategy session at t = 0 on the same task, sharing the predictor, so
    that no run reaches the end of the simulated horizon."""

    def __init__(self, built: Built, traffic: dict, record: int):
        self.b = built
        self.cls = strategy_class(traffic["strategy"])
        self.kwargs = traffic["strategy_args"]
        self.sim_rounds = traffic["sim_rounds"]
        self.record = record
        self.strategy = None
        self.t = 0.0
        self.episodes = 0
        self.failed = 0
        self.first: List[Record] = []
        self.first_hours: List[float] = []   # round ends of the first episode
        self.restart_s: List[float] = []     # host seconds of each restart

    @property
    def first_done(self) -> bool:
        return self.episodes > 1 or len(self.first_hours) == self.sim_rounds

    def _start(self) -> None:
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.episode_start"):
            if self.strategy is not None:
                self.strategy.finish(self.t)
            self.strategy = self.cls(self.b.task, self.b.sim,
                                     env=self.b.env.derive(), **self.kwargs)
        if self.episodes:
            self.restart_s.append(time.perf_counter() - t0)
        self.t = 0.0
        self.episodes += 1

    def round(self) -> None:
        import jax

        s = self.strategy
        if s is None or s.round_index >= self.sim_rounds:
            self._start()
            s = self.strategy
        with jax.profiler.TraceAnnotation("bench.round"):
            t_next = s.run_round(self.t)
            jax.block_until_ready(s.global_params)
        if t_next is None:           # no feasible schedule left in the horizon
            self.failed += 1
            self.strategy = None
            return
        self.t = t_next
        if self.episodes == 1:
            self.first_hours.append(t_next / 3600.0)
            if len(self.first) < self.record:
                h = s.history[-1]
                self.first.append(Record(s.global_params, h.metrics["loss"],
                                         h.events))


def finish_first_episode(episodes: Episodes) -> None:
    """Go on until the first episode has ended, so that its schedule and
    the rounds the reference replays are recorded."""
    while not episodes.first_done:
        episodes.round()


@dataclasses.dataclass
class Window:
    rounds: int
    wall_s: float
    compiles: int
    counters: Counters = dataclasses.field(default_factory=Counters)
    round_walls: List[float] = dataclasses.field(default_factory=list)


def measure(episodes: Episodes, seconds: float, monitor: CompileMonitor,
            counters: Counters) -> Window:
    """A closed loop of whole rounds, each ended by ``block_until_ready``,
    until ``seconds`` have passed; the window's counts are copied at its end,
    so that rounds trained after it are not counted."""
    import jax

    counters.reset()
    c0 = monitor.backend_compiles
    ends = []
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            episodes.round()
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= seconds:
                break
        wall = time.perf_counter() - t0
    return Window(rounds=len(ends), wall_s=wall,
                  compiles=monitor.backend_compiles - c0,
                  counters=dataclasses.replace(counters),
                  round_walls=list(np.diff([0.0] + ends)))


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader gets."""

    trace: dict
    window: Window
    counters: Counters
    peak: dict
    flops_per_sample: int


def read_metrics(names: List[str], view: RunView) -> Dict[str, dict]:
    out = {}
    for name in names:
        mod = load_module(BENCH / "metrics" / f"{name}.py")
        value = mod.read(view)
        if value is not None:
            out[name] = {"value": value, "unit": mod.UNIT}
    return out


def matmul_precision(cfg: dict) -> str:
    """The precision the reference's matrix products and convolutions run
    at: the one the configuration states, or the highest."""
    return cfg.get("matmul_precision", "highest")


def reference_replay(cell: Cell, clients: list, test: tuple, seeds: dict,
                     records: List[Record], dtype=None):
    """Replay the recorded rounds with the plain reference from the seed's
    weights, in ``dtype`` (float32 by default) at the configuration's matmul
    precision.  Returns the start weights and, per round, the weights and
    eval loss."""
    import jax
    import jax.numpy as jnp

    from bench import reference as ref

    cfg = cell.config
    dtype = dtype or jnp.float32
    with jax.default_matmul_precision(matmul_precision(cfg)):
        params = jax.jit(lambda k: cell.model.init_params(cfg, k))(
            jax.random.PRNGKey(seeds["params"]))
        treedef = jax.tree_util.tree_structure(params)
        w0 = ref.to_host(params)
        rt = ref.make_runtime(
            clients, cfg["num_classes"],
            ref.make_client_trainer(cell.model.reference_apply, cfg, dtype),
            seeds["sim"], cell.traffic["sim"]["noniid_alpha"])
        K = _sats_per_plane(cell)
        ex, ey = test[0][:cfg["eval_samples"]], test[1][:cfg["eval_samples"]]
        out_params, out_losses = [], []
        for rec in records:
            params = cell.strategy_ref.reference_round(
                rt, params, treedef, rec.events, K)
            out_params.append(ref.to_host(params))
            out_losses.append(ref.eval_loss(cell.model.reference_apply,
                                            params, ex, ey, dtype))
    return w0, out_params, out_losses


def reference_losses(cell: Cell, test: tuple, params: list) -> List[float]:
    """The reference's eval loss of each of the given weights."""
    import jax

    from bench import reference as ref

    n = cell.config["eval_samples"]
    with jax.default_matmul_precision(matmul_precision(cell.config)):
        return [ref.eval_loss(cell.model.reference_apply, p, test[0][:n],
                              test[1][:n]) for p in params]


def compare(cell: Cell, test: tuple, records: List[Record],
            replay) -> Dict[str, float]:
    """The compared numbers of the program's recorded rounds against the
    reference's replay of them, and of the losses the program reported
    against the reference's losses of the same weights."""
    from bench import reference as ref

    w0, ref_params, ref_losses = replay
    own = reference_losses(cell, test, [r.params for r in records])
    return ref.readings(w0, [ref.to_host(r.params) for r in records],
                        [r.loss for r in records], ref_params, ref_losses, own)


def schedule_gap(hours: List[float], expected: List[float]) -> float:
    """Worst relative gap between the first episode's round ends and the
    cell's recorded schedule; infinite where a round is missing.  The
    schedule depends on the sizes alone, which no seed changes."""
    if len(hours) != len(expected):
        return float("inf")
    return max(abs(h - e) / e for h, e in zip(hours, expected))


def _sats_per_plane(cell: Cell) -> int:
    from repro.configs.constellations import get_constellation

    return get_constellation(cell.traffic["constellation"]).sats_per_plane


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, chip: Optional[dict] = None,
        log: Callable[[str], None] = print) -> dict:
    """One run of the cell; returns the result line's object."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    chip = chip or find_chip(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    counters = Counters()
    with CompileMonitor() as monitor:
        built = build(cell, seed, counters)
        tr = cell.traffic
        episodes = Episodes(built, tr, record=cell.reference_rounds)
        for _ in range(tr["warmup_rounds"]):
            episodes.round()
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s} s ({monitor.seconds} s compiling, "
            f"{monitor.backend_compiles} backend compiles)")
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(trace_dir)
        window = measure(episodes, seconds, monitor, counters)
        if trace:
            jax.profiler.stop_trace()
        finish_first_episode(episodes)
    log(f"window: {window.rounds} rounds in {window.wall_s} s, "
        f"{window.compiles} backend compiles, {episodes.episodes} episodes, "
        f"{episodes.failed} rounds without a feasible schedule, "
        f"restarts took {episodes.restart_s} s")
    log(f"round walls {[float(w) for w in window.round_walls]} s")
    log(f"first episode's rounds end at {episodes.first_hours} simulated h")
    stats = jax.devices()[0].memory_stats() or {}
    device = {k: chip[k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    if trace:
        from bench import tracereduce

        t_read = time.perf_counter()
        tdict = tracereduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        view = RunView(trace=tdict, window=window, counters=window.counters,
                       peak=chip["peak"],
                       flops_per_sample=cell.model.forward_flops_per_sample(
                           cell.config))
        metrics = read_metrics(cell.per_layer, view)
        device["busy_s"] = tracereduce.busy_ns(tdict) / 1e9
        device["window_s"] = tracereduce.window_ns(tdict) / 1e9
        breakdown = {"device_ops": tracereduce.top_ops(tdict),
                     "idle_gaps": tracereduce.idle_gaps(tdict)}
        log(f"trace read in {time.perf_counter() - t_read} s: "
            f"{len(tdict['device'])} device events")
    else:
        metrics = {"round_s": {"value": window.wall_s / window.rounds,
                               "unit": "s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        breakdown = None

    # the program's state goes before the reference runs
    records = [Record(jax.device_get(r.params), r.loss, r.events)
               for r in episodes.first]
    clients, test, seeds = built.clients, built.test, built.seeds
    failed, hours = episodes.failed, episodes.first_hours
    del episodes, built
    gc.collect()
    t_ref = time.perf_counter()
    if len(records) < cell.reference_rounds:   # the first episode failed early
        numbers = {k: float("inf") for k in cell.limits}
    else:
        numbers = compare(cell, test, records, reference_replay(
            cell, clients, test, seeds, records))
    numbers["schedule_gap"] = schedule_gap(hours, cell.schedule_hours)
    log(f"reference: {len(records)} rounds in {time.perf_counter() - t_ref} s")
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in cell.limits.items()}
    correct = bool(checks) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    result = {"correct": correct, "attempted": window.rounds,
              "failed": failed, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    log("readings: " + json.dumps(numbers))
    result["checks"] = checks
    return result
