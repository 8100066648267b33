"""The program's own spans in a profiler trace, and the numbers they give.

The program marks its round with ``repro.obs.span``: ``repro.round``,
``repro.group``, ``repro.plan``, ``repro.commit``, ``repro.local_train``
(stats ``steps``, ``samples``), ``repro.aggregate`` (stat ``bytes``),
``repro.evaluate`` and ``repro.wait``.  ``load`` reads them from a trace
directory, to be kept in ``tracereduce``'s dict under a key of their own:

  program: [[name, start_ns, dur_ns, {stat: value}], ...]

The readers below take that dict.  One without a ``program`` key (a trace
of a program without spans) has no spans, and they return None.  "Per
round" is per ``repro.round`` span that starts inside the window, and only
spans and device events that start inside the window count.

A span's host time holds whatever the host waited for inside it, and the
host waits in a dispatch whenever the device's queue is full: which span
takes that wait depends on the order of the launches, not on the code the
span names.  The device-idle time inside a span (``idle_schedule_ms``,
``idle_gaps``) does not have that fault.

``tracereduce.load`` does not keep these events yet, so no metric of
BENCHMARK.json reads them.  Until it does, this file runs a cell with the
profiler on around its window and prints them with the run's result:

    python3 bench/programspans.py --workload <cell> --seed <n> --seconds <s> \
        [--keep <dir>]

``--keep`` leaves the profiler's directory there, for TensorBoard,
Perfetto or ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import pathlib
import sys
import time
from typing import Dict, Iterable, List, Optional, Tuple

T_START = time.perf_counter()

if __package__ in (None, ""):
    ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tracereduce  # noqa: E402

PREFIX = "repro."
SCHEDULE_SPANS = ("repro.plan", "repro.commit")
TRAIN_PROGRAM = "_local_train_one"
AGGREGATE_PROGRAM = "aggregate_pytree"


def load(trace_dir: str) -> list:
    """The ``repro.*`` events of the host planes, in start order."""
    from jax.profiler import ProfileData

    (path,) = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1:]
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                         {k: int(v) for k, v in ev.stats}]
                        for ev in line.events if ev.name.startswith(PREFIX)]
    return sorted(out, key=lambda e: e[1])


def spans(trace: tracereduce.Trace, names: Iterable[str]) -> List[list]:
    """The window's program spans whose name is one of ``names``."""
    lo, hi = tracereduce.window(trace)
    names = set(names)
    return [e for e in trace.get("program", ())
            if e[0] in names and lo <= e[1] < hi]


def _stat(trace: tracereduce.Trace, name: str, stat: str) -> int:
    return sum(e[3][stat] for e in spans(trace, [name]))


def rounds(trace: tracereduce.Trace) -> int:
    return len(spans(trace, ["repro.round"]))


def _per_round_ms(trace: tracereduce.Trace, ns: float) -> Optional[float]:
    n = rounds(trace)
    return ns / n / 1e6 if n else None


def schedule_host_ms(trace: tracereduce.Trace) -> Optional[float]:
    """Host milliseconds per round in the sink scheduler and the booking."""
    return _per_round_ms(trace, sum(e[2] for e in spans(trace, SCHEDULE_SPANS)))


def eval_sync_ms(trace: tracereduce.Trace) -> Optional[float]:
    """Host milliseconds per round in ``repro.wait``: the evaluation's
    conversion of its numbers, the round's one sync with the device.  The
    host's waits in full dispatch queues fall in other spans."""
    return _per_round_ms(trace, sum(e[2] for e in spans(trace, ["repro.wait"])))


def _idle(trace: tracereduce.Trace) -> List[Tuple[int, int]]:
    """The window's stretches with no op on the device."""
    lo, hi = tracereduce.window(trace)
    edges = [lo] + [x for iv in tracereduce.busy_intervals(trace)
                    for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_schedule_ms(trace: tracereduce.Trace) -> Optional[float]:
    """Device-idle milliseconds per round while the host schedules: the
    overlap of idle gaps with the scheduler's and the booking's spans."""
    sched = tracereduce._merged([(e[1], e[1] + e[2])
                                 for e in spans(trace, SCHEDULE_SPANS)])
    ns = sum(max(0, min(ge, se) - max(gs, ss))
             for gs, ge in _idle(trace) for ss, se in sched)
    return _per_round_ms(trace, ns)


def local_step_ms(trace: tracereduce.Trace) -> Optional[float]:
    """Device milliseconds of the local-train program per sequential SGD
    step, the steps counted by the window's ``repro.local_train`` spans."""
    ns, n = tracereduce.events_ns(trace, tracereduce.MODULES_LINE,
                                  TRAIN_PROGRAM)
    steps = _stat(trace, "repro.local_train", "steps")
    return ns / steps / 1e6 if n and steps else None


def local_step_mfu(trace: tracereduce.Trace, flops_per_sample: int,
                   peak_flops_per_s: float) -> Optional[float]:
    """Local training's share of the peak, in %: forward + backward FLOPs
    (3 x forward) of the samples the window's ``repro.local_train`` spans
    count, over the local-train program's device time."""
    ns, n = tracereduce.events_ns(trace, tracereduce.MODULES_LINE,
                                  TRAIN_PROGRAM)
    samples = _stat(trace, "repro.local_train", "samples")
    if not n or not samples:
        return None
    return 100.0 * 3 * flops_per_sample * samples / (ns / 1e9) / peak_flops_per_s


def aggregate_gbps(trace: tracereduce.Trace) -> Optional[float]:
    """GB/s of the aggregation: the stacked bytes the window's
    ``repro.aggregate`` spans read, over the device time of the
    ``aggregate_pytree`` program."""
    ns, n = tracereduce.events_ns(trace, tracereduce.MODULES_LINE,
                                  AGGREGATE_PROGRAM)
    nbytes = _stat(trace, "repro.aggregate", "bytes")
    return nbytes / ns if n and nbytes else None


def idle_gaps(trace: tracereduce.Trace, n: int = 10) -> List[list]:
    """``tracereduce.idle_gaps``, each gap named by the innermost span,
    the benchmark's or the program's, open at its middle."""
    host = trace["host"] + [e[:3] for e in trace.get("program", ())]
    return tracereduce.idle_gaps({**trace, "host": host}, n)


def self_ms(trace: tracereduce.Trace) -> Dict[str, float]:
    """Host milliseconds per round in each span's own code, by name: the
    spans' time less the part their nested spans cover.  Waits in full
    dispatch queues count where they fall (see the module's docstring)."""
    n = rounds(trace)
    every = spans(trace, {e[0] for e in trace.get("program", ())})
    out: Dict[str, float] = {}
    for name, s, d, _ in every:
        inner = tracereduce._merged(
            [(cs, cs + cd) for _, cs, cd, _ in every
             if s <= cs and cs + cd <= s + d and (cs, cd) != (s, d)])
        out[name] = out.get(name, 0.0) + d - sum(e - b for b, e in inner)
    return {k: v / n / 1e6 for k, v in out.items()} if n else {}


def read(trace: tracereduce.Trace, flops_per_sample: int,
         peak_flops_per_s: float) -> dict:
    """Every number above, by name."""
    return {"rounds": rounds(trace),
            "schedule_host_ms": schedule_host_ms(trace),
            "idle_schedule_ms": idle_schedule_ms(trace),
            "eval_sync_ms": eval_sync_ms(trace),
            "local_step_ms": local_step_ms(trace),
            "local_step_mfu": local_step_mfu(trace, flops_per_sample,
                                             peak_flops_per_s),
            "aggregate_gbps": aggregate_gbps(trace),
            "self_ms": self_ms(trace),
            "idle_gaps": idle_gaps(trace)}


def run(cell, seed: int, seconds: float, t_start: float, trace_dir: str,
        chip: Optional[dict] = None, log=print) -> Tuple[dict, Dict]:
    """``cell.run`` untraced, with the profiler writing to ``trace_dir``
    from the end of set-up to the end of the first episode: the log lines
    that ``cell.run`` writes around its window, so its ``round_s`` is the
    traced window's.  Returns the result line's object, with the numbers
    above under ``program``, and the trace."""
    import jax

    from bench import cell as cells

    chip = chip or cells.find_chip(cell.chips)
    marks: List[str] = []

    def log_and_profile(msg: str) -> None:
        log(msg)
        if msg.startswith("set-up ") and not marks:
            jax.profiler.start_trace(trace_dir)
            marks.append("start")
        elif msg.startswith("window: ") and marks == ["start"]:
            jax.profiler.stop_trace()
            marks.append("stop")

    result = cells.run(cell, seed, seconds, False, t_start, chip=chip,
                       log=log_and_profile)
    if marks != ["start", "stop"]:
        raise RuntimeError("cell.run logged no set-up and window lines")
    trace = tracereduce.load(trace_dir)
    trace["program"] = load(trace_dir)
    result["program"] = read(
        trace, cell.model.forward_flops_per_sample(cell.config),
        chip["peak"]["bf16_flops_per_s"])
    return result, trace


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import json
    import shutil
    import tempfile

    from bench import cell as cells

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="write the profiler's trace here")
    args = ap.parse_args(argv)

    trace_dir = args.keep or tempfile.mkdtemp(prefix="programspans-")
    try:
        result, _ = run(cells.load_cell(args.workload), args.seed,
                        args.seconds, T_START, trace_dir,
                        log=lambda m: print(m, file=sys.stderr, flush=True))
    finally:
        if not args.keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
