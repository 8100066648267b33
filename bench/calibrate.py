"""The readings a cell's limits are set from, at the cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --faults 3

For every seed the program runs the cell's reference rounds (no window) and
the plain reference replays them: the program's readings.  On the first
``--faults`` seeds it also reads the control, the reference computed in
bfloat16, and each planted fault of ``bench/faults.py``.  One JSON object
per seed goes to standard output.  Needs the chip, as ``bench/run.py`` does.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def program_records(cell, built):
    """The recorded rounds and the schedule of the first episode."""
    import jax

    from bench import cell as cells

    episodes = cells.Episodes(built, cell.traffic, record=cell.reference_rounds)
    cells.finish_first_episode(episodes)
    return ([cells.Record(jax.device_get(r.params), r.loss, r.events)
             for r in episodes.first], episodes.first_hours)


def readings(cell, test, records, hours, replay):
    from bench import cell as cells

    out = cells.compare(cell, test, records, replay)
    out["schedule_gap"] = cells.schedule_gap(hours, cell.schedule_hours)
    return out


def calibrate_seed(cell, seed: int, faults: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import cell as cells
    from bench import faults as planted

    out = {"seed": seed}
    t0 = time.perf_counter()
    built = cells.build(cell, seed, cells.Counters())
    records, hours = program_records(cell, built)
    clients, test, seeds = built.clients, built.test, built.seeds
    del built
    gc.collect()
    t1 = time.perf_counter()
    replay = cells.reference_replay(cell, clients, test, seeds, records)
    t2 = time.perf_counter()
    out["program"] = readings(cell, test, records, hours, replay)
    out["seconds"] = {"program": t1 - t0, "reference": t2 - t1}
    if faults:
        bf16 = cells.reference_replay(cell, clients, test, seeds, records,
                                      dtype=jnp.bfloat16)
        w0, p, losses = bf16
        treedef = jax.tree_util.tree_structure(records[0].params)
        out["control_bfloat16"] = readings(
            cell, test, [cells.Record(treedef.unflatten(pp), ll, r.events)
                         for pp, ll, r in zip(p, losses, records)],
            hours, replay)
        runs = [(f, cell, planted.task_class(f)) for f in planted.FAULTS]
        runs.append(("worse_schedule", planted.worse_schedule(cell), None))
        for fault, fcell, task_cls in runs:
            built = cells.build(fcell, seed, cells.Counters(),
                                task_cls=task_cls)
            frec, fhours = program_records(fcell, built)
            del built
            gc.collect()
            out[fault] = readings(cell, test, frec, fhours, replay)
        out["seconds"]["control_and_faults"] = time.perf_counter() - t2
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import cell as cells
    from repro.launch.compile_cache import enable_compile_cache

    cell = cells.load_cell(args.workload)
    cells.find_chip(cell.chips)
    enable_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        print(json.dumps(calibrate_seed(cell, seed, i < args.faults)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
