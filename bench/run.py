"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the window.
Progress and the compared numbers beside their limits go to standard error;
the last line of standard output is the result object.  Without a TPU whose
``device_kind`` is in ``bench/peaks.json`` the run exits 2 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        _log(f"bench: the program is not in this checkout: {e}")
        return 2
    from bench import cell as cells

    cell = cells.load_cell(args.workload)
    try:
        chip = cells.find_chip(cell.chips)
    except cells.NoChip as e:
        _log(f"bench: {e}")
        return 2
    result = cells.run(cell, args.seed, args.seconds, bool(args.trace),
                       T_START, chip=chip, log=_log)
    for name, c in result["checks"].items():
        _log(f"check {name}: {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
