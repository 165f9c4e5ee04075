#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 \\
        [--seconds S] [--control] [--fault unchanged|half_batch|token]

Runs the cell once per seed in one process (compiles shared), each run as
the benchmark runs it (set-up, a window of ``--seconds``, the check), and
prints one JSON line per seed: ``correct`` and ``checks`` as the check
judged the run, and ``numbers``, the program's own readings.
``--control`` puts the control (the plain reference computed in float8)
in the program's place, so ``checks`` and ``correct`` judge the control
against the float32 reference. ``--fault`` plants one of the faults the
check has to catch in the timed path. Neither is ever used by
``bench/run.py``. Needs the chip, like the benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from benchlib import core

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            line = core.run_cell(ROOT, args.workload, seed, args.seconds,
                                 False, t0, control=args.control,
                                 fault=args.fault)
        except core.NoChip as e:
            print(f"readings: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "control": args.control,
                          "correct": line["correct"],
                          "checks": line["checks"],
                          "numbers": line.get("numbers"),
                          "metrics": line["metrics"],
                          "run_s": time.perf_counter() - t0}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
