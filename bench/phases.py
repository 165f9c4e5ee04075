#!/usr/bin/env python3
"""Where the device's idle time falls among the program's serving phases.

    python3 bench/phases.py --workload <name> --seed <n> --seconds <s> \\
        [--out phases.json]

Runs one serving cell once as ``bench/run.py --trace 1`` runs it, keeps the
profiler's trace long enough to read the program's own ``serve.*`` spans
from it (``benchlib.spans``), and prints one JSON object:

* ``metrics``: the cell's per-layer metrics, read as the benchmark reads
  them; ``end_to_end``: the job's end-to-end values in this traced run;
* ``from_trace``: ``step_host_ms`` and ``integrity_host_ms_per_step``
  computed from the spans in the trace file, on the profiler's clock;
* ``phases``: per program span, its count, mean length and the device
  idle time per step whose middle it is the innermost span of;
* ``gaps``: the longest idle gaps, named ``<bench span>/<program span>``;
* ``step_coverage``: the share of the ``bench.scheduler_step`` spans'
  length that ``serve.step`` spans cover.

A diagnostic beside the benchmark, never run by it. Needs the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _covered(outer, inner) -> float:
    """Share of the length of ``outer`` intervals that ``inner`` covers."""
    from benchlib import trace as tr
    merged = tr.union_ns(inner)
    total = sum(e - s for s, e in outer)
    hit = sum(max(0.0, min(e, b) - max(s, a))
              for s, e in outer for a, b in merged)
    return hit / total if total else float("nan")


def analyse(events, program, steps) -> dict:
    from benchlib import spans as sp
    from benchlib import trace as tr
    lo, hi = tr.window_of(events, "bench.window")
    inside = [e for e in program if e.start_ns >= lo and e.end_ns <= hi]
    spans = sp.as_spans(inside)
    host = sp.step_host(spans)
    per = sp.integrity_per_step(spans)
    n_steps = max(1, sum(1 for e in inside if e.name == sp.STEP))
    phases = {k: {"count": v["count"],
                  "mean_ms": v["span_ns"] * 1e-6 / max(1, v["count"]),
                  "idle_ms_per_step": v["idle_ns"] * 1e-6 / n_steps}
              for k, v in sp.idle_by_span(events, inside).items()}
    bench_steps = [(e.start_ns, e.end_ns) for e in events
                   if e.name == "bench.scheduler_step"
                   and e.start_ns >= lo and e.end_ns <= hi]
    return {
        "from_trace": {
            "step_host_ms": 1e3 * 1e-9 * sum(host) / len(host)
            if host else None,
            "integrity_host_ms_per_step": None if per is None
            else per * 1e-6,
            "serve_steps": sum(1 for e in inside if e.name == sp.STEP),
            "job_steps": steps},
        "phases": phases,
        "gaps": [[n, g * 1e-9] for n, _, g in
                 sp.named_gaps(events, inside)[:15]],
        "step_coverage": _covered(
            bench_steps, [(e.start_ns, e.end_ns) for e in inside
                          if e.name == sp.STEP])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from benchlib import core, peaks
    from benchlib import spans as sp
    from benchlib import trace as tr

    try:
        run, job, devs = core.prepare(ROOT, args.workload, args.seed,
                                      args.seconds, True, T_START)
    except core.NoChip as e:
        print(f"phases: {e}", file=sys.stderr)
        return 2
    out = job(run)
    try:
        path = tr.find_xplane(run.trace_dir)
        events = tr.load_xplane(path)
        program = sp.load_program_events(path)
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    reduced = tr.reduce(events)
    reader = core.Reading(run.cell, run, out, reduced,
                          peaks.for_kind(run.device.device_kind))
    metrics = {}
    for m in run.cell.per_layer():
        mod = core.load_module(BENCH / "metrics" / f"{m['name']}.py",
                               "bench_metric_" + m["name"].replace(".", "_"))
        metrics[m["name"]] = mod.read(reader)
    line = {"workload": args.workload, "seed": args.seed,
            "device": run.device.device_kind, "metrics": metrics,
            "end_to_end": out.end_to_end,
            "idle_share": reduced.idle_share,
            "breakdown": reduced.breakdown(),
            **analyse(events, program, out.facts.get("steps"))}
    text = json.dumps(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
