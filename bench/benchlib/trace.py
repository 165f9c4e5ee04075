"""Reduction of a profiler trace to the benchmark's device numbers.

A traced run writes an ``.xplane.pb``; ``load_xplane`` reads it through
``jax.profiler.ProfileData`` into plain ``Event`` records, and ``reduce``
turns those into:

* busy time: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices;
* device time by operation and by compiled program;
* idle gaps, each named by the benchmark's own host span
  (``jax.profiler.TraceAnnotation`` names starting with ``bench.``) that
  covers it.

On a TPU each device plane has an ``XLA Ops`` line, whose events are HLO
instructions (named by their text, ``%name.N = shape op(...)``) nested
inside the loops and calls that run them, and an ``XLA Modules`` line,
one event per run of a compiled program. Only leaf operations (those that
contain no other) count as device time, so a loop is not counted beside
its body; each is attributed to the program whose run covers it.

Everything below ``load_xplane`` works on ``Event`` lists, so the tests
check it on a small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_PREFIX = "bench."          # names of the benchmark's host spans
DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _is_device_plane(name: str) -> bool:
    return name.startswith(DEVICE_PLANE_PREFIX) and "SparseCore" not in name


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def module_name(text: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", text.strip())


def load_xplane(path: str) -> List[Event]:
    """Device ops and program runs (every device plane's ``XLA Ops`` and
    ``XLA Modules`` lines) and the benchmark's host spans, from one
    ``.xplane.pb``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        device = _is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            name_of = op_name if line.name == OP_LINE else module_name
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIX):
                    continue
                out.append(Event(plane.name, line.name,
                                 name_of(ev.name) if device else ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def union_ns(intervals: Iterable[Tuple[float, float]]
             ) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The operations of one plane that contain no other operation."""
    order = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    out = []
    for i, e in enumerate(order):
        nxt = order[i + 1] if i + 1 < len(order) else None
        if nxt is None or nxt.start_ns >= e.end_ns:
            out.append(e)
    return out


@dataclasses.dataclass
class Op:
    """A leaf device operation inside the window, with its program."""

    name: str
    module: str
    plane: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Reduced:
    window_ns: float
    busy_ns: float                      # mean over devices
    devices: int
    op_ns: Dict[str, float]             # "module/op" -> device time
    module_ns: Dict[str, float]         # program -> device time
    gaps: List[Tuple[str, float]]       # (host span, ns), longest first
    ops: List[Op]                       # leaf device ops inside the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def time_ns(self, match) -> float:
        """Device time of the ops for which ``match(op)`` holds."""
        return sum(o.dur_ns for o in self.ops if match(o))

    def count(self, match) -> int:
        return sum(1 for o in self.ops if match(o))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v * 1e-9] for k, v in top],
                "idle_gaps": [[k, v * 1e-9] for k, v in self.gaps[:n]]}


def window_of(events: Sequence[Event], span: str) -> Tuple[float, float]:
    """(start, end) of the host span named ``span`` (first one found)."""
    for e in events:
        if e.name == span and not _is_device_plane(e.plane):
            return e.start_ns, e.end_ns
    raise KeyError(f"no host span {span!r} in the trace")


def _host_phase(host: Sequence[Event], t: float, window_span: str) -> str:
    """Innermost benchmark host span covering time ``t``."""
    best: Optional[Event] = None
    for e in host:
        if e.name == window_span or not (e.start_ns <= t < e.end_ns):
            continue
        if best is None or e.dur_ns < best.dur_ns:
            best = e
    return best.name[len(HOST_PREFIX):] if best else "outside_host_spans"


def _module_at(runs: List[Event], starts: List[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and runs[i].start_ns <= t < runs[i].end_ns:
        return runs[i].name
    return ""


def reduce(events: Sequence[Event], window_span: str = "bench.window",
           min_gap_ns: float = 1e4) -> Reduced:
    lo, hi = window_of(events, window_span)
    host = [e for e in events if not _is_device_plane(e.plane)]
    planes = sorted({e.plane for e in events if _is_device_plane(e.plane)
                     and e.line == OP_LINE
                     and e.end_ns > lo and e.start_ns < hi})
    if not planes:
        raise ValueError("no device operation ran inside the traced window")
    busy = 0.0
    gaps: List[Tuple[str, float]] = []
    ops: List[Op] = []
    for p in planes:
        leaf = [e for e in leaves([e for e in events if e.plane == p
                                   and e.line == OP_LINE])
                if e.end_ns > lo and e.start_ns < hi]
        runs = sorted((e for e in events if e.plane == p
                       and e.line == MODULE_LINE), key=lambda e: e.start_ns)
        starts = [e.start_ns for e in runs]
        for e in leaf:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            ops.append(Op(e.name, _module_at(runs, starts, (s + t) / 2), p,
                          s, t - s))
        merged = union_ns(_clip([(e.start_ns, e.end_ns) for e in leaf],
                                lo, hi))
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s >= min_gap_ns:
                gaps.append((_host_phase(host, (s + e) / 2, window_span),
                             e - s))
    op_ns: Dict[str, float] = {}
    module_ns: Dict[str, float] = {}
    for o in ops:
        key = f"{o.module}/{o.name}" if o.module else o.name
        op_ns[key] = op_ns.get(key, 0.0) + o.dur_ns
        module_ns[o.module] = module_ns.get(o.module, 0.0) + o.dur_ns
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_ns=hi - lo, busy_ns=busy / len(planes),
                   devices=len(planes), op_ns=op_ns, module_ns=module_ns,
                   gaps=gaps, ops=ops)


def matcher(*prefixes: str, module: str = ""):
    """Match leaf ops whose name (numeric suffix dropped) starts with any
    of ``prefixes``, inside programs whose name contains ``module``."""
    def match(o: Op) -> bool:
        base = re.sub(r"\.\d+$", "", o.name)
        return (any(base.startswith(p) for p in prefixes)
                and module in o.module)
    return match


def to_json(events: Sequence[Event]) -> list:
    return [[e.plane, e.line, e.name, e.start_ns, e.dur_ns] for e in events]


def from_json(rows: list) -> List[Event]:
    return [Event(p, l, n, float(s), float(d)) for p, l, n, s, d in rows]
