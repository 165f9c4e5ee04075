"""The program's own host spans (``repro.obs.span``: ``serve.step`` and the
phases inside it), read beside the device trace.

The serving loop marks each phase of ``Scheduler.step`` with a
``jax.profiler.TraceAnnotation`` whose name starts with ``serve.``. Two
readings of the same spans exist:

* in the process, while the profiler records: ``repro.obs.profiled_spans``
  keeps each closed span as (name, start, end) on ``time.perf_counter``.
  The per-layer readers use it (``profiled``), since the harness keeps
  only the benchmark's own ``bench.`` spans of the trace file;
* in the trace file, on the profiler's clock: ``load_program_events``
  reads them, and ``named_gaps`` names each idle gap of the device by the
  innermost benchmark span and the innermost program span covering it
  (``scheduler_step/serve.refresh``), with the lengths ``trace.reduce``
  gives.

A program without these spans gives no spans, and the readers nothing.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from benchlib import trace as tr

PREFIX = "serve."                  # names of the program's spans
STEP = "serve.step"
# spans that wait on a device program: host time outside them is host work
DEVICE_WAITS = ("serve.decode", "serve.prefill", "serve.checksums")
INTEGRITY = ("serve.verify", "serve.refresh")

Span = Tuple[str, float, float]    # (name, start, end), one clock


def profiled(r) -> Optional[List[Span]]:
    """The program's spans wholly inside the traced window, in seconds on
    ``time.perf_counter``; None when nothing was traced or the program
    keeps no spans."""
    run = r.run
    if r.trace is None or run.setup_s is None or run.window_s is None:
        return None
    from repro import obs
    read = getattr(obs, "profiled_spans", None)
    if read is None:
        return None
    lo = run.t_start + run.setup_s
    return [s for s in read(lo, lo + run.window_s)
            if s[0].startswith(PREFIX)] or None


def _inside(spans: Sequence[Span], lo: float, hi: float) -> List[Span]:
    return [s for s in spans if s[1] >= lo and s[2] <= hi]


def step_host(spans: Sequence[Span]) -> List[float]:
    """For each ``serve.step``: its length less the union of the
    device-waiting spans inside it (host time with no device call
    pending)."""
    out = []
    for _, lo, hi in (s for s in spans if s[0] == STEP):
        waits = tr.union_ns((a, b) for n, a, b in _inside(spans, lo, hi)
                            if n in DEVICE_WAITS)
        out.append((hi - lo) - sum(b - a for a, b in waits))
    return out


def integrity_per_step(spans: Sequence[Span]) -> Optional[float]:
    """Length of the ``serve.verify`` and ``serve.refresh`` spans over the
    number of ``serve.step`` spans."""
    steps = sum(1 for s in spans if s[0] == STEP)
    if not steps:
        return None
    return sum(b - a for n, a, b in spans if n in INTEGRITY) / steps


def mean_step_host_ms(r) -> Optional[float]:
    """``step_host`` over the traced window's steps, mean, in ms."""
    host = step_host(profiled(r) or [])
    return 1e3 * sum(host) / len(host) if host else None


def integrity_host_ms(r) -> Optional[float]:
    """``integrity_per_step`` over the traced window, in ms."""
    per = integrity_per_step(profiled(r) or [])
    return None if per is None else 1e3 * per


def load_program_events(path: str) -> List[tr.Event]:
    """The program's host spans in one ``.xplane.pb``, as ``trace.Event``s
    on the profiler's clock (the device planes and ``bench.`` spans come
    from ``trace.load_xplane``)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return [tr.Event(plane.name, line.name, ev.name, float(ev.start_ns),
                     float(ev.duration_ns))
            for plane in data.planes if not plane.name.startswith(
                tr.DEVICE_PLANE_PREFIX)
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


def as_spans(events: Sequence[tr.Event]) -> List[Span]:
    return [(e.name, e.start_ns, e.end_ns) for e in events]


def _innermost(spans: Sequence[tr.Event], t: float, skip: str = ""
               ) -> Optional[tr.Event]:
    best = None
    for e in spans:
        if e.name != skip and e.start_ns <= t < e.end_ns and (
                best is None or e.dur_ns < best.dur_ns):
            best = e
    return best


def named_gaps(events: Sequence[tr.Event], program: Sequence[tr.Event],
               window_span: str = "bench.window", min_gap_ns: float = 1e4
               ) -> List[Tuple[str, float, float]]:
    """(name, start, length) of each idle gap of each device plane, by the
    rule of ``trace.reduce`` (so the lengths are its ``gaps``), longest
    first. The name is the innermost ``bench.`` span covering the gap's
    middle, then ``/`` and the innermost program span there, if any."""
    lo, hi = tr.window_of(events, window_span)
    bench = [e for e in events if not e.plane.startswith(
        tr.DEVICE_PLANE_PREFIX)]
    planes = sorted({e.plane for e in events
                     if e.plane.startswith(tr.DEVICE_PLANE_PREFIX)
                     and e.line == tr.OP_LINE})
    out = []
    for p in planes:
        leaf = [e for e in tr.leaves([e for e in events if e.plane == p
                                      and e.line == tr.OP_LINE])
                if e.end_ns > lo and e.start_ns < hi]
        merged = tr.union_ns(tr._clip([(e.start_ns, e.end_ns)
                                       for e in leaf], lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s < min_gap_ns:
                continue
            mid = (s + e) / 2
            b = _innermost(bench, mid, skip=window_span)
            name = b.name[len(tr.HOST_PREFIX):] if b else "outside_host_spans"
            inner = _innermost(program, mid)
            out.append((f"{name}/{inner.name}" if inner else name, s, e - s))
    out.sort(key=lambda g: -g[2])
    return out


def idle_by_span(events: Sequence[tr.Event], program: Sequence[tr.Event],
                 window_span: str = "bench.window"
                 ) -> dict:
    """Per program span name: its count and total length inside the
    window, and the device idle time (all gaps, no minimum) whose middle
    it is the innermost program span of."""
    lo, hi = tr.window_of(events, window_span)
    inside = [e for e in program if e.start_ns >= lo and e.end_ns <= hi]
    out: dict = {}
    for e in inside:
        d = out.setdefault(e.name, {"count": 0, "span_ns": 0.0,
                                    "idle_ns": 0.0})
        d["count"] += 1
        d["span_ns"] += e.dur_ns
    for name, _, ns in named_gaps(events, inside, window_span, 0.0):
        key = name.split("/", 1)[1] if "/" in name else "(no program span)"
        d = out.setdefault(key, {"count": 0, "span_ns": 0.0, "idle_ns": 0.0})
        d["idle_ns"] += ns
    return out
