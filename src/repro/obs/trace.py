"""Per-request span tracer exporting Chrome ``trace_event`` JSON.

Every request gets its own lane (tid) inside one process row, so the
Perfetto timeline reads as: one horizontal track per request, spans for
`queued → prefill → decode[burst] → ...`, instants for submit / preempt /
recover / retire, with geometry, blocks held, and downshift flags as
span args. The output format is the Trace Event Format's JSON-array
flavor (``{"traceEvents": [...]}``) — the same container
``jax.profiler.trace`` produces — so a serve trace opens in
Perfetto/``chrome://tracing`` next to the profiler capture from
`bench_decode_micro.py`.

Timestamps are microseconds from a monotonic clock; the tracer never
touches device values, so it adds no host sync — callers hand it host
scalars only, after any jitted step has already been consumed at the
host boundary.

``span`` marks a phase of the host loop (``serve.step``, ``serve.decode``,
...) on the profiler's own clock: a ``jax.profiler.TraceAnnotation``, so a
``jax.profiler`` capture shows the phase on its host thread above the
device ops it waited for. With a tracer it also lands on the tracer's
``scheduler`` lane. While a profiler records, each closed span is kept in
``profiled_spans()`` too, so the process can read back the phases it put
in the capture without parsing the capture's file.
"""
from __future__ import annotations

import contextlib
import json
import time
from collections import deque
from typing import Any, Iterator, NamedTuple

from jax.profiler import TraceAnnotation

_PID = 1  # single-process: one row in the viewer


class _Open(NamedTuple):
    name: str
    tid: int
    t0_us: float
    args: dict[str, Any]


class SpanTracer:
    def __init__(self, clock=time.perf_counter) -> None:
        self._clock = clock
        self._t0 = clock()
        self.events: list[dict[str, Any]] = []
        self._tids: dict[str, int] = {}

    def _now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def _tid(self, lane: str) -> int:
        tid = self._tids.get(lane)
        if tid is None:
            tid = self._tids[lane] = len(self._tids) + 1
            self.events.append({
                "name": "thread_name", "ph": "M", "pid": _PID, "tid": tid,
                "args": {"name": lane}})
        return tid

    def begin(self, name: str, lane: str, **args: Any) -> _Open:
        """Open a span on ``lane`` (request uid or subsystem name)."""
        return _Open(name, self._tid(lane), self._now_us(), args)

    def end(self, span: _Open, **extra: Any) -> None:
        t1 = self._now_us()
        self.events.append({
            "name": span.name, "ph": "X", "pid": _PID, "tid": span.tid,
            "ts": span.t0_us, "dur": max(t1 - span.t0_us, 0.0),
            "args": {**span.args, **extra}})

    def complete(self, name: str, lane: str, dur_s: float,
                 **args: Any) -> None:
        """Record an already-finished span ending now, ``dur_s`` long."""
        t1 = self._now_us()
        dur = max(dur_s, 0.0) * 1e6
        # A span can out-span the tracer (the first prefill includes jit
        # compile; the tracer may be younger): clamp its start into the
        # trace's epoch rather than emitting a negative timestamp.
        self.events.append({
            "name": name, "ph": "X", "pid": _PID, "tid": self._tid(lane),
            "ts": max(t1 - dur, 0.0), "dur": dur, "args": args})

    def instant(self, name: str, lane: str, **args: Any) -> None:
        self.events.append({
            "name": name, "ph": "i", "s": "t", "pid": _PID,
            "tid": self._tid(lane), "ts": self._now_us(), "args": args})

    def export(self) -> dict[str, Any]:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.export(), fh)

    # ---- queries (test/report support) ----------------------------------

    def spans(self, lane: str | None = None,
              name: str | None = None) -> list[dict[str, Any]]:
        tid = self._tids.get(lane) if lane is not None else None
        return [e for e in self.events
                if e["ph"] in ("X", "i")
                and (lane is None or e["tid"] == tid)
                and (name is None or e["name"] == name)]

    def lanes(self) -> list[str]:
        return list(self._tids)


# (name, start_s, end_s) on time.perf_counter, one per span closed while a
# profiler recorded; bounded, so the oldest go first.
_PROFILED: deque[tuple[str, float, float]] = deque(maxlen=1 << 16)


@contextlib.contextmanager
def span(name: str, tracer: SpanTracer | None = None) -> Iterator[None]:
    """A phase of the host loop, named ``name``. Inert (no annotation, no
    record) when no profiler runs and ``tracer`` is None. Host code only:
    inside jitted code it would mark tracing, not the step."""
    profiling = TraceAnnotation.is_enabled()
    if not profiling and tracer is None:
        yield
        return
    opened = tracer.begin(name, "scheduler") if tracer is not None else None
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name):
            yield
    finally:
        if profiling:
            _PROFILED.append((name, t0, time.perf_counter()))
        if opened is not None:
            tracer.end(opened)


# (name, time_s, values) noted while a profiler recorded; bounded too.
_NOTED: deque[tuple[str, float, tuple]] = deque(maxlen=1 << 16)


def note(name: str, values) -> None:
    """Keep host ``values`` under ``name``, stamped on
    ``time.perf_counter``, for ``profiled_notes()`` while a profiler
    records; nothing otherwise. Host code only, after the values were
    pulled from the device."""
    if TraceAnnotation.is_enabled():
        _NOTED.append((name, time.perf_counter(), tuple(values)))


def profiled_notes(lo: float = float("-inf"),
                   hi: float = float("inf")) -> list[tuple[str, float, tuple]]:
    """Notes taken while a profiler recorded, stamped inside
    [``lo``, ``hi``], oldest first."""
    return [n for n in _NOTED if lo <= n[1] <= hi]


def profiled_spans(lo: float = float("-inf"),
                   hi: float = float("inf")) -> list[tuple[str, float, float]]:
    """Spans closed while a profiler recorded that lie wholly inside
    [``lo``, ``hi``] (``time.perf_counter`` seconds), oldest first."""
    return [s for s in _PROFILED if s[1] >= lo and s[2] <= hi]
