"""The program's serving spans beside the device trace (``benchlib.spans``)
and the readers built on them, on hand-made events with hand-counted
answers; and the trace reduction's figures on the recorded trace, pinned
so that reading the program's spans changes none of them."""
import json
from collections import deque
from pathlib import Path

import pytest

from benchlib import core
from benchlib import spans as sp
from benchlib import trace as tr
from repro import obs
from repro.obs import trace as obs_trace

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "train_qmqe_trace.json"
DECODE = "mistral-large-123b.serve.decode-m2e4"
CHAT = "mistral-large-123b.serve.chat-sfp8"
DEV, HOST = "/device:TPU:0", "/host:CPU"
US = 1e3  # ns


@pytest.fixture(scope="module")
def recorded():
    return tr.from_json(json.loads(FIXTURE.read_text()))


def test_recorded_trace_figures_are_pinned(recorded):
    red = tr.reduce(recorded)
    assert red.idle_share == pytest.approx(0.029336, abs=5e-7)
    assert [ns for _, ns in red.gaps[:3]] == [590904.0, 538271.0, 524562.0]
    assert [n for n, _ in red.gaps[:3]] == ["loss_read", "batch",
                                            "loss_read"]
    top = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:3]
    assert top == [("jit_train_step/copy.587", 5183988.0),
                   ("jit_train_step/reshape.1107", 4185720.0),
                   ("jit_train_step/sfp_unpack.21", 3761734.0)]
    assert red.busy_ns == 64090882.0


def test_named_gaps_keep_the_reduction_lengths(recorded):
    """Without program spans the names are the reduction's own; the
    lengths are its gaps either way."""
    red = tr.reduce(recorded)
    gaps = sp.named_gaps(recorded, [])
    assert [(n, ns) for n, _, ns in gaps] == red.gaps
    # a program span over the longest gap adds its name, nothing else
    _, start, ns = gaps[0]
    prog = [tr.Event(HOST, "python", "serve.decode", start - 10, ns + 20)]
    named = sp.named_gaps(recorded, prog)
    assert named[0] == ("loss_read/serve.decode", start, ns)
    assert [g[2] for g in named] == [g[2] for g in gaps]


def _ev(name, start_us, dur_us, plane=HOST, line="python"):
    return tr.Event(plane, line, name, start_us * US, dur_us * US)


def _step_events():
    """One 1000 us window, two scheduler steps, the device busy only
    inside the decode calls and one checksum program."""
    bench = [_ev("bench.window", 0, 1000),
             _ev("bench.scheduler_step", 0, 500),
             _ev("bench.scheduler_step", 500, 500)]
    program = [
        _ev("serve.step", 0, 480),
        _ev("serve.verify", 10, 60), _ev("serve.checksums", 20, 40),
        _ev("serve.admit", 80, 120), _ev("serve.prefill", 90, 100),
        _ev("serve.checksums", 100, 30),
        _ev("serve.decode", 250, 150),
        _ev("serve.refresh", 420, 50), _ev("serve.checksums", 430, 30),
        _ev("serve.step", 500, 450),
        _ev("serve.decode", 600, 200),
        _ev("serve.refresh", 870, 70), _ev("serve.checksums", 875, 20)]
    device = [_ev("fusion.1", 20, 40, DEV, tr.OP_LINE),
              _ev("paged_flash_decode_planes.9", 260, 140, DEV, tr.OP_LINE),
              _ev("paged_flash_decode_planes.9", 610, 190, DEV,
                  tr.OP_LINE)]
    return bench + device, program


def test_step_host_and_integrity_by_hand():
    _, program = _step_events()
    spans = sp.as_spans(program)
    # step 1: 480 less prefill [90, 190] (covers its checksums), verify's
    # checksums 40, decode 150, refresh's checksums 30 -> 160
    # step 2: 450 less decode 200 and checksums 20 -> 230
    assert sp.step_host(spans) == [160 * US, 230 * US]
    # verify 60 + refresh 50 + refresh 70, over two steps
    assert sp.integrity_per_step(spans) == pytest.approx(90 * US)
    assert sp.integrity_per_step([]) is None


def test_gaps_named_by_the_innermost_spans():
    events, program = _step_events()
    gaps = sp.named_gaps(events, program, min_gap_ns=0)
    # idle: [0,20] [60,260] [400,610] [800,1000], named at their middles
    # 10, 160, 505, 900
    assert [(n, s / US, ns / US) for n, s, ns in gaps] == [
        ("scheduler_step/serve.step", 400, 210),
        ("scheduler_step/serve.prefill", 60, 200),
        ("scheduler_step/serve.refresh", 800, 200),
        ("scheduler_step/serve.verify", 0, 20)]
    # the lengths are the reduction's
    assert sorted(g[2] for g in gaps) == sorted(
        ns for _, ns in tr.reduce(events, min_gap_ns=0).gaps)
    by = sp.idle_by_span(events, program)
    assert by["serve.step"]["count"] == 2
    assert by["serve.step"]["span_ns"] == 930 * US
    assert by["serve.prefill"]["idle_ns"] == 200 * US
    assert by["serve.step"]["idle_ns"] == 210 * US
    assert by["serve.decode"]["idle_ns"] == by["serve.admit"]["idle_ns"] == 0


def _reading(cell, trace, spans, monkeypatch, t_start=100.0):
    """A traced run whose window is [t_start + 2, t_start + 3] s on
    perf_counter, with ``spans`` as the program's profiled spans."""
    c = core.Cell.load(ROOT, cell)
    run = core.Run(c, 1, 1.0, True, t_start, None, None)
    run.setup_s, run.window_s = 2.0, 1.0
    monkeypatch.setattr(obs_trace, "_PROFILED", deque(spans))
    out = core.Outcome(end_to_end={}, attempted=1, failed=0, checks={},
                       facts={})
    return core.Reading(c, run, out, trace, None)


def _reader(name):
    return core.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                            "m_" + name.replace(".", "_"))


def test_readers_read_the_window_of_profiled_spans(monkeypatch):
    events, program = _step_events()
    red = tr.reduce(events)
    # the same spans in seconds, shifted into the window; one step before
    # the window and one span of another program are left out
    lo = 102.0
    spans = [(n, lo + a * 1e-9, lo + b * 1e-9)
             for n, a, b in sp.as_spans(program)]
    spans += [("serve.step", 101.0, 101.5), ("other.step", lo, lo + 0.5)]
    r = _reading(DECODE, red, spans, monkeypatch)
    assert _reader("step_host_ms.decode").read(r) == pytest.approx(0.195)
    assert _reader("integrity_host_ms_per_step").read(r) \
        == pytest.approx(0.09)
    r = _reading(CHAT, red, spans, monkeypatch)
    assert _reader("step_host_ms.chat").read(r) == pytest.approx(0.195)


@pytest.mark.parametrize("name", ["step_host_ms.decode", "step_host_ms.chat",
                                  "integrity_host_ms_per_step"])
def test_readers_give_nothing_without_spans_or_trace(name, monkeypatch):
    events, _ = _step_events()
    cell = CHAT if name.endswith("chat") else DECODE
    r = _reading(cell, tr.reduce(events), [], monkeypatch)
    assert _reader(name).read(r) is None
    r = _reading(cell, None, [("serve.step", 102.1, 102.2)], monkeypatch)
    assert _reader(name).read(r) is None
    # a program that keeps no spans (the benchmark laid over an older one)
    r = _reading(cell, tr.reduce(events), [("serve.step", 102.1, 102.2)],
                 monkeypatch)
    monkeypatch.delattr(obs, "profiled_spans")
    assert _reader(name).read(r) is None
