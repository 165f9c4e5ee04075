"""The granite chat cell's per-layer readers on made-up readings: the
grouped matmul's roofline share from the program's per-layer notes and the
trace's ``ragged-dot`` ops, its operations and bytes by hand, and the span
readers over the window; each gives nothing where there is nothing to
read, as it must on a program that keeps no notes."""
from collections import deque

import pytest

from benchlib import core, moe_cost, peaks
from benchlib import spans as sp
from benchlib import trace as tr
from repro import obs
from repro.obs import trace as obs_trace
from test_bench_spans import ROOT, _reader, _step_events

CELL = "granite-4.0-h-small.serve.azure-chat-sfp8"
V5E = peaks.for_kind("TPU v5 lite")


def _reading(trace, notes, spans, monkeypatch, peak=V5E):
    """A traced run whose window is [102, 103] s on perf_counter."""
    c = core.Cell.load(ROOT, CELL)
    run = core.Run(c, 1, 1.0, True, 100.0, None, None)
    run.setup_s, run.window_s = 2.0, 1.0
    monkeypatch.setattr(obs_trace, "_NOTED", deque(notes))
    monkeypatch.setattr(obs_trace, "_PROFILED", deque(spans))
    out = core.Outcome(end_to_end={}, attempted=1, failed=0, checks={},
                       facts={})
    return core.Reading(c, run, out, trace, peak)


def _moe_trace(gmm_ns):
    dev = "/device:TPU:0"
    return tr.reduce([
        tr.Event("/host:CPU", "python", "bench.window", 0.0, 1e9),
        tr.Event(dev, tr.MODULE_LINE, "jit__step_fn", 0.0, 5e8),
        tr.Event(dev, tr.OP_LINE, "ragged-dot-metadata.1", 0.0, 1e6),
        tr.Event(dev, tr.OP_LINE, "ragged-dot-none.28", 1e6, gmm_ns - 1e6),
        tr.Event(dev, tr.OP_LINE, "fusion.617", 2e8, 1e7)])


def test_gmm_call_by_hand():
    conf = core.Cell.load(ROOT, CELL).config
    flops, byts = moe_cost.gmm_call(conf, 89)
    # 3 matrices of 4096 x 768 an expert; 9 experts held
    assert flops == 2 * 3 * 4096 * 768 * 89
    assert byts == 9 * 3 * 4096 * 768 * 2 + 2 * 89 * 4096 * 2


def test_moe_gmm_roofline_from_notes_and_ragged_dot_ops(monkeypatch):
    conf = core.Cell.load(ROOT, CELL).config
    # two decode steps of 10 MoE layers in the window, one before it
    notes = [("serve.moe_held", 101.5, (80,) * 10),
             ("serve.moe_held", 102.2, (89,) * 10),
             ("serve.moe_held", 102.4, (70,) * 10)]
    r = _reading(_moe_trace(8e6), notes, [], monkeypatch)
    least = sum(moe_cost.gmm_call(conf, n)[1] / V5E.hbm_bytes
                for n in (89,) * 10 + (70,) * 10)
    roof = _reader("moe_gmm_roofline").read(r)
    assert roof == pytest.approx(100 * least / 8e-3)
    assert 0 < roof < 100


def test_moe_gmm_roofline_gives_nothing_without_notes_or_ops(monkeypatch):
    notes = [("serve.moe_held", 102.2, (89,) * 10)]
    r = _reading(_moe_trace(4e6), [], [], monkeypatch)
    assert _reader("moe_gmm_roofline").read(r) is None
    r = _reading(None, notes, [], monkeypatch)
    assert _reader("moe_gmm_roofline").read(r) is None
    no_gmm = tr.reduce([tr.Event("/host:CPU", "python", "bench.window",
                                 0.0, 1e9),
                        tr.Event("/device:TPU:0", tr.OP_LINE, "fusion.1",
                                 0.0, 1e8)])
    r = _reading(no_gmm, notes, [], monkeypatch)
    assert _reader("moe_gmm_roofline").read(r) is None
    # the benchmark laid over a program that keeps no notes
    r = _reading(_moe_trace(4e6), notes, [], monkeypatch)
    monkeypatch.delattr(obs, "profiled_notes")
    assert _reader("moe_gmm_roofline").read(r) is None


def test_span_readers_over_the_window(monkeypatch):
    events, program = _step_events()
    red = tr.reduce(events)
    lo = 102.0
    spans = [(n, lo + a * 1e-9, lo + b * 1e-9)
             for n, a, b in sp.as_spans(program)]
    spans += [("serve.step", 101.0, 101.5)]
    r = _reading(red, [], spans, monkeypatch)
    assert _reader("step_host_ms.azure-chat").read(r) == pytest.approx(0.195)
    prefills = [b - a for n, a, b in spans[:-1] if n == "serve.prefill"]
    assert _reader("prefill_ms_per_admission.azure-chat").read(r) \
        == pytest.approx(1e3 * sum(prefills) / len(prefills))
    assert _reader("device_idle_share.azure-chat").read(r) \
        == pytest.approx(100 * red.idle_share)
    r = _reading(red, [], [], monkeypatch)
    assert _reader("step_host_ms.azure-chat").read(r) is None
    assert _reader("prefill_ms_per_admission.azure-chat").read(r) is None
    r = _reading(None, [], spans, monkeypatch)
    assert _reader("device_idle_share.azure-chat").read(r) is None
