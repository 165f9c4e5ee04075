"""The reduction from a profiler trace to the benchmark's device numbers:
on hand-made events, and on a trimmed trace of a training cell recorded on
a TPU v5e (``fixtures/train_qmqe_trace.json``, events of the reduction's
own form)."""
import json
from pathlib import Path

import pytest

from benchlib import trace as tr

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "train_qmqe_trace.json"
DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
US = 1e3  # ns


def _ev(plane, name, start_us, dur_us, line=None):
    if line is None:
        line = tr.OP_LINE if plane.startswith("/device") else "python"
    return tr.Event(plane, line, name, start_us * US, dur_us * US)


def _events():
    return [
        _ev(HOST, "bench.window", 0, 1000),
        _ev(HOST, "bench.train_step", 50, 400),
        _ev(HOST, "bench.batch", 450, 250),
        _ev(DEV0, "jit_step", 90, 220, line=tr.MODULE_LINE),
        _ev(DEV0, "jit_batch", 490, 120, line=tr.MODULE_LINE),
        _ev(DEV0, "while.7", 100, 200),           # a loop around two ops
        _ev(DEV0, "fusion.1", 100, 100),
        _ev(DEV0, "sfp_unpack.4", 200, 100),
        _ev(DEV0, "fusion.2", 500, 100),
        _ev(DEV0, "fusion.3", 1200, 100),         # after the window
    ]


def test_union_merges_overlaps_and_keeps_order():
    assert tr.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.union_ns([]) == []


def test_busy_idle_and_gaps_named_by_host_span():
    red = tr.reduce(_events())
    assert red.window_ns == 1000 * US
    assert red.busy_ns == 300 * US          # [100, 300] and [500, 600]
    assert red.idle_share == pytest.approx(0.7)
    assert red.devices == 1
    # [600, 1000] under no span but the window's, [300, 500] and [0, 100]
    # inside the train_step span
    assert red.gaps == [("outside_host_spans", 400 * US),
                        ("train_step", 200 * US), ("train_step", 100 * US)]


def test_only_leaf_ops_inside_the_window_count():
    red = tr.reduce(_events())
    assert red.module_ns == {"jit_step": 200 * US, "jit_batch": 100 * US}
    assert red.op_ns == {"jit_step/fusion.1": 100 * US,
                         "jit_step/sfp_unpack.4": 100 * US,
                         "jit_batch/fusion.2": 100 * US}
    assert red.time_ns(tr.matcher("sfp_unpack")) == 100 * US
    assert red.time_ns(tr.matcher("fusion", module="batch")) == 100 * US
    assert red.count(tr.matcher("fusion")) == 2


def test_leaves_and_names():
    outer = _ev(DEV0, "while.1", 0, 10)
    inner = [_ev(DEV0, "a.1", 0, 4), _ev(DEV0, "b.2", 4, 6)]
    assert tr.leaves([outer] + inner) == inner
    assert tr.op_name("%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop") \
        == "fusion.12"
    assert tr.module_name("jit_train_step(42)") == "jit_train_step"


def test_busy_is_averaged_over_devices():
    ev = _events() + [_ev(DEV1, "fusion.9", 0, 1000)]
    red = tr.reduce(ev)
    assert red.devices == 2
    assert red.busy_ns == pytest.approx((300 + 1000) / 2 * US)


def test_breakdown_is_in_seconds_and_capped():
    ev = _events() + [_ev(DEV0, f"op.{i}", 700 + 10 * i, 5)
                      for i in range(20)]
    b = tr.reduce(ev).breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0] == ["jit_step/fusion.1", pytest.approx(100e-6)]
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_a_trace_without_device_ops_or_window_is_refused():
    with pytest.raises(ValueError):
        tr.reduce([_ev(HOST, "bench.window", 0, 10)])
    with pytest.raises(KeyError):
        tr.reduce([_ev(DEV0, "fusion.1", 0, 10)])


def test_events_round_trip_through_json():
    ev = _events()
    assert tr.from_json(json.loads(json.dumps(tr.to_json(ev)))) == ev


@pytest.fixture(scope="module")
def recorded():
    return tr.from_json(json.loads(FIXTURE.read_text()))


def test_recorded_trace_reduces(recorded):
    red = tr.reduce(recorded)
    assert red.devices == 1
    assert 0 < red.busy_ns <= red.window_ns
    assert 0.0 <= red.idle_share < 1.0
    assert red.gaps and all(ns >= 1e4 for _, ns in red.gaps)
    assert len(red.breakdown()["device_ops"]) == 10
    # every leaf op lies inside a run of the step or of the batch maker
    assert set(red.module_ns) == {"jit_train_step", "jit_make"}
    assert red.module_ns["jit_train_step"] > 0.9 * red.busy_ns


def test_recorded_trace_names_the_stash_kernels(recorded):
    red = tr.reduce(recorded)
    pack = red.count(tr.matcher("sfp_quantize_pack"))
    unpack = red.count(tr.matcher("sfp_unpack"))
    assert pack > 0 and unpack > 0
    assert red.time_ns(tr.matcher("sfp_quantize_pack", "sfp_unpack")) \
        < red.busy_ns
