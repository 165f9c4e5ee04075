"""Per-layer metric readers: each finds its number where the cell has it,
and returns nothing (never 0) where there is nothing to read."""
import json
from pathlib import Path

import pytest

from benchlib import core, cost, peaks
from benchlib import trace as tr

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
V5E = peaks.for_kind("TPU v5 lite")
DECODE = "mistral-large-123b.serve.decode-m2e4"


def _reader(name):
    return core.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                            "m_" + name.replace(".", "_"))


def _reading(cell, facts, trace=None, peak=V5E):
    c = core.Cell.load(ROOT, cell)
    out = core.Outcome(end_to_end={}, attempted=1, failed=0, checks={},
                       facts=facts)
    run = core.Run(c, 1, 1.0, trace is not None, 0.0, None, None)
    return core.Reading(c, run, out, trace, peak)


@pytest.mark.parametrize("m", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_nothing_to_read_gives_nothing(m):
    for cell in m["workloads"]:
        assert _reader(m["name"]).read(_reading(cell, {})) is None


def _decode_trace(kernel_ns, sums_ns):
    dev, host = "/device:TPU:0", "/host:CPU"
    ev = [tr.Event(host, "python", "bench.window", 0.0, 1e9),
          tr.Event(dev, tr.MODULE_LINE, "jit__step_fn", 0.0, 5e8),
          tr.Event(dev, tr.OP_LINE, "paged_flash_decode.9", 0.0, kernel_ns),
          tr.Event(dev, tr.MODULE_LINE, "jit__block_sums_fn", 6e8, 2e8),
          tr.Event(dev, tr.OP_LINE, "multiply_reduce_fusion", 6e8, sums_ns)]
    return tr.reduce(ev)


def test_decode_readers_on_a_made_up_trace():
    conf = core.Cell.load(ROOT, DECODE).config
    facts = {"steps": 10, "slots": 64, "ctx_total_mean": 300_000.0,
             "container": "sfp-m2e4", "window_s": 2.0}
    r = _reading(DECODE, facts, _decode_trace(4e8, 1e8))
    flops, byts = cost.paged_decode_call(conf, "sfp-m2e4", 64, 300_000.0)
    least, bound = cost.roofline_time(flops, byts, V5E)
    assert bound == "memory"
    roof = _reader("paged_decode_roofline").read(r)
    assert roof == pytest.approx(100 * 20 * least / 0.4)
    assert 0 < roof < 100
    assert _reader("integrity_ms_per_step").read(r) == pytest.approx(10.0)
    mfu = _reader("decode_step_mfu").read(r)
    assert 0 < mfu < 100
    assert _reader("device_idle_share.decode").read(r) == pytest.approx(50.0)


def test_train_mfu_from_tokens_per_second():
    """Tokens of the traced window's steps over the window's length on the
    profiler's clock; nothing without a trace."""
    cell = "mamba2-370m.train.bf16"
    conf = core.Cell.load(ROOT, cell).config
    per_tok = cost.mamba2_train_flops_per_token(conf)
    facts = {"steps": 10, "tokens_per_step": 16384,
             "flops_per_token": per_tok}
    trace = tr.reduce([
        tr.Event("/host:CPU", "python", "bench.window", 0.0, 2e9),
        tr.Event("/device:TPU:0", tr.OP_LINE, "fusion.1", 0.0, 1.5e9)])
    r = _reading(cell, facts, trace)
    assert _reader("train_mfu").read(r) == pytest.approx(
        100 * 10 * 16384 / 2.0 * per_tok / 197e12)
    assert _reader("train_mfu").read(_reading(cell, facts)) is None
