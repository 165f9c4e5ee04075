"""The check that decides a training cell's ``correct``, driven through
the harness on the CPU at a tiny size (``bench_tiny``): a sound run passes
the cell's limits; the control (the reference computed in float8 in the
program's place) and each fault planted in the timed step fail them."""
import json
import time

import pytest

from bench_tiny import harness, tiny_name

BF16 = "mamba2-370m.train.bf16"
TRAFFIC = "train.bf16"


def _run(root, core, cell, **kw):
    return core.run_cell(root, tiny_name(cell), 11, 0.5, False,
                         time.perf_counter(), require_tpu=False, **kw)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    with harness(tmp_path_factory.mktemp("bench")) as h:
        yield h


def test_sound_run_passes_and_control_fails(bench):
    """One run: the program's own numbers pass the limits, and the control
    in the program's place makes ``correct`` false."""
    root, core = bench
    line = _run(root, core, BF16, control=True)
    limits = {k: c["limit"] for k, c in line["checks"].items()}
    assert all(line["numbers"][k] <= limits[k] for k in limits), \
        (line["numbers"], limits)
    assert not line["correct"], line["checks"]


def test_policy_the_reference_does_not_model_is_refused(bench, tmp_path):
    """A training mix whose policy the plain reference does not model (a
    precision policy's quantizers) is refused before anything runs."""
    root, core = bench
    path = root / "bench" / "traffic" / f"tiny.{TRAFFIC}.json"
    kept = path.read_text()
    t = json.loads(kept)
    path.write_text(json.dumps(dict(t, policy="qm+qe")))
    try:
        with pytest.raises(ValueError, match="reference models policies"):
            _run(root, core, BF16)
    finally:
        path.write_text(kept)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_fails(bench, fault):
    root, core = bench
    line = _run(root, core, BF16, fault=fault)
    assert not line["correct"], line["checks"]
