"""chip_smoke.py refuses to run without a TPU, and its serve and train
phases run end to end on the CPU at a tiny size with the Pallas kernels in
interpret mode (the chip run itself happens only on a TPU host)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.fixture
def interpret_smoke(monkeypatch):
    """chip_smoke with 'pallas' mapped to interpret mode, and no
    tpu_custom_call text to count (the CPU compiles no TPU kernels)."""
    from repro.kernels import ops
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke
    force = ops.force_backend
    monkeypatch.setattr(ops, "force_backend", lambda name: force(
        "interpret" if name in (None, "pallas") else name))
    monkeypatch.setattr(chip_smoke, "_custom_calls", lambda compiled: 1)
    force("interpret")
    try:
        yield chip_smoke
    finally:
        force(None)


def test_phases_at_tiny_size(interpret_smoke, capsys):
    serve = ["--arch", "mistral-large-123b", "--preset", "tiny", "--trace",
             "--flood", "--requests", "3", "--max-slots", "3",
             "--max-len", "256", "--prompt-len-min", "8",
             "--prompt-len-max", "40", "--max-new-min", "3",
             "--max-new-max", "6", "--seed", "0"]
    train = ["--arch", "mamba2-370m", "--preset", "tiny", "--policy",
             "qm+qe", "--container", "sfp-m2e4", "--steps", "2"]
    interpret_smoke.serve_phase(serve)
    interpret_smoke.train_phase(train)
    out = capsys.readouterr().out
    assert out.count('"phase": "serve"') == 2
    assert out.count('"phase": "train_step"') == 2
    assert '"phase": "train"' in out
