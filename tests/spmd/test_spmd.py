"""SPMD tests run in a subprocess (needs 8 host devices; the main test
process must keep the default single-device view for everything else)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

WORKER = Path(__file__).parent / "worker.py"


def _run(name, timeout=420):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # The child must never reach for an accelerator its parent may hold.
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(WORKER), name],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert f"PASS {name}" in r.stdout


@pytest.mark.parametrize("name", ["sharded_embed", "pipeline",
                                  "grad_compress", "elastic"])
def test_spmd_fast(name):
    _run(name)


@pytest.mark.slow
def test_spmd_sharded_train_step_matches_single_device():
    _run("sharded_vs_single", timeout=560)
