"""bench/run.py refuses to measure without a TPU and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run(root: Path, cell: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload", cell,
         "--seed", str(2 ** 33 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=root, timeout=300)


@pytest.mark.parametrize("cell", CELLS[:1])
def test_refuses_without_tpu(cell):
    r = _run(ROOT, cell)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert "{" not in r.stdout


def test_refuses_with_only_the_benchmark_files(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths
    has no program to measure."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, CELLS[0])
    assert r.returncode != 0
    assert "{" not in r.stdout
