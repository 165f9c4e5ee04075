"""A copy of the benchmark at a size a CPU test can run.

``tiny_root(dest)`` copies ``bench/`` to ``dest`` and adds, beside the real
cells, one tiny cell per real cell: the same job and traffic file with
the program's tiny preset and short lengths.
The harness finds them by name, as it finds any cell. A tiny cell compares
the same numbers as its real cell, against limits of its own: the
readings at the tiny size differ from those at the cell's size.
"""
from __future__ import annotations

import contextlib
import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

TINY_CONFIGS = {
    "mamba2-370m": dict(program_args=["--arch", "mamba2-370m", "--preset",
                                      "tiny"],
                        hidden_size=128, num_hidden_layers=2, state_size=16,
                        head_dim=32, chunk_size=16, vocab_size=512),
    "mistral-large-123b": dict(program_args=["--arch", "mistral-large-123b",
                                             "--preset", "tiny", "--layers",
                                             "2"],
                               hidden_size=128, num_attention_heads=4,
                               num_key_value_heads=4, head_dim=32,
                               intermediate_size=384, vocab_size=512),
}
# Limits at the tiny size, from CPU readings on seeds 1-5 (sound run /
# float8 control / planted fault): loss 1.8e-5-2.8e-5 / 8.9e-5-6.4e-4;
# grad 0.0019-0.0039 / 0.031-0.038; update 0.0023-0.0039 / 0.010-0.018;
# grad_median 0.00043-0.00082 / 0.0027-0.0054; update_median
# 0.00011-0.00027 / 0.0017-0.0034. The serving cells keep contexts of
# 1024-2304 tokens, as the cells' own are long: the float8 control's
# attention weights (about 1/context) fall below float8's least value
# there, as they do at the cells' size, so the control separates. served_gap
# on seeds 1-6 (decode) and 1-4 and 2**33 + 11 (chat): sound 0-0.18 /
# control 1.94-3.87, sound 0-0.091 / control 1.27-2.87; with contexts of
# 32-96 tokens the control read 0.27-0.51 against sound 0.11-0.52 and did
# not separate.
TINY_LIMITS = {"loss": 6e-5, "grad": 0.012, "update": 0.007,
               "grad_median": 0.0015, "update_median": 0.0008,
               "served_gap": 1.0}
TINY_TRAFFIC = {
    "train": dict(batch=4, seq=64),
    "serve_closed": dict(max_slots=8, max_len=2560, sessions=4,
                         contexts=[2048, 2304], check_tokens=64),
    "serve_open": dict(max_slots=8, max_len=2560, rate=4.0,
                       prompt_buckets=[1024, 2048], prompt_median=1500,
                       out_median=8, out_min=2, out_max=32, ramp_s=3.0,
                       check_tokens=32, check_requests=3),
}


def _load(p: Path) -> dict:
    return json.loads(p.read_text())


def tiny_name(cell: str) -> str:
    return "tiny." + cell


def tiny_root(dest: Path) -> Path:
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = dest / "bench"
    m = _load(ROOT / "BENCHMARK.json")
    for c in list(m["configs"]):
        conf = _load(ROOT / c["file"])
        conf.update(TINY_CONFIGS[c["name"]], name="tiny." + c["name"])
        path = f"bench/configs/tiny.{c['name']}.json"
        (dest / path).write_text(json.dumps(conf))
        m["configs"].append(dict(c, name=conf["name"], file=path))
    for w in list(m["workloads"]):
        t = _load(bench / "traffic" / f"{w['traffic']}.json")
        t.update(TINY_TRAFFIC[t["job"]])
        (bench / "traffic" / f"tiny.{w['traffic']}.json").write_text(
            json.dumps(t))
        name = tiny_name(w["name"])
        limits = _load(bench / "cells" / f"{w['name']}.json")["limits"]
        (bench / "cells" / f"{name}.json").write_text(json.dumps(
            {"limits": {k: TINY_LIMITS[k] for k in limits}}))
        m["workloads"].append(dict(w, name=name, config="tiny." + w["config"],
                                   traffic="tiny." + w["traffic"]))
        for e in m["end_to_end"] + m["per_layer"]:
            if w["name"] in e.get("workloads", []):
                e["workloads"].append(name)
    (dest / "BENCHMARK.json").write_text(json.dumps(m))
    return dest


@contextlib.contextmanager
def harness(dest: Path):
    """(root of a tiny copy, ``benchlib.core``) with the persistent
    compilation cache left off, as the rest of the test process has it."""
    from benchlib import core
    mp = pytest.MonkeyPatch()
    mp.setattr(core, "_setup_jax", lambda root: None)
    try:
        yield tiny_root(dest), core
    finally:
        mp.undo()
