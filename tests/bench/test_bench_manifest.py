"""BENCHMARK.json and the files it names: the contract's shapes and
limits, and discovery of cells and metrics by name."""
import json
import re
import shutil
from pathlib import Path

import pytest

from benchlib import core

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
LINE = re.compile(r"[^\t\n\r]{1,200}")
METRIC_KEYS = {"name", "unit", "better", "source", "workloads"}


def test_top_level_keys_and_run_seconds():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= len(MANIFEST["command"]) <= 32
    for p in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines():
    entries = (MANIFEST["configs"] + MANIFEST["workloads"]
               + MANIFEST["end_to_end"] + MANIFEST["per_layer"])
    for e in entries:
        assert NAME.fullmatch(e["name"]), e["name"]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[kind]]
        assert len(names) == len(set(names)), kind
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert LINE.fullmatch(c["why"]) and LINE.fullmatch(c["source"])
        assert all(NAME.fullmatch(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and LINE.fullmatch(w["why"])
        assert NAME.fullmatch(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in names
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= METRIC_KEYS | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = next(m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup


@pytest.mark.parametrize("w", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_reports_metrics_and_files_exist(w):
    cell = core.Cell.load(ROOT, w)
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer(), w
    assert cell.traffic["job"] in core.JOBS
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    ref = cell.traffic.get("reference", cell.config["reference"])
    assert (cell.bench / "configs" / ref).is_file()
    for m in cell.per_layer():
        assert m["moves"] in e2e, (w, m["name"])


def test_per_layer_metrics_have_readers_and_move_reported_metrics():
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    layers = {}
    for m in MANIFEST["per_layer"]:
        assert set(m) <= METRIC_KEYS | {"layer", "moves"}
        assert LINE.fullmatch(m["layer"])
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
        mod = core.load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py",
                               "m_" + m["name"].replace(".", "_"))
        assert callable(mod.read) and mod.__doc__
        layers.setdefault(m["layer"], []).append(m["name"])
    assert {"device", "kernels"} <= set(layers)


def test_configs_files_hold_the_sizes_they_declare():
    for c in MANIFEST["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert c["file"].startswith(tuple(MANIFEST["paths"]))


@pytest.mark.parametrize("c", MANIFEST["configs"], ids=lambda c: c["name"])
def test_departures_are_stated_apart_from_cuts(c):
    """A key the program runs otherwise than its source states (and no cut
    of depth) is a departure: the file holds the value run, the published
    one and why; it is not listed as reduced."""
    conf = json.loads((ROOT / c["file"]).read_text())
    for key, d in conf.get("departures", {}).items():
        assert key not in c["reduced"], key
        assert conf[key] == d["runs"] != d["published"], key
        assert d["why"]


def test_a_mix_can_name_its_own_reference(tmp_path):
    """A later mix whose job needs more of the reference (a policy's
    quantizers) names a reference file of its own; the configuration's
    stays as it is."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = json.loads(json.dumps(MANIFEST))
    base = next(w for w in m["workloads"] if "train" in w["traffic"])
    t = json.loads((ROOT / "bench" / "traffic" / f"{base['traffic']}.json")
                   .read_text())
    (tmp_path / "bench" / "traffic" / "new.mix.json").write_text(json.dumps(
        dict(t, policy="new", reference="new.ref.py")))
    (tmp_path / "bench" / "configs" / "new.ref.py").write_text(
        'POLICIES = ("new",)\n')
    new = f"{base['config']}.new.mix"
    (tmp_path / "bench" / "cells" / f"{new}.json").write_text(
        json.dumps({"limits": {"x": 1.0}}))
    m["workloads"].append(dict(base, name=new, traffic="new.mix"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    assert core.Cell.load(tmp_path, new).reference().POLICIES == ("new",)
    assert core.Cell.load(tmp_path, base["name"]).reference().POLICIES == (
        "none",)


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    """A later change adds a traffic file, a cell file and a metric reader
    and lists them in the manifest; no existing file under bench/ changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    m = json.loads(json.dumps(MANIFEST))
    base = m["workloads"][0]
    t = json.loads((ROOT / "bench" / "traffic" / f"{base['traffic']}.json")
                   .read_text())
    (tmp_path / "bench" / "traffic" / "new.mix.json").write_text(
        json.dumps(dict(t, why="a new mix")))
    new = f"{base['config']}.new.mix"
    (tmp_path / "bench" / "cells" / f"{new}.json").write_text(
        json.dumps({"limits": {"x": 1.0}}))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        '"""A new reader."""\n\n\ndef read(r):\n    return 42.0\n')
    m["workloads"].append(dict(base, name=new, traffic="new.mix"))
    moves = m["end_to_end"][0]
    moves.setdefault("workloads", []).append(new)
    m["per_layer"].append({"name": "new_metric", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "device", "moves": moves["name"],
                           "workloads": [new]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    cell = core.Cell.load(tmp_path, new)
    assert cell.traffic["why"] == "a new mix"
    assert "new_metric" in [x["name"] for x in cell.per_layer()]
    mod = core.load_module(tmp_path / "bench" / "metrics" / "new_metric.py",
                           "m_new")
    assert mod.read(None) == 42.0
    after = {p: p.read_bytes() for p in before}
    assert after == before
