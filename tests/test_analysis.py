"""The static checker itself: every seeded fixture violation fires on
exactly its marked line, the real tree is clean, the jaxpr contracts
hold on the live entry points, and the launchers reject bad names at
argparse time with the registry's did-you-mean."""
import json
import pathlib

import numpy as np
import pytest

from repro.analysis import astlint, contracts, names, vmem
from repro.analysis.findings import Finding, load_baseline, split_by_baseline
from repro.analysis.runner import REPO_ROOT, main

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "analysis"
VIOLATIONS = sorted(FIXTURES.glob("viol_*.py"))


def _markers(path: pathlib.Path):
    """{(line, rule)} promised by the fixture's ``# LINT: rule`` markers."""
    out = set()
    for i, line in enumerate(path.read_text().splitlines(), 1):
        if "# LINT:" in line:
            out.add((i, line.split("# LINT:")[1].strip()))
    return out


def _rel(path: pathlib.Path) -> str:
    return path.resolve().relative_to(REPO_ROOT).as_posix()


# ---------------------------------------------------------------------------
# layer 1: AST lints
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixture", VIOLATIONS, ids=lambda p: p.stem)
def test_seeded_violations_fire_exactly(fixture):
    assert _markers(fixture), f"{fixture.name} has no # LINT markers"
    found = astlint.lint_source(fixture.read_text(), _rel(fixture))
    assert {(f.line, f.rule) for f in found} == _markers(fixture)


def test_every_rule_has_a_fixture():
    covered = {rule for fx in VIOLATIONS for _, rule in _markers(fx)}
    assert covered == {"host-sync-in-jit", "stale-interpret-flag",
                       "force-backend-leak", "traced-truthiness",
                       "container-name", "policy-name", "float64",
                       "obs-no-hot-path-sync"}


def test_clean_fixture_is_clean():
    fx = FIXTURES / "clean_ok.py"
    assert astlint.lint_source(fx.read_text(), _rel(fx)) == []


def test_real_tree_is_lint_clean():
    assert astlint.run_lints([REPO_ROOT / "src" / "repro"], REPO_ROOT) == []


def test_did_you_mean():
    assert "did you mean 'sfp8'" in names.check_container("spf8")
    assert names.check_container("sfp-m2e4") is None
    assert "did you mean 'qm'" in names.check_policy("qm+qx")
    assert "duplicate" in names.check_policy("qm+qm")
    assert names.check_policy("qm+qe") is None


# ---------------------------------------------------------------------------
# findings / baseline mechanics
# ---------------------------------------------------------------------------


def test_waiver_key_ignores_line_numbers(tmp_path):
    f = Finding(rule="r", path="p.py", line=12, scope="fn", message="m")
    g = Finding(rule="r", path="p.py", line=99, scope="fn", message="m")
    assert f.key == g.key
    base = tmp_path / "b.json"
    base.write_text(json.dumps({"waivers": [
        {"key": f.key, "reason": "known host boundary"},
        {"key": "r:gone.py:old", "reason": "stale entry"}]}))
    waivers = load_baseline(base)
    active, waived, stale = split_by_baseline([f, g], waivers)
    assert active == [] and len(waived) == 2
    assert stale == ["r:gone.py:old"]


def test_waiver_without_reason_rejected(tmp_path):
    base = tmp_path / "b.json"
    base.write_text(json.dumps({"waivers": [{"key": "r:p.py:fn"}]}))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(base)


def test_cli_exit_codes(tmp_path):
    clean = FIXTURES / "clean_ok.py"
    bad = FIXTURES / "viol_force_backend.py"
    assert main(["--no-contracts", "--paths", str(clean)]) == 0
    assert main(["--no-contracts", "--paths", str(bad)]) == 1
    # A justified waiver turns the failure into a pass.
    key = f"force-backend-leak:{_rel(bad)}:setup_model"
    base = tmp_path / "waive.json"
    base.write_text(json.dumps({"waivers": [
        {"key": key, "reason": "fixture exercises the rule"}]}))
    assert main(["--no-contracts", "--paths", str(bad),
                 "--baseline", str(base)]) == 0


@pytest.mark.parametrize("fixture", VIOLATIONS, ids=lambda p: p.stem)
def test_cli_nonzero_on_each_fixture(fixture):
    assert main(["--no-contracts", "--paths", str(fixture)]) == 1


# ---------------------------------------------------------------------------
# layer 2: jaxpr contracts on the real entry points
# ---------------------------------------------------------------------------


def test_precision_leak_quick_geometries():
    assert contracts.check_precision_leak(contracts.QUICK_GEOMETRIES) == []


def test_buffer_geometry_quick_geometries():
    import dataclasses

    from repro import configs
    from repro.configs.base import reduced
    cfg = dataclasses.replace(reduced(configs.get("mistral-large-123b")),
                              dtype="float32")
    assert contracts.check_buffer_geometry(contracts.QUICK_GEOMETRIES,
                                           cfg) == []


@pytest.mark.slow
def test_donation_audit():
    assert contracts.check_donation(include_train=True) == []


@pytest.mark.slow
def test_recompile_guard():
    assert contracts.check_recompile() == []


@pytest.mark.slow
def test_recompile_guard_burst_memo_across_k():
    _, _, _, eng = contracts._tiny_serving("sfp8")
    S = eng.max_slots
    toks, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
    for k in (2, 3, 2, 3):
        eng.decode_burst(toks, pos, k)
    assert set(eng._bursts) == {2, 3}
    for k, fn in eng._bursts.items():
        assert fn._cache_size() == 1, f"K={k} burst re-traced"


@pytest.mark.slow
def test_recompile_guard_scheduler_burst_path():
    from repro.serve.scheduler import Request, Scheduler
    cfg, _, _, eng = contracts._tiny_serving("sfp8")
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i,
                    prompt=rng.randint(0, cfg.vocab, size=6).astype(np.int32),
                    max_new=5) for i in range(3)]
    sched = Scheduler(eng)
    out = sched.run(reqs, burst=2)
    assert len(out) == 3
    # The whole trace — admissions, bursts, retirements — holds exactly
    # one K=2 burst executable and one decode-step executable.
    assert set(eng._bursts) <= {2}
    for fn in eng._bursts.values():
        assert fn._cache_size() == 1
    assert eng._step._cache_size() in (0, 1)


def test_vmem_quick_geometries():
    assert vmem.check_vmem(contracts.QUICK_GEOMETRIES) == []


# ---------------------------------------------------------------------------
# launcher argparse validation (same registry parsers)
# ---------------------------------------------------------------------------


def test_serve_parser_rejects_bad_container(capsys):
    from repro.launch import serve
    ap = serve.build_parser()
    with pytest.raises(SystemExit):
        ap.parse_args(["--arch", "gemma2-2b", "--kv-container", "spf8"])
    assert "did you mean 'sfp8'" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        ap.parse_args(["--arch", "gemma2-2b", "--kv-container", "sfp8",
                       "--degraded-container", "gecko9"])
    args = ap.parse_args(["--arch", "gemma2-2b", "--kv-container", "sfp8",
                          "--degraded-container", "sfp-m1e2"])
    assert args.kv_container == "sfp8"


def test_train_parser_rejects_bad_names(capsys):
    from repro.launch import train
    ap = train.build_parser()
    with pytest.raises(SystemExit):
        ap.parse_args(["--arch", "gemma2-2b", "--policy", "qm+qx"])
    assert "did you mean 'qm'" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        ap.parse_args(["--arch", "gemma2-2b", "--container", "spf8"])
    args = ap.parse_args(["--arch", "gemma2-2b", "--policy", "qm+qe",
                          "--container", "sfp-m2e4"])
    assert args.policy == "qm+qe" and args.container == "sfp-m2e4"


def test_launchers_layers_cut_depth_only():
    """--layers keeps every published width and cuts whole periods."""
    import dataclasses

    from repro import configs
    from repro.configs.base import depth_cut
    from repro.launch import train
    full = configs.get("mistral-large-123b")
    args = train.build_parser().parse_args(
        ["--arch", full.name, "--preset", "full", "--layers", "2",
         "--policy", "none"])
    cfg = train.build(args)[0]
    assert cfg.n_layers == 2
    assert dataclasses.replace(cfg, n_layers=full.n_layers) == full
    gemma3 = configs.get("gemma3-12b")  # period of 6 layer kinds
    assert depth_cut(gemma3, 6).period == gemma3.period
    for bad in (0, 5, gemma3.n_layers + 6):
        with pytest.raises(ValueError):
            depth_cut(gemma3, bad)


def test_compile_cache_dir(monkeypatch):
    """JAX's own variable wins; otherwise a fixed repo-root directory."""
    import jax

    from repro.launch import cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cache.enable_compile_cache() == str(cache.REPO_CACHE_DIR)
        assert cache.REPO_CACHE_DIR.parent == pathlib.Path(__file__).parents[1]
        assert (jax.config.jax_compilation_cache_dir
                == str(cache.REPO_CACHE_DIR))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
