"""The check that decides a serving cell's ``correct``, driven through the
harness on the CPU at a tiny size (``bench_tiny``, with contexts of
1024-2304 tokens): a sound run passes the cell's limit; the control (the
reference computed in float8, its picks in the served tokens' place)
fails it; a token altered where the engine produces it fails it."""
import time

import pytest

from bench_tiny import harness, tiny_name

DECODE = "mistral-large-123b.serve.decode-m2e4"
CHAT = "mistral-large-123b.serve.chat-sfp8"


def _run(root, core, cell, **kw):
    return core.run_cell(root, tiny_name(cell), 2 ** 33 + 11, 1.0, False,
                         time.perf_counter(), require_tpu=False, **kw)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    with harness(tmp_path_factory.mktemp("bench")) as h:
        yield h


@pytest.mark.parametrize("cell", [DECODE, CHAT])
def test_sound_run_passes_and_control_is_read(bench, cell):
    """One run: the program's own reading passes the limit, and the same
    comparison with the control in the program's place makes ``correct``
    false."""
    root, core = bench
    line = _run(root, core, cell, control=True)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line["checks"]) == ["served_gap"]
    limit = line["checks"]["served_gap"]["limit"]
    assert line["numbers"]["served_gap"] <= limit, line["numbers"]
    assert not line["correct"], line["checks"]
    assert line["checks"]["served_gap"]["value"] > limit


@pytest.mark.parametrize("cell", [DECODE, CHAT])
def test_altered_token_fails(bench, cell):
    root, core = bench
    line = _run(root, core, cell, fault="token")
    assert not line["correct"], line["checks"]
