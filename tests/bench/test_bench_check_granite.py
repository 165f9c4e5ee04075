"""The check that decides the granite-4.0-h-small chat cell's ``correct``,
driven through the harness on the CPU at a tiny size (``bench_tiny``, its
tiny configuration from ``tests/conftest.py``): a sound run passes the
cell's limit; a token altered where the engine produces it fails it.

The float8 control is not asserted here: at the tiny width it moves a
pick only now and then, by 0.0007-0.019, so whether the requests the
check samples hold such a pick depends on which finished in the window;
at the cell's width it moves none (PERF.md, section 7)."""
import json
import time

import pytest

from bench_tiny import harness, tiny_name

CELL = "granite-4.0-h-small.serve.azure-chat-sfp8"


def _run(root, core, **kw):
    return core.run_cell(root, tiny_name(CELL), 2 ** 33 + 11, 4.0, False,
                         time.perf_counter(), require_tpu=False, **kw)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    with harness(tmp_path_factory.mktemp("bench")) as (root, core):
        # On the CPU the tiny hybrid's admission of 1024-2048 tokens takes
        # 0.4-1 s, so the tiny mixes' 4 requests a second would leave the
        # window without a token gap; 1 a second keeps it below capacity.
        path = root / "bench" / "traffic" / "tiny.serve.azure-chat-sfp8.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        rate=1.0)))
        # granite divides its logits by 16, so its gaps are 16 times
        # narrower than the tiny mistral's, for which the tiny limit of 1.0
        # was read. CPU readings: sound runs 0 on seeds 1, 2, 3 and
        # 2**31 + 11, at most 0.0003 on 2**33 + 11; a token fault
        # 0.067-0.088 on seeds 1 and 5.
        (root / "bench" / "cells" / f"{tiny_name(CELL)}.json").write_text(
            json.dumps({"limits": {"served_gap": 0.02}}))
        yield root, core


def test_granite_sound_run_passes(bench):
    root, core = bench
    line = _run(root, core)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"], line["checks"]


def test_granite_altered_token_fails(bench):
    root, core = bench
    line = _run(root, core, fault="token")
    assert not line["correct"], line["checks"]
    assert line["checks"]["served_gap"]["value"] > 0.02
