"""The seeded generators: determinism, bucketed lengths, due times."""
import json
from pathlib import Path

import numpy as np

from benchlib import traffic

TRAFFIC = Path(__file__).resolve().parents[2] / "bench" / "traffic"


def _t(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _key(reqs):
    return [(q.uid, q.prompt.tolist(), q.max_new, q.due) for q in reqs]


def test_open_loop_is_deterministic_per_seed():
    t = _t("serve.chat-sfp8")
    a = traffic.open_loop(t, 32768, 20.0, 2 ** 31 + 7)
    b = traffic.open_loop(t, 32768, 20.0, 2 ** 31 + 7)
    c = traffic.open_loop(t, 32768, 20.0, 2 ** 31 + 8)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_open_loop_same_work_for_every_seed_in_another_order():
    t = _t("serve.chat-sfp8")
    a = traffic.open_loop(t, 32768, 30.0, 1)
    b = traffic.open_loop(t, 32768, 30.0, 2)
    assert len(a) == len(b) == round(t["rate"] * 30.0)
    assert sorted(len(q.prompt) for q in a) == sorted(len(q.prompt) for q in b)
    assert sorted(q.max_new for q in a) == sorted(q.max_new for q in b)
    assert [len(q.prompt) for q in a] != [len(q.prompt) for q in b]
    assert abs(a[-1].due - b[-1].due) < 1e-9  # same gaps, summed


def test_open_loop_lengths_are_bucketed_and_due_times_stamped():
    t = _t("serve.chat-sfp8")
    reqs = traffic.open_loop(t, 32768, 30.0, 5)
    lens = {len(q.prompt) for q in reqs}
    assert lens <= set(t["prompt_buckets"]) and len(lens) >= 3
    outs = [q.max_new for q in reqs]
    assert min(outs) >= t["out_min"] and max(outs) <= t["out_max"]
    due = np.asarray([q.due for q in reqs])
    assert np.all(np.diff(due) > 0) and due[0] > 0
    # the mean gap is the rate's
    assert abs(due[-1] / len(reqs) - 1 / t["rate"]) < 0.1 / t["rate"]
    med = np.median([len(q.prompt) for q in reqs])
    assert 256 <= med <= 512
    assert all(0 <= q.prompt.min() and q.prompt.max() < 32768 for q in reqs)


def test_closed_set_contexts_and_budgets():
    t = _t("serve.decode-m2e4")
    a = traffic.closed_set(t, 32768, t["max_len"], 3)
    b = traffic.closed_set(t, 32768, t["max_len"], 4)
    assert len(a) == t["sessions"]
    ctx = sorted(len(q.prompt) for q in a)
    assert ctx == sorted(len(q.prompt) for q in b)
    assert set(ctx) == set(t["contexts"]) and sum(ctx) == 260_096
    assert all(len(q.prompt) + q.max_new == t["max_len"] - 1 for q in a)
    assert min(q.max_new for q in a) >= 8191
    assert _key(a) == _key(traffic.closed_set(t, 32768, t["max_len"], 3))


def test_train_batches_deterministic_and_rows_differ():
    import jax
    t = _t("train.bf16")
    make = traffic.train_batch_fn(t, 4, 64, 512)
    key = jax.random.PRNGKey(0)
    a, b, c = make(key, 0), make(key, 0), make(key, 1)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    tok, lab = np.asarray(a["tokens"]), np.asarray(a["labels"])
    assert tok.shape == lab.shape == (4, 64)
    assert np.array_equal(tok[:, 1:], lab[:, :-1])
    assert tok.min() >= 0 and tok.max() < 512
    assert len({r.tobytes() for r in tok}) == 4


def test_percentile_nearest_rank():
    assert traffic.percentile(list(range(1, 101)), 95) == 95
    assert traffic.percentile([3.0], 95) == 3.0
    assert traffic.percentile([], 95) is None
    assert traffic.percentile([1.0, float("inf")], 95) == float("inf")
