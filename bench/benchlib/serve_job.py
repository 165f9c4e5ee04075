"""Serving cells: the program's ``Scheduler.step`` over its ``PagedEngine``.

Two traffic shapes, both read from the traffic file:

* ``serve_closed``: a fixed set of long sessions, all admitted in set-up
  (their prefills fill the packed pool), then decoded through the window.
  No session can finish inside a window.
* ``serve_open``: open-loop arrivals at a fixed rate. Set-up compiles
  every prompt length the mix uses and decode, then runs the arrivals for
  a ramp before the window opens. Each request is timed from when it was
  due, not from when it was submitted.

The benchmark's ``on_token`` callback stamps every token. After the
window, a seeded sample of the served requests, the longest among them,
is run through the plain reference (the pool freed first), and the check
is the widest gap by which a served token's reference logit lies below
the reference's best. With ``control=True`` the control's picks (the
reference in float8, at each position of the same prompts and served
tokens) take the served tokens' place in that comparison.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from benchlib import traffic, weights
from benchlib.core import Outcome, Run

SIZE_KEYS = {"d_model": "hidden_size", "n_layers": "num_hidden_layers",
             "n_heads": "num_attention_heads",
             "n_kv_heads": "num_key_value_heads", "head_dim_": "head_dim",
             "d_ff": "intermediate_size", "vocab": "vocab_size",
             "rope_theta": "rope_theta"}
REGISTRY_SUMS = ("serve_prefill_seconds", "serve_decode_seconds",
                 "serve_step_seconds", "serve_verify_seconds")


def program_args(conf: dict, t: dict) -> list:
    return conf["program_args"] + [
        "--kv-container", t["kv_container"], "--max-slots",
        str(t["max_slots"]), "--max-len", str(t["max_len"]),
        "--burst", str(t["burst"])]


@dataclasses.dataclass
class Served:
    """Tokens and their host times, per request, from ``on_token``."""

    tokens: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    times: Dict[int, List[float]] = dataclasses.field(default_factory=dict)

    def __call__(self, uid, tok, done) -> None:
        self.tokens.setdefault(uid, []).append(int(tok))
        self.times.setdefault(uid, []).append(time.perf_counter())

    def count_between(self, lo: float, hi: float) -> int:
        return sum(int(np.sum((np.asarray(ts) >= lo) & (np.asarray(ts) < hi)))
                   for ts in self.times.values())

    def gaps_ending_between(self, lo: float, hi: float) -> List[float]:
        out: List[float] = []
        for ts in self.times.values():
            a = np.asarray(ts)
            d = np.diff(a)
            out.extend(d[(a[1:] >= lo) & (a[1:] < hi)].tolist())
        return out


def build(run: Run, served: Served, fault=None):
    """(scheduler, engine, params) with weights from the seed.

    ``fault="token"`` (tests and readings of the check only) alters every
    slot's token where the engine produces it, in one decode call of
    every eight (so every request of 16 tokens or more gets one)."""
    from repro.launch import serve as launch
    from repro.serve import engine as engine_mod
    from repro.serve.scheduler import Scheduler

    conf, t = run.cell.config, run.cell.traffic
    args = launch.build_parser().parse_args(program_args(conf, t))
    cfg, model, _, container = launch.build_model(args, params={})
    for attr, key in SIZE_KEYS.items():
        if getattr(cfg, attr) != conf[key]:
            raise ValueError(f"program {attr}={getattr(cfg, attr)} but the "
                             f"configuration file says {key}={conf[key]}")
    params = weights.generator(model.param_shapes())(
        weights.seed_key(run.seed))
    eng = engine_mod.PagedEngine(model, params, max_slots=t["max_slots"],
                                 max_len=t["max_len"],
                                 integrity=t["integrity"])
    if fault == "token":
        decode, calls = eng.decode, [0]

        def altered(toks, pos):
            nxt, bad = decode(toks, pos)
            calls[0] += 1
            if calls[0] % 8 == 2:
                nxt = (nxt + 1) % cfg.vocab
            return nxt, bad

        eng.decode = altered
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    sched = Scheduler(eng, on_token=served)
    return sched, eng, params


def _request(req, arrival: float):
    from repro.serve.scheduler import Request
    return Request(uid=req.uid, prompt=req.prompt, max_new=req.max_new,
                   arrival=arrival)


def _registry_sums(sched) -> Dict[str, List[float]]:
    snap = sched.obs.registry.snapshot()
    out = {}
    for name in REGISTRY_SUMS:
        series = snap.get(name, {}).get("series", [])
        out[name] = [sum(s["sum"] for s in series),
                     sum(s["count"] for s in series)]
    return out


def _delta(a: dict, b: dict) -> dict:
    return {k: [b[k][0] - a[k][0], b[k][1] - a[k][1]] for k in a}


def _check(run: Run, params, reqs, served: Served, candidates,
           control: bool = False) -> dict:
    """Widest gap of a served token's reference logit below the best, over
    a seeded sample of ``candidates`` (uids) that includes the longest;
    with ``control``, of the control's pick at each position instead.
    ``numbers`` keeps the served tokens' gap either way."""
    import jax

    t, conf = run.cell.traffic, run.cell.config
    ref = run.cell.reference()
    by_uid = {q.uid: q for q in reqs}
    cands = sorted(candidates)
    total = {u: len(by_uid[u].prompt) + len(served.tokens[u]) for u in cands}
    longest = max(cands, key=lambda u: total[u])
    rng = traffic.rng_for(run.seed, 3)
    rest = [u for u in cands if u != longest]
    k = min(len(rest), t["check_requests"] - 1)
    sample = [longest] + [rest[i] for i in
                          sorted(rng.choice(len(rest), k, replace=False))]
    widest, widest_ctl, n = 0.0, 0.0, 0
    for u in sample:
        prompt = np.asarray(by_uid[u].prompt)
        toks = np.asarray(served.tokens[u][:t["check_tokens"]])
        seq = np.concatenate([prompt, toks[:-1]])
        rows = np.arange(len(prompt) - 1, len(seq))
        lg = np.asarray(jax.device_get(ref.logits_at(params, seq, rows,
                                                     conf)), np.float64)
        best = lg.max(axis=1)
        at = np.arange(len(rows))
        widest = max(widest, float(np.max(best - lg[at, toks])))
        if control:
            lc = np.asarray(jax.device_get(ref.logits_at(
                params, seq, rows, conf, prec="fp8")))
            pick = lc.argmax(axis=1)
            widest_ctl = max(widest_ctl, float(np.max(best - lg[at, pick])))
        n += len(toks)
    return {"served_gap": widest_ctl if control else widest,
            "numbers": {"served_gap": widest}, "compared_tokens": n,
            "compared_requests": len(sample)}


def _free(sched, eng) -> None:
    eng.mem = None
    sched.engine = None
    gc.collect()


def run_closed(r: Run, control: bool = False, fault=None) -> Outcome:
    t, conf = r.cell.traffic, r.cell.config
    served = Served()
    sched, eng, params = build(r, served, fault)
    reqs = traffic.closed_set(t, conf["vocab_size"], t["max_len"], r.seed)
    with r.span("admit_all"):
        for q in reqs:
            sched.submit(_request(q, 0.0))
        sched.step()
    if sched.pending or len(sched.running) != len(reqs):
        raise RuntimeError(f"set-up admitted {len(sched.running)} of "
                           f"{len(reqs)} sessions")
    for _ in range(t["warm_steps"]):
        with r.span("scheduler_step"):
            sched.step()
    ctx0 = sum(st.n_ctx for st in sched.running.values())
    reg0 = _registry_sums(sched)
    steps = 0
    with r.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < r.seconds:
            with r.span("scheduler_step"):
                sched.step()
            steps += 1
        t1 = time.perf_counter()
    ctx1 = sum(st.n_ctx for st in sched.running.values())
    reg = _delta(reg0, _registry_sums(sched))
    done = {u: res.status for u, res in sched.results.items()}
    tokens = served.count_between(t0, t1)
    gaps = served.gaps_ending_between(t0, t1)
    failed = sum(1 for q in reqs
                 if done.get(q.uid, "ok") != "ok"
                 or not any(t0 <= x < t1 for x in served.times[q.uid]))
    slots = len(reqs)
    _free(sched, eng)
    chk = _check(r, params, reqs, served, [q.uid for q in reqs], control)
    lim = r.cell.limits
    facts = {"steps": steps, "slots": slots,
             "ctx_total_mean": (ctx0 + ctx1) / 2,
             "container": t["kv_container"], "registry": reg, **chk}
    e2e = {"serve_tokens_per_s": tokens / r.window_s,
           "itl_p95_ms": 1e3 * traffic.percentile(gaps, 95)}
    return Outcome(end_to_end=e2e, attempted=len(reqs), failed=failed,
                   checks={"served_gap": (chk["served_gap"],
                                          lim["served_gap"])},
                   facts=facts)


def _warm_lengths(sched, t: dict, vocab: int, served: Served) -> None:
    """Compile prefill at every prompt length of the mix, and decode."""
    rng = traffic.rng_for(0, 9)
    for i, n in enumerate(t["prompt_buckets"]):
        sched.submit(_request(traffic.Request(
            uid=-1 - i, prompt=rng.integers(0, vocab, n).astype(np.int32),
            max_new=2), 0.0))
    while not sched.idle:
        sched.step()
    for u in list(served.tokens):
        if u < 0:
            served.tokens.pop(u)
            served.times.pop(u)


def run_open(r: Run, control: bool = False, fault=None) -> Outcome:
    t, conf = r.cell.traffic, r.cell.config
    served = Served()
    sched, eng, params = build(r, served, fault)
    with r.span("warm"):
        _warm_lengths(sched, t, conf["vocab_size"], served)
    ramp = t["ramp_s"]
    reqs = traffic.open_loop(t, conf["vocab_size"], ramp + r.seconds, r.seed)
    due = np.asarray([q.due for q in reqs])
    nxt = 0
    late: List[float] = []
    start = time.perf_counter()

    def pump(until: float) -> None:
        nonlocal nxt
        while True:
            now = time.perf_counter() - start
            if now >= until:
                return
            with r.span("submit"):
                while nxt < len(reqs) and due[nxt] <= now:
                    sched.submit(_request(reqs[nxt], float(due[nxt])))
                    late.append(now - due[nxt])
                    nxt += 1
            if sched.idle:
                wake = due[nxt] if nxt < len(reqs) else until
                time.sleep(max(0.0, min(wake, until) - now))
                continue
            with r.span("scheduler_step"):
                sched.step(now=now)

    pump(ramp)
    reg0 = _registry_sums(sched)
    with r.window():
        w0 = time.perf_counter()
        pump(ramp + r.seconds)
        w1 = time.perf_counter()
    backlog = len(sched.pending)
    reg = _delta(reg0, _registry_sums(sched))
    in_window = [q for q in reqs if ramp <= q.due < ramp + r.seconds]
    # requests that fell due while the window's last step ran are served
    # late, not dropped: their wait counts from when they were due
    now = time.perf_counter() - start
    while nxt < len(reqs) and due[nxt] < ramp + r.seconds:
        sched.submit(_request(reqs[nxt], float(due[nxt])))
        late.append(now - due[nxt])
        nxt += 1
    drain_end = time.perf_counter() + t["drain_s"]
    while (any(q.uid not in served.times for q in in_window)
           and not sched.idle and time.perf_counter() < drain_end):
        sched.step(now=time.perf_counter() - start)
    gave_up = time.perf_counter()
    ttft, failed = [], 0
    for q in in_window:
        res = sched.results.get(q.uid)
        first = served.times.get(q.uid, [None])[0]
        if first is None or (res is not None and res.status != "ok"):
            # missing counts as at least the whole wait until the drain ends
            failed += 1
            ttft.append(gave_up - (start + q.due))
        else:
            ttft.append(first - (start + q.due))
    gaps = served.gaps_ending_between(w0, w1)
    finished = [q.uid for q in reqs
                if (res := sched.results.get(q.uid)) is not None
                and res.status == "ok"]
    _free(sched, eng)
    chk = _check(r, params, reqs, served, finished, control)
    lim = r.cell.limits
    # how late the generator ran, and whether a queue was left: a starved
    # generator or a growing backlog is not read as a fast server. The
    # TTFT tail is a reading here, not a metric: a window holds too few
    # requests for its 95th percentile to hold a bound.
    load = {"submit_late_p95_s": traffic.percentile(late, 95),
            "backlog_at_close": backlog,
            "ttft_p50_ms": 1e3 * traffic.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * traffic.percentile(ttft, 95),
            "tokens_per_s": served.count_between(w0, w1) / r.window_s}
    facts = {"registry": reg, "load": load, **chk}
    e2e = {"itl_p95_ms": 1e3 * traffic.percentile(gaps, 95)}
    return Outcome(end_to_end=e2e, attempted=len(in_window), failed=failed,
                   checks={"served_gap": (chk["served_gap"],
                                          lim["served_gap"])},
                   facts=facts)
