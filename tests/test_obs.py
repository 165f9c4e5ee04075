"""Unified telemetry (repro.obs): registry/tracer/timeline units, the
exporter validators, and the accounting-consistency property — across
chaos scenarios the metrics registry, the ``SchedulerStats`` compat
view, and ``Scheduler.results`` must agree (ok + expired + cancelled +
shed + failed == submitted), instrumentation must add zero executables,
and one fully instrumented flood must yield a Perfetto-loadable span
chain with a downshift annotation plus a precision timeline whose
per-geometry bytes sum exactly to the pool's accounting."""
import dataclasses
import json
import math
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, policies
from repro import obs as obs_mod
from repro.configs.base import reduced
from repro.kernels import ops
from repro.models.model import DecoderModel
from repro.obs import validate as validate_mod
from repro.obs.registry import EventLog, MetricsRegistry, log_buckets
from repro.obs.timeline import PrecisionTimeline
from repro.obs.trace import SpanTracer
from repro.optim import adamw
from repro.serve import engine, faults, precision
from repro.serve.scheduler import Request, Scheduler, SchedulerStats
from repro.train import loop as loop_mod
from repro.train.state import TrainState

SCHEMAS = pathlib.Path(__file__).parent / "fixtures" / "obs"


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_labels_totals_and_errors():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "terminal outcomes", labels=("outcome",))
    c.labels(outcome="ok").inc()
    c.labels(outcome="ok").inc()
    c.labels(outcome="shed").inc()
    assert c.total() == 3
    assert c.total(outcome="ok") == 2 and c.total(outcome="shed") == 1
    with pytest.raises(KeyError):
        c.labels(bad="x")
    with pytest.raises(KeyError):
        c.inc()  # labeled family has no solo series
    with pytest.raises(ValueError):
        c.labels(outcome="ok").inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("reqs_total")  # kind mismatch on an existing name
    # get-or-create is idempotent: same family object by name
    assert reg.counter("reqs_total", labels=("outcome",)) is c


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("pool_free_blocks", "free blocks")
    g.set(7)
    g.dec(3)
    g.inc()
    assert g.value == 5


def test_log_buckets_span_and_monotonicity():
    b = log_buckets(1e-5, 100.0, per_decade=4)
    assert len(b) == 29
    assert math.isclose(b[0], 1e-5) and math.isclose(b[-1], 100.0)
    assert all(x < y for x, y in zip(b, b[1:]))


def test_histogram_percentiles_count_and_overflow():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", unit="s")
    for v in [0.0012] * 50 + [0.012] * 45 + [1.2] * 5:
        h.observe(v)
    assert reg.snapshot()["lat_seconds"]["series"][0]["count"] == 100
    # p50 lands in 0.0012's bucket (bounded above by the next log bound)
    assert 0.0012 <= h.percentile(0.50) <= 0.002
    assert 0.012 <= h.percentile(0.95) <= 0.02
    # p99 bucket bound exceeds the observed max, so the max wins
    assert h.percentile(0.99) == 1.2
    h.observe(1e6)  # overflow slot: above every bound
    assert h.percentile(1.0) == 1e6
    # 101 samples: the median is now the 51st value (a 0.012 sample)
    assert 0.012 <= h.percentile(0.5) <= 0.02


def test_prometheus_export_round_trips_the_validator(tmp_path):
    reg = MetricsRegistry()
    reg.counter("serve_requests_total", "outcomes",
                labels=("outcome",)).labels(outcome="ok").inc(3)
    reg.gauge("pool_used_blocks", "used").set(2)
    reg.histogram("serve_ttft_seconds", "ttft", unit="s").observe(0.05)
    reg.histogram("serve_itl_seconds", "itl", unit="s").observe(0.002)
    text = reg.to_prometheus()
    assert "# TYPE serve_requests_total counter" in text
    assert 'serve_requests_total{outcome="ok"} 3' in text
    assert '_bucket{le="+Inf"} 1' in text
    p = tmp_path / "metrics.prom"
    p.write_text(text)
    assert validate_mod.validate_prometheus(
        str(p), require=("serve_ttft_seconds", "serve_itl_seconds")) == []
    # a histogram that was never registered is a hard failure
    errs = validate_mod.validate_prometheus(str(p),
                                            require=("serve_step_seconds",))
    assert errs and "missing histogram" in errs[0]


def test_event_log_streams_jsonl(tmp_path):
    p = tmp_path / "events.jsonl"
    log = EventLog(str(p))
    log.emit("step_failure", step=7, restart=1)
    log.write({"step": 7, "loss": 1.5})  # verbatim metric-line mode
    log.close()
    lines = [json.loads(x) for x in p.read_text().splitlines()]
    assert lines[0]["event"] == "step_failure" and "ts" in lines[0]
    assert lines[1] == {"step": 7, "loss": 1.5}  # byte-stable: no stamps
    assert validate_mod.validate_jsonl(
        str(p), json.loads((SCHEMAS / "events.schema.json").read_text())) \
        == []


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_tracer_spans_lanes_and_export():
    tr = SpanTracer()
    span = tr.begin("queued", "7", requeued=False)
    tr.end(span, outcome="ok")
    tr.complete("decode", "7", 0.004, burst=2)
    tr.instant("retire", "7", outcome="ok")
    tr.instant("submit", "8")
    out = tr.export()
    assert set(out) == {"traceEvents", "displayTimeUnit"}
    # one thread_name metadata event per lane
    meta = [e for e in out["traceEvents"] if e["ph"] == "M"]
    assert [m["args"]["name"] for m in meta] == ["7", "8"]
    assert tr.lanes() == ["7", "8"]
    qs = tr.spans(lane="7", name="queued")
    assert len(qs) == 1 and qs[0]["dur"] >= 0
    assert qs[0]["args"] == {"requeued": False, "outcome": "ok"}
    dec = tr.spans(name="decode")[0]
    assert math.isclose(dec["dur"], 4000.0)  # 0.004 s in us
    assert tr.spans(lane="8") and tr.spans(lane="8")[0]["ph"] == "i"


def test_tracer_output_passes_trace_schema(tmp_path):
    tr = SpanTracer()
    tr.complete("prefill", "0", 0.001, geometry="sfp8", downshift=False)
    p = tmp_path / "trace.json"
    tr.write(str(p))
    schema = json.loads((SCHEMAS / "trace.schema.json").read_text())
    assert validate_mod.validate(json.loads(p.read_text()), schema) == []


def test_trace_chain_checker_requires_full_chain():
    tr = SpanTracer()
    tr.instant("submit", "0")
    tr.complete("queued", "0", 0.001)
    tr.complete("prefill", "0", 0.001, downshift=False)
    # no decode span, no retire yet: chain incomplete
    assert validate_mod.check_trace_chain(tr.export())
    tr.complete("decode", "0", 0.001)
    tr.instant("retire", "0", outcome="ok")
    assert validate_mod.check_trace_chain(tr.export()) == []
    # downshift demanded but never annotated
    assert validate_mod.check_trace_chain(tr.export(),
                                          require_downshift=True)


# ---------------------------------------------------------------------------
# precision timeline
# ---------------------------------------------------------------------------


def test_timeline_round_trips_schema_and_accounting(tmp_path):
    p = tmp_path / "timeline.jsonl"
    tl = PrecisionTimeline(str(p))
    tl.record_train(40, [(3, 5), (7, 8)])
    tl.record_serve(12, geometry_blocks={"sfp-m3e5": 6},
                    geometry_bytes={"sfp-m3e5": 98304}, used_bytes=98304,
                    free_bytes=32768, capacity_bytes=131072,
                    occupancy=0.75, pressure="degraded", quarantined=0,
                    running=2)
    tl.close()
    schema = json.loads((SCHEMAS / "timeline.schema.json").read_text())
    assert validate_mod.validate_jsonl(str(p), schema) == []
    assert validate_mod.check_timeline_accounting(str(p)) == []
    train, serve = [json.loads(x) for x in p.read_text().splitlines()]
    assert train["layers"][1] == {"layer": 1, "man_bits": 7, "exp_bits": 8}
    assert serve["pressure"] == "degraded"
    # seeded disagreement: bytes that do not sum to used_bytes must fail
    bad = tmp_path / "bad.jsonl"
    serve["geometry_bytes"] = {"sfp-m3e5": 1}
    bad.write_text(json.dumps(serve) + "\n")
    errs = validate_mod.check_timeline_accounting(str(bad))
    assert errs and "used_bytes" in errs[0]


def test_validate_cli_exit_codes(tmp_path):
    good = tmp_path / "tl.jsonl"
    PrecisionTimeline(str(good)).record_train(0, [(3, 5)])
    assert validate_mod.main(["--timeline", str(good),
                              "--schemas-dir", str(SCHEMAS)]) == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "train", "step": -1, "layers": []}\n')
    assert validate_mod.main(["--timeline", str(bad),
                              "--schemas-dir", str(SCHEMAS)]) == 1


# ---------------------------------------------------------------------------
# scheduler accounting consistency across chaos scenarios
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving():
    cfg = dataclasses.replace(reduced(configs.get("mistral-large-123b")),
                              dtype="float32")
    model = DecoderModel(cfg, kv_container="sfp8")
    params = model.init(jax.random.PRNGKey(0))
    ops.force_backend("ref")
    yield cfg, model, params
    ops.force_backend(None)


def _reqs(cfg, sizes, news, seed=0, **kw):
    rng = np.random.RandomState(seed)
    return [Request(uid=i,
                    prompt=rng.randint(0, cfg.vocab, size=s).astype(np.int32),
                    max_new=n, **kw)
            for i, (s, n) in enumerate(zip(sizes, news))]


def _run_scenario(name, cfg, model, params):
    eng = engine.PagedEngine(model, params, max_slots=2, max_len=128,
                             num_blocks=4)
    if name == "clean_burst":
        sched = Scheduler(eng)
        sched.run(_reqs(cfg, [4, 4, 4], [3, 3, 3]), burst=2)
    elif name == "shed":
        sched = Scheduler(eng, max_pending=2)
        sched.run(_reqs(cfg, [4] * 6, [3] * 6))
    elif name == "expire":
        sched = Scheduler(eng)
        reqs = _reqs(cfg, [4, 4], [50, 2])
        reqs[0] = dataclasses.replace(reqs[0], deadline=4.0)
        clock = {"t": 0.0}

        def now():
            clock["t"] += 1.0
            return clock["t"]

        sched.run(reqs, now_fn=now)
    elif name == "cancel":
        sched = Scheduler(eng)
        for r in _reqs(cfg, [4, 4], [10, 10]):
            sched.submit(r)
        sched.step()
        assert sched.cancel(0) and sched.cancel(1)
        sched.run()
    elif name == "bitflip_recovery":
        inj = faults.FaultInjector(eng, seed=3)

        def hook(step):
            if step == 2:
                inj.flip_random_bit(step)

        sched = Scheduler(eng)
        sched.run(_reqs(cfg, [6, 9], [6, 6]), fault_hook=hook)
    else:  # pragma: no cover
        raise AssertionError(name)
    return eng, sched


@pytest.mark.parametrize("scenario", ["clean_burst", "shed", "expire",
                                      "cancel", "bitflip_recovery"])
def test_accounting_identity_across_chaos(serving, scenario):
    """The property: registry counters, the SchedulerStats view, and the
    per-request terminal records are three readings of one ledger."""
    cfg, model, params = serving
    eng, sched = _run_scenario(scenario, cfg, model, params)
    assert sched.idle
    reg = sched.obs.registry
    s = sched.stats
    submitted = int(reg.counter("serve_submitted_total").value)
    outcomes = reg.counter("serve_requests_total", labels=("outcome",))
    # the identity: every submitted request reached exactly one outcome
    assert int(outcomes.total()) == submitted == s.submitted > 0
    assert (s.finished + s.deadline_misses + s.cancelled + s.shed
            + s.failed) == submitted
    # view == registry == results, per outcome
    by_status = {}
    for r in sched.results.values():
        by_status[r.status] = by_status.get(r.status, 0) + 1
    for attr, outcome in SchedulerStats._OUTCOMES.items():
        assert getattr(s, attr) == int(outcomes.total(outcome=outcome)) \
            == by_status.get(outcome, 0), (scenario, attr)
    # emitted tokens reconcile with the terminal records' token arrays
    assert s.emitted_tokens == sum(len(r.tokens)
                                   for r in sched.results.values())
    # instrumentation adds no executables: one decode-step trace, ever
    n = getattr(eng._step, "_cache_size", lambda: None)()
    assert n in (None, 0, 1)
    eng.pool.verify_invariants()


def test_stats_view_rejects_unknown_attr(serving):
    _, model, params = serving
    eng = engine.PagedEngine(model, params, max_slots=1, max_len=128)
    sched = Scheduler(eng)
    with pytest.raises(AttributeError):
        sched.stats.bogus_counter
    d = sched.stats.as_dict()
    assert d["submitted"] == 0 and "admitted" in d


def test_fresh_scheduler_gets_fresh_counters(serving):
    """Benches run several schedulers over one warm engine; per-run stats
    must not bleed across runs through the shared engine/pool."""
    cfg, model, params = serving
    eng = engine.PagedEngine(model, params, max_slots=2, max_len=128)
    a = Scheduler(eng)
    a.run(_reqs(cfg, [4, 4], [2, 2]))
    assert a.stats.finished == 2
    b = Scheduler(eng)
    assert b.stats.submitted == b.stats.finished == 0
    assert eng.obs is b.obs and eng.pool.obs is b.obs


# ---------------------------------------------------------------------------
# serving phases and latency histograms
# ---------------------------------------------------------------------------

# each phase of Scheduler.step -> the span it nests in
PHASES = {"serve.verify": "serve.step", "serve.admit": "serve.step",
          "serve.prefill": "serve.admit", "serve.ensure_blocks": "serve.step",
          "serve.inputs": "serve.step", "serve.decode": "serve.step",
          "serve.replay": "serve.step", "serve.refresh": "serve.step"}
CHECKSUM_PARENTS = {"serve.verify", "serve.refresh", "serve.prefill"}


def _parent(i, spans):
    """Name of the innermost other span that contains span ``i``."""
    _, s, e = spans[i]
    outer = [(b - a, n) for j, (n, a, b) in enumerate(spans)
             if j != i and a <= s and e <= b]
    return min(outer)[1] if outer else None


def test_scheduler_phases_in_a_profiler_trace(serving, tmp_path):
    """A scheduler run under jax.profiler writes every phase span on the
    profiler's clock, nested as the step runs them, one ``serve.step`` a
    step; the process keeps the same spans in ``profiled_spans``."""
    cfg, model, params = serving
    eng = engine.PagedEngine(model, params, max_slots=2, max_len=128)
    Scheduler(eng).run(_reqs(cfg, [5, 5], [3, 3]))  # compiles, untraced
    sched = Scheduler(eng)
    for r in _reqs(cfg, [5, 5], [3, 3], seed=1):
        sched.submit(r)
    steps = 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = time.perf_counter()
        while not sched.idle:
            sched.step()
            steps += 1
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for p in data.planes for line in p.lines for e in line.events
             if e.name.startswith("serve.")]
    names = [n for n, _, _ in spans]
    assert set(names) == set(PHASES) | {"serve.step", "serve.checksums"}
    assert names.count("serve.step") == steps == 2
    assert names.count("serve.prefill") == 2
    for i, name in enumerate(names):
        parent = _parent(i, spans)
        if name == "serve.step":
            assert parent is None
        elif name == "serve.checksums":
            assert parent in CHECKSUM_PARENTS
        else:
            assert parent == PHASES[name], (name, parent)
    mirror = obs_mod.profiled_spans(t0, t1)
    assert sorted(n for n, _, _ in mirror) == sorted(names)


def test_phase_spans_inert_without_profiler_and_on_the_tracer_lane(serving):
    cfg, model, params = serving
    eng = engine.PagedEngine(model, params, max_slots=2, max_len=128)
    before = len(obs_mod.profiled_spans())
    Scheduler(eng).run(_reqs(cfg, [5], [2]))
    assert len(obs_mod.profiled_spans()) == before
    sched = Scheduler(eng, obs=obs_mod.Obs(trace=True))
    sched.run(_reqs(cfg, [5], [3]))
    lane = sched.obs.tracer.spans(lane="scheduler")
    assert len([e for e in lane if e["name"] == "serve.step"]) == 2
    assert {e["name"] for e in lane} >= set(PHASES) - {"serve.verify"}


def test_ttft_and_queue_wait_from_arrival_and_itl_per_request(serving):
    """With the caller's clock, TTFT and queue wait count from arrival;
    without one, from submit. ITL is one sample per token after a
    request's first."""
    cfg, model, params = serving
    eng = engine.PagedEngine(model, params, max_slots=1, max_len=128)
    clock = {"t": 0.0}

    def now():
        clock["t"] += 100.0
        return clock["t"]

    sched = Scheduler(eng)
    # one slot: r0 admitted at t=100, finishes at t=200; r1 admitted at 300
    sched.run(_reqs(cfg, [4, 4], [3, 2]), now_fn=now)
    reg = sched.obs.registry
    for name in ("serve_ttft_seconds", "serve_queue_wait_seconds"):
        h = reg.histogram(name)._solo()
        assert h.count == 2, name
        assert 100.0 <= h.min < 150.0 and 300.0 <= h.max < 350.0, name
    itl = reg.histogram("serve_itl_seconds")._solo()
    assert itl.count == 5 - 2 and 0.0 <= itl.max < 50.0
    plain = Scheduler(eng)
    plain.run(_reqs(cfg, [4, 4], [3, 2]))
    for name in ("serve_ttft_seconds", "serve_queue_wait_seconds"):
        h = plain.obs.registry.histogram(name)._solo()
        assert h.count == 2 and 0.0 < h.max < 50.0, name
    assert plain.obs.registry.histogram("serve_itl_seconds")._solo() \
        .count == 3


def test_latency_histograms_step_about_twelve_percent():
    b = log_buckets(per_decade=20)
    ratios = [y / x for x, y in zip(b, b[1:])]
    assert all(math.isclose(r, 10 ** 0.05) for r in ratios)
    assert math.isclose(b[0], 1e-5) and math.isclose(b[-1], 100.0)


# ---------------------------------------------------------------------------
# end-to-end: instrumented flood (chain + downshift + byte-agreement)
# ---------------------------------------------------------------------------


def test_instrumented_flood_end_to_end(tmp_path):
    cfg = dataclasses.replace(reduced(configs.get("mistral-large-123b")),
                              dtype="float32")
    model = DecoderModel(cfg, kv_container="sfp-m3e5")
    params = model.init(jax.random.PRNGKey(0))
    paths = {k: tmp_path / v for k, v in
             [("metrics", "metrics.prom"), ("events", "events.jsonl"),
              ("trace", "trace.json"), ("timeline", "timeline.jsonl")]}
    ops.force_backend("ref")
    try:
        eng = engine.PagedEngine(model, params, max_slots=8, max_len=256,
                                 num_blocks=4,
                                 degraded_container="sfp-m1e2")
        obs = obs_mod.Obs(metrics_path=str(paths["metrics"]),
                          events_path=str(paths["events"]),
                          trace_path=str(paths["trace"]),
                          timeline_path=str(paths["timeline"]))
        sched = Scheduler(eng, obs=obs,
                          pressure=precision.PressureController(low=0.6,
                                                                high=0.85))
        out = sched.run(_reqs(cfg, [100] * 8, [10] * 8))
    finally:
        ops.force_backend(None)
    obs.close()
    s = sched.stats
    assert s.finished == 8 and s.downshifted >= 1
    assert all(len(out[u]) == 10 for u in range(8))
    # TTFT: observed once per request, on its first-ever token
    ttft = obs.registry.histogram("serve_ttft_seconds")
    assert ttft._solo().count == 8 and ttft.percentile(0.5) > 0
    # every timeline entry byte-agrees with the pool, in-stream
    assert validate_mod.check_timeline_accounting(
        str(paths["timeline"])) == []
    serve_entries = [json.loads(x) for x in
                     paths["timeline"].read_text().splitlines()]
    assert any(e["pressure"] == "degraded" for e in serve_entries)
    assert any(len(e["geometry_bytes"]) == 2 for e in serve_entries), \
        "mixed-geometry residency never captured"
    # the full CLI gate, exactly as the CI smoke invokes it
    rc = validate_mod.main([
        "--metrics", str(paths["metrics"]), "--trace", str(paths["trace"]),
        "--timeline", str(paths["timeline"]),
        "--events", str(paths["events"]),
        "--schemas-dir", str(SCHEMAS),
        "--require-chain", "--require-downshift"])
    assert rc == 0
    # and the burst/step executables stayed singular under full telemetry
    n = getattr(eng._step, "_cache_size", lambda: None)()
    assert n in (None, 0, 1)


# ---------------------------------------------------------------------------
# train loop: structured failure events share the metrics stream
# ---------------------------------------------------------------------------

_DIMS = policies.ScopeDims(n_periods=1, n_rem=0, man_bits=7, exp_bits=8)


def _mini_state():
    params = {"w": jnp.zeros((4,))}
    return TrainState(
        params=params, opt=adamw.init(params),
        pstate=policies.get("qm").init_state(_DIMS),
        step=jnp.zeros((), jnp.int32), rng=jax.random.PRNGKey(0),
        grad_residual=None)


def _mini_step(state, batch):
    new = state._replace(
        params={"w": state.params["w"] + batch["x"].mean()},
        step=state.step + 1)
    return new, {"loss": jnp.sum(new.params["w"])}


def _mini_batches(start):
    def gen():
        i = start
        while True:
            yield {"x": jnp.full((2,), float(i + 1))}
            i += 1
    return gen()


def test_loop_failure_is_a_structured_event_not_just_a_print(tmp_path):
    metrics = tmp_path / "metrics.jsonl"
    obs = obs_mod.Obs(events_path=str(tmp_path / "events.jsonl"))
    cfg = loop_mod.LoopConfig(total_steps=10, ckpt_every=2,
                              ckpt_dir=str(tmp_path / "ck"),
                              metrics_file=str(metrics), log_every=1,
                              obs=obs)
    fired = {"done": False}

    def fault(step):
        if step == 7 and not fired["done"]:
            fired["done"] = True
            raise RuntimeError("simulated node failure")

    res = loop_mod.run(_mini_step, _mini_state(), _mini_batches, cfg,
                       fault_hook=fault)
    assert res.restarts == 1 and int(res.state.step) == 10
    lines = [json.loads(x) for x in metrics.read_text().splitlines()]
    fails = [x for x in lines if x.get("event") == "step_failure"]
    assert len(fails) == 1
    f = fails[0]
    assert f["step"] == 7 and f["restart"] == 1
    assert f["error"] == "RuntimeError" and f["restore_step"] <= 7
    # the same event reaches the obs event stream for exporters
    assert any(e.get("event") == "step_failure"
               for e in obs.events.entries)
    assert int(obs.registry.counter("train_step_failures_total").value) == 1
    # metric lines and lifecycle events interleave in one valid stream
    schema = json.loads((SCHEMAS / "events.schema.json").read_text())
    assert validate_mod.validate_jsonl(str(metrics), schema) == []
    assert any("loss" in x and "event" not in x for x in lines)
    assert any(x.get("event") == "checkpoint" for x in lines)
    # the step-time histogram saw every executed step (incl. replays)
    h = obs.registry.histogram("train_step_seconds")
    assert h._solo().count >= 10
