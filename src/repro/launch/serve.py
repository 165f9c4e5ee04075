"""Serving launcher: batch mode, or a continuous-batching request-trace
simulator over the paged compressed-KV engine.

Batch mode (one prefill + one jitted decode loop, the PR 2 path — now
reachable with a compressed cache from the CLI):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --preset tiny \
      --batch 4 --prompt-len 32 --max-new 16 --kv-container sfp8

Trace mode simulates production traffic: Poisson request arrivals with
mixed prompt/output lengths, driven through the scheduler's admission /
continuous-batching / preemption machinery on a virtual clock:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --preset tiny \
      --trace --requests 16 --arrival-rate 2.0 --kv-container sfp8 \
      --max-slots 8 --max-len 256

Policy-aware precision (paper §IV-A4 deployment mode): point
``--policy-ckpt`` at a training run's checkpoint directory and the KV
container geometry is derived from the learned PrecisionDecision stamped
in its manifest (see serve/precision.py) — overriding --kv-container.

Fault-tolerant operation (see README "Operating the server"): deadlines
(--deadline as a TTL after arrival), a bounded queue with load shedding
(--max-pending), chaos injection (--inject-flip-p / --inject-alloc-p,
seeded), the preemption-storm guard (--storm-guard), and the
precision-downshift pressure controller (--degraded-container +
--pressure-low/--pressure-high). An arrival flood — every request landing
at once — is just --flood:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --preset tiny \
      --trace --flood --requests 32 --kv-container sfp-m3e5 --num-blocks 8 \
      --max-pending 8 --deadline 20 --degraded-container sfp-m1e2
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro import obs as obs_mod
from repro.configs.base import depth_cut, expert_share, reduced
from repro.launch.args import container_name
from repro.launch.cache import enable_compile_cache
from repro.models.model import DecoderModel
from repro.serve import engine, faults, precision
from repro.serve.scheduler import Request, Scheduler


def build_model(args, params=None):
    """(cfg, model, params, container) for the serve flags; ``params``
    reuses weights already built for the same --arch/--preset/--layers
    (the KV container does not change them)."""
    cfg = configs.get(args.arch)
    if args.preset == "tiny":
        cfg = reduced(cfg)
    elif args.preset == "small":
        cfg = reduced(cfg, n_layers=max(2 * len(cfg.period), 4), d_model=256)
    if args.layers:
        cfg = depth_cut(cfg, args.layers)
    if args.expert_share:
        cfg = expert_share(cfg, *args.expert_share)
    container = args.kv_container
    if args.policy_ckpt:
        container = precision.container_from_checkpoint(args.policy_ckpt)
        print(f"policy-aware container from {args.policy_ckpt}: {container}")
    model = DecoderModel(cfg, kv_container=container)
    if params is None:
        # Jitted, the f32 normal draws fuse into the bf16 weights instead
        # of each materializing in f32 (a 2-layer published-width mistral
        # peaks at 9.3 GB on the device this way).
        params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    return cfg, model, params, container


def run_batch(args) -> None:
    cfg, model, params, container = build_model(args)
    prompt = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                (args.batch, args.prompt_len), 0, cfg.vocab)
    cond = (jnp.zeros((args.batch, cfg.prefix_tokens, cfg.d_model),
                      cfg.compute_dtype) if cfg.prefix_tokens else None)
    t0 = time.time()
    res = engine.generate(model, params, prompt, max_new=args.max_new,
                          cond_embeddings=cond)
    dt = time.time() - t0
    toks = args.batch * args.max_new
    print(f"arch={cfg.name} kv={container or 'raw'} generated {toks} tokens "
          f"in {dt:.1f}s ({toks/dt:.1f} tok/s)")
    print("sample:", np.asarray(res.tokens[0]).tolist())


def make_trace(args, vocab: int):
    """Poisson arrivals (exponential gaps at --arrival-rate req/s) with
    prompt/output lengths drawn uniformly from the given ranges.
    ``--flood`` collapses every arrival to t=0 (a thundering herd);
    ``--deadline`` stamps each request with arrival + TTL."""
    rng = np.random.RandomState(args.seed + 2)
    lo_p, hi_p = args.prompt_len_min, args.prompt_len_max
    lo_n, hi_n = args.max_new_min, args.max_new_max
    t = 0.0
    reqs = []
    for i in range(args.requests):
        if not getattr(args, "flood", False):
            t += rng.exponential(1.0 / args.arrival_rate)
        reqs.append(Request(
            uid=i,
            prompt=rng.randint(0, vocab,
                               size=rng.randint(lo_p, hi_p + 1)
                               ).astype(np.int32),
            max_new=int(rng.randint(lo_n, hi_n + 1)),
            arrival=t,
            deadline=(t + args.deadline if getattr(args, "deadline", None)
                      else None)))
    return reqs


def run_trace(args, built=None, on_step=None):
    """Serve the --trace workload; prints and returns the report dict
    together with the Scheduler (per-request outcomes in ``results``).
    ``built`` is ``build_model``'s tuple (built from ``args`` if None);
    ``on_step(step, sched)`` runs before each scheduler step."""
    cfg, model, params, container = built or build_model(args)
    if container is None:
        raise SystemExit("--trace needs a packed cache: pass --kv-container "
                         "(or --policy-ckpt)")
    eng = engine.PagedEngine(model, params, max_slots=args.max_slots,
                             max_len=args.max_len,
                             num_blocks=args.num_blocks,
                             degraded_container=args.degraded_container,
                             integrity=not args.no_integrity)
    reqs = make_trace(args, cfg.vocab)
    # Time-to-first-token in scheduler steps, per request (streaming
    # callback: fires the step each token is produced).
    ttft = {}
    pressure = None
    if args.degraded_container:
        pressure = precision.PressureController(low=args.pressure_low,
                                                high=args.pressure_high)
    obs = obs_mod.Obs(metrics_path=args.metrics_out,
                      events_path=args.events_out,
                      trace_path=args.trace_out,
                      timeline_path=args.timeline_out)
    sched = Scheduler(eng, on_token=lambda uid, tok, done:
                      ttft.setdefault(uid, sched.stats.decode_steps),
                      max_pending=args.max_pending,
                      storm_guard=args.storm_guard,
                      pressure=pressure, obs=obs)
    hook = None
    if args.inject_flip_p or args.inject_alloc_p:
        hook = faults.FaultInjector(eng, seed=args.fault_seed,
                                    p_flip=args.inject_flip_p,
                                    p_alloc_fail=args.inject_alloc_p)
    # --profile-steps N brackets jax.profiler around scheduler steps
    # [1, 1+N) — step 0 is excluded so the capture skips compile time.
    prof = {"on": False}

    def step_hook(i):
        if args.profile_steps:
            if not prof["on"] and i == 1:
                Path(args.profile_dir).mkdir(parents=True, exist_ok=True)
                jax.profiler.start_trace(args.profile_dir)
                prof["on"] = True
            elif prof["on"] and i >= 1 + args.profile_steps:
                jax.profiler.stop_trace()
                prof["on"] = False
        if hook is not None:
            hook(i)
        if on_step is not None:
            on_step(i, sched)

    # Virtual clock: admission sees arrivals as wall-clock-free step time
    # (one scheduler step advances it by --step-dt), so the same trace
    # replays identically on any hardware.
    clock = {"t": 0.0}

    def now():
        clock["t"] += args.step_dt
        return clock["t"]

    t0 = time.time()
    try:
        out = sched.run(reqs, now_fn=now, burst=args.burst,
                        fault_hook=step_hook, speculate=args.speculate,
                        draft_planes=args.draft_planes)
    finally:
        if prof["on"]:
            jax.profiler.stop_trace()
    dt = time.time() - t0
    total = int(sum(len(v) for v in out.values()))
    s = sched.stats
    pool = eng.pool.stats()
    n = max(1, len(reqs))
    kv_blocks = sched.obs.registry.counter("serve_decode_kv_blocks_total",
                                           labels=("kind",))
    moe = sched.obs.registry.counter("serve_moe_assignments_total",
                                     labels=("kind",))
    report = {
        "arch": cfg.name, "container": container,
        "requests": len(reqs), "emitted_tokens": total,
        "wall_s": round(dt, 2), "tok_per_s": round(total / max(dt, 1e-9), 1),
        "decode_steps": s.decode_steps,
        "mean_batch_occupancy": round(total / max(s.decode_steps, 1), 2),
        # Share of the paged decode grid's KV block steps that hold a
        # live slot (the rest are skipped by the kernel).
        "decode_kv_live_share": round(
            kv_blocks.total(kind="live")
            / max(1.0, kv_blocks.total(kind="grid")), 4),
        "preemptions": s.preemptions,
        # Dropless MoE routing (engine counters; every slot a step runs,
        # idle ones included): assignments to the experts held here, and
        # the most one held expert got in one layer call.
        "moe_held_assignments": int(moe.total(kind="held")),
        "moe_held_share": round(moe.total(kind="held")
                                / max(1.0, moe.total(kind="routed")), 4),
        "moe_max_expert_load": int(sched.obs.registry.gauge(
            "serve_moe_max_expert_load").value),
        "mean_ttft_steps": round(float(np.mean(list(ttft.values()))), 2)
        if ttft else None,
        # Wall-clock latency percentiles from the obs histograms
        # (bucket-resolution: log-spaced bounds, see obs/registry.py).
        "ttft_s_p50": round(sched._h_ttft.percentile(0.50), 6),
        "ttft_s_p95": round(sched._h_ttft.percentile(0.95), 6),
        "ttft_s_p99": round(sched._h_ttft.percentile(0.99), 6),
        "itl_s_p50": round(sched._h_itl.percentile(0.50), 6),
        "itl_s_p95": round(sched._h_itl.percentile(0.95), 6),
        "itl_s_p99": round(sched._h_itl.percentile(0.99), 6),
        "pool_blocks": pool.num_blocks, "pool_peak_used": pool.peak_used,
        "block_l": eng.block_l, "max_slots": eng.max_slots,
        "max_len": eng.max_len,
        # fault-tolerance layer
        "finished_ok": s.finished,
        "deadline_miss_pct": round(100.0 * s.deadline_misses / n, 1),
        "shed_pct": round(100.0 * s.shed / n, 1),
        "cancelled": s.cancelled, "failed": s.failed,
        "recoveries": s.recoveries, "corrupt_blocks": s.corrupt_blocks,
        "nan_guard_trips": s.nan_guard_trips,
        "alloc_failures": s.alloc_failures,
        "downshifted": s.downshifted,
        "quarantined_blocks": pool.quarantined,
        "injected_faults": hook.counts() if hook else {},
    }
    if args.speculate:
        report["speculate"] = args.speculate
        report["draft_planes"] = (args.draft_planes if args.draft_planes
                                  is not None
                                  else eng.default_draft_planes())
        report["spec_rounds"] = s.spec_rounds
        report["drafted"] = s.drafted
        report["draft_accepted"] = s.draft_accepted
        report["draft_rejected"] = s.draft_rejected
        report["acceptance_rate"] = round(
            s.draft_accepted / max(1, s.drafted), 3)
    obs.close()  # writes --metrics-out / --trace-out, closes streams
    if args.tokens_out:
        # Per-request emitted streams, for identity diffs across runs
        # (e.g. CI asserts --speculate K streams == burst=1 streams).
        Path(args.tokens_out).write_text(json.dumps(
            {int(uid): [int(t) for t in toks] for uid, toks in out.items()},
            sort_keys=True))
    print(json.dumps(report, indent=2))
    return report, sched


def _share(value: str):
    try:
        shard, shards = (int(v) for v in value.split("/"))
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected I/N, got {value!r}")
    return shard, shards


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="tiny", choices=["tiny", "small",
                                                         "full"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=None,
                    help="keep every width and cut the depth to N layers "
                    "(whole periods; configs.base.depth_cut)")
    ap.add_argument("--expert-share", default=None, type=_share,
                    metavar="I/N",
                    help="hold slice I of N equal slices of every MoE "
                    "layer's experts (expert parallelism over N chips; "
                    "the router keeps every expert)")
    ap.add_argument("--kv-container", default=None, type=container_name,
                    help="registry codec for the packed KV cache (sfp8, "
                    "sfp16, dense sfp-m2e4, ...); None = raw bf16 cache")
    ap.add_argument("--policy-ckpt", default=None,
                    help="checkpoint dir of a trained policy run; the KV "
                    "container geometry is derived from its stamped "
                    "PrecisionDecision (overrides --kv-container)")
    # batch mode
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    # trace mode (continuous batching over the paged pool)
    ap.add_argument("--trace", action="store_true",
                    help="simulate a Poisson request trace through the "
                    "paged engine + scheduler")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="mean request arrivals per virtual second")
    ap.add_argument("--step-dt", type=float, default=0.1,
                    help="virtual seconds one scheduler step advances")
    ap.add_argument("--prompt-len-min", type=int, default=8)
    ap.add_argument("--prompt-len-max", type=int, default=48)
    ap.add_argument("--max-new-min", type=int, default=4)
    ap.add_argument("--max-new-max", type=int, default=24)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="pool capacity in packed blocks (default: full "
                    "residency for every slot)")
    ap.add_argument("--burst", type=int, default=1,
                    help="decode tokens per scheduler step (one scan "
                    "dispatch)")
    ap.add_argument("--speculate", type=int, default=None, metavar="K",
                    help="self-speculative decoding: K draft steps at "
                    "prefix-precision reads + one full-width verify per "
                    "scheduler step (token-identical to --burst 1)")
    ap.add_argument("--draft-planes", type=int, default=None,
                    help="bit planes the draft expands per group "
                    "(default: container payload width - 1)")
    ap.add_argument("--tokens-out", default=None,
                    help="write the per-request emitted token streams "
                    "(JSON uid -> tokens) for identity diffs across runs")
    # fault tolerance / chaos
    ap.add_argument("--flood", action="store_true",
                    help="collapse every trace arrival to t=0")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request TTL in virtual seconds after arrival")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="bounded admission queue: arrived requests beyond "
                    "this are explicitly shed")
    ap.add_argument("--storm-guard", action="store_true",
                    help="reserve running slots' growth blocks at "
                    "admission (no preemption thrash)")
    ap.add_argument("--no-integrity", action="store_true",
                    help="disable per-block checksum verification")
    ap.add_argument("--degraded-container", default=None,
                    type=container_name,
                    help="narrower geometry for pressure-downshifted "
                    "admissions (enables the pressure controller)")
    ap.add_argument("--pressure-low", type=float, default=0.25,
                    help="degrade when free pool bytes fall below this "
                    "fraction of capacity")
    ap.add_argument("--pressure-high", type=float, default=0.5,
                    help="restore once free bytes recover above this "
                    "fraction")
    ap.add_argument("--inject-flip-p", type=float, default=0.0,
                    help="per-step probability of a seeded bit flip in an "
                    "allocated packed block")
    ap.add_argument("--inject-alloc-p", type=float, default=0.0,
                    help="per-step probability of arming one transient "
                    "admission alloc failure")
    ap.add_argument("--fault-seed", type=int, default=0)
    # observability (repro.obs)
    ap.add_argument("--metrics-out", default=None,
                    help="write Prometheus-text metrics here at exit "
                    "(counters + TTFT/latency histograms)")
    ap.add_argument("--events-out", default=None,
                    help="structured-event JSONL (quarantine/scrub/"
                    "corruption lifecycle)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace_event JSON of per-request "
                    "span chains here (opens in Perfetto)")
    ap.add_argument("--timeline-out", default=None,
                    help="stream the per-step pool geometry/occupancy/"
                    "pressure timeline (JSONL)")
    ap.add_argument("--profile-steps", type=int, default=None, metavar="N",
                    help="bracket jax.profiler.trace around N scheduler "
                    "steps (from step 1, past compile)")
    ap.add_argument("--profile-dir", default="experiments/traces/serve")
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    if args.trace:
        run_trace(args)
    else:
        run_batch(args)


if __name__ == "__main__":
    main()
