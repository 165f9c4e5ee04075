"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --preset tiny \
      --policy qm+qe --steps 200 --ckpt-dir /tmp/ckpt

``--policy`` takes any registered precision policy (none, static, qm, qe,
bitchop, bitwave) or a '+'-composition such as ``qm+qe`` (learn mantissa
AND exponent bitlengths in one run). Presets scale the assigned configs
down for the CPU environment; ``--preset full`` keeps the published
widths, and ``--layers N`` cuts only the depth. The loop is fault-tolerant: it
checkpoints every --ckpt-every steps (recording the policy in the
manifest) and restores+continues on step failure. The final report
includes the modeled stash footprint under the learned/adapted decisions —
exponent-bit savings from qe/bitwave show up there.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import codecs, configs, policies
from repro import obs as obs_mod
from repro.configs.base import depth_cut, reduced
from repro.launch.args import container_name, policy_name
from repro.launch.cache import enable_compile_cache
from repro.data import pipeline, synthetic
from repro.models.model import DecoderModel
from repro.optim import adamw
from repro.optim.schedule import Schedule
from repro.train import loop as loop_mod
from repro.train import step as step_mod


def build_policy(args) -> policies.Policy:
    """Resolve --policy, routing the qm-* / qe-* flags to their sub-policy.

    QE rides its own knobs (the exponent field is smaller and flushing a
    binade is harsher than dropping a mantissa bit), so each '+'-part is
    constructed with its own kwarg set and composed once.
    """
    per_sub = {
        "qm": dict(gamma=args.gamma, lr=args.qm_lr,
                   init_bits=args.qm_init_bits),
        "qe": dict(gamma=args.qe_gamma, lr=args.qe_lr),
    }
    parts = args.policy.split("+")
    if len(set(parts)) != len(parts):
        raise SystemExit(f"duplicate sub-policy in --policy {args.policy!r}")
    subs = [policies.get(part, container=args.container,
                         **per_sub.get(part, {}))
            for part in parts]
    return (subs[0] if len(subs) == 1
            else policies.CompositePolicy(policies=tuple(subs)))


def build(args):
    cfg = configs.get(args.arch)
    if args.preset == "tiny":
        cfg = reduced(cfg)
        batch, seq = 8, 64
    elif args.preset == "small":
        cfg = reduced(cfg, n_layers=max(2 * len(cfg.period), 4), d_model=256)
        batch, seq = 8, 128
    else:
        batch, seq = args.batch, args.seq
    if args.layers:
        cfg = depth_cut(cfg, args.layers)

    policy = build_policy(args)
    model = DecoderModel(cfg, policy)
    tc = step_mod.TrainConfig(
        opt=adamw.AdamWConfig(lr=args.lr),
        schedule=Schedule(kind="cosine", base_lr=args.lr,
                          warmup_steps=min(50, args.steps // 10),
                          total_steps=args.steps),
        num_microbatches=args.microbatches,
        grad_compress_bits=args.grad_compress_bits,
    )
    return cfg, model, tc, batch, seq


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="tiny",
                    choices=["tiny", "small", "full"])
    ap.add_argument("--policy", default="qm", metavar="NAME[+NAME...]",
                    type=policy_name,
                    help="precision policy from the registry "
                         f"({'/'.join(policies.names())}), composable with "
                         "'+', e.g. qm+qe")
    ap.add_argument("--container", default="bit_exact", type=container_name,
                    help="stash codec: any registered name "
                         f"({'/'.join(codecs.names())}) or a parametric "
                         "dense geometry like sfp-m2e4")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep every width and cut the depth to N layers "
                         "(whole periods; configs.base.depth_cut)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--gamma", type=float, default=0.05,
                    help="QM footprint-penalty strength (eq. 7)")
    ap.add_argument("--qm-init-bits", type=float, default=7.0)
    ap.add_argument("--qm-lr", type=float, default=0.05)
    ap.add_argument("--qe-gamma", type=float, default=0.05,
                    help="QE footprint-penalty strength")
    ap.add_argument("--qe-lr", type=float, default=0.05)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress-bits", type=int, default=None)
    ap.add_argument("--per-layer-stash", action="store_true",
                    help="pack each period's stash at its own policy-"
                         "learned dense container (model.stash_plan); the "
                         "plan refreshes every --stash-refresh steps and "
                         "the step re-jits when it changes")
    ap.add_argument("--stash-refresh", type=int, default=None,
                    help="steps between per-layer stash plan refreshes "
                         "(default: --ckpt-every)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--metrics", default=None,
                    help="per-step metrics JSONL (the obs event stream)")
    ap.add_argument("--metrics-out", default=None,
                    help="write Prometheus-text metrics (step-time "
                         "histogram, failure counters) here at exit")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace_event JSON of train-step "
                         "spans here at exit (opens in Perfetto)")
    ap.add_argument("--timeline-out", default=None,
                    help="stream the per-layer precision timeline "
                         "(JSONL; one entry per --timeline-every steps)")
    ap.add_argument("--timeline-every", type=int, default=10)
    ap.add_argument("--profile-steps", type=int, default=None,
                    metavar="N",
                    help="bracket jax.profiler.trace around N steps "
                         "(starting at --profile-start)")
    ap.add_argument("--profile-start", type=int, default=1)
    ap.add_argument("--profile-dir",
                    default="experiments/traces/train")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main():
    # Container/policy typos fail in the usage message: both flags carry
    # registry-backed argparse validators (launch/args.py).
    args = build_parser().parse_args()
    enable_compile_cache()
    cfg, model, tc, batch, seq = build(args)
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"policy={model.policy.name} container={args.container}")

    train_step = jax.jit(step_mod.make_train_step(model, tc),
                         donate_argnums=(0,))
    state = step_mod.init_state(model, jax.random.PRNGKey(args.seed), tc)

    dcfg = synthetic.SyntheticConfig(vocab=cfg.vocab, seq_len=seq,
                                     global_batch=batch, seed=args.seed)

    def batches(start):
        it = synthetic.batches(dcfg, start)
        def to_batch(b):
            out = {k: jnp.asarray(v) for k, v in b.items()}
            if cfg.prefix_tokens:
                out["cond_embeddings"] = jnp.zeros(
                    (batch, cfg.prefix_tokens, cfg.d_model),
                    cfg.compute_dtype)
            return out
        return (to_batch(b) for b in it)

    def ckpt_extra(state):
        # Stamp the policy's *current* decision summary alongside the run
        # identity: policy-aware serving (serve/precision.py) derives the
        # KV pool's container geometry from these learned bitlengths via
        # CheckpointManager.read_extra — no state restore needed.
        d = model.policy.decision_summary(state.pstate, model.dims)
        return {"policy": model.policy.name, "container": args.container,
                "decision": {"man_bits": float(d["man_bits"]),
                             "exp_bits": float(d["exp_bits"])}}

    obs = obs_mod.Obs(metrics_path=args.metrics_out,
                      trace_path=args.trace_out,
                      timeline_path=args.timeline_out)

    def timeline_fn(state):
        # Late-binds `model`: the per-layer-stash loop rebuilds the model
        # each refresh segment, and the timeline must follow the live one.
        return model.policy.layer_decisions(state.pstate, model.dims)

    lc = loop_mod.LoopConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, metrics_file=args.metrics,
        log_every=max(1, args.steps // 50),
        ckpt_extra=ckpt_extra, obs=obs, timeline_fn=timeline_fn,
        timeline_every=args.timeline_every,
        profile_steps=(None if args.profile_steps is None
                       else (args.profile_start, args.profile_steps)),
        profile_dir=args.profile_dir)
    if args.per_layer_stash:
        # Per-layer realized containers: the stash plan is static under
        # jit, so the loop runs in segments — every refresh boundary the
        # plan is re-derived from the live policy state and the step
        # re-jits only when a layer's container actually changed (learned
        # bitlengths move slowly, so re-lowering is rare).
        import dataclasses as _dc
        refresh = max(1, args.stash_refresh or args.ckpt_every)
        plan = None
        history = []
        res = None
        done = int(np.asarray(state.step))
        while done < args.steps:
            new_plan = model.stash_plan(state.pstate)
            if new_plan != plan:
                plan = new_plan
                print(f"[train] per-layer stash plan @ step {done}: "
                      f"{','.join(plan)}")
                model = DecoderModel(cfg, model.policy,
                                     stash_containers=plan)
                train_step = jax.jit(step_mod.make_train_step(model, tc),
                                     donate_argnums=(0,))
            seg = _dc.replace(lc, total_steps=min(done + refresh,
                                                  args.steps),
                              metrics_truncate=(res is None))
            res = loop_mod.run(train_step, state, batches, seg)
            state = res.state
            history.extend(res.history)
            done = int(np.asarray(state.step))
        res = _dc.replace(res, state=state, history=history)
        print(f"[train] final per-layer stash plan: {','.join(plan)}")
    else:
        res = loop_mod.run(train_step, state, batches, lc)
    last = res.history[-1]
    print(json.dumps({k: last[k] for k in
                      ("step", "loss", "xent", "qm_act_mean", "qm_w_mean",
                       "qe_act_mean", "qe_w_mean", "bc_bits", "bw_man_bits",
                       "bw_exp_bits") if k in last}, indent=2))
    # Modeled stash footprint under the final decisions: sign + learned
    # mantissa bits + (learned/adapted) exponent bits per value.
    fp = policies.modeled_footprint(model.policy, res.state.pstate,
                                    model.dims)
    print("footprint " + json.dumps({k: round(v, 4) for k, v in fp.items()}))
    obs.close()  # writes --metrics-out / --trace-out, closes the timeline


if __name__ == "__main__":
    main()
