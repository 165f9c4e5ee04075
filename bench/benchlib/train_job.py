"""Training cells: the program's jitted train step, fed on the device.

Set-up builds one object, the compiled step with its state, drives it
through its first three steps (the check reads the loss of each, the
first gradient from the optimizer's state after step one, and the
parameters' change after step three), then hands the same object to the
window, which runs steps until ``--seconds`` have passed. Every step goes
through the same call: a batch made on the device from (seed, step), the
step, and a host read of the loss (the program's own loop syncs the same
way).

The reference module names the policies it models (``POLICIES``); a job
whose policy it does not model is refused before anything runs.
"""
from __future__ import annotations

import gc
import math
import time

from benchlib import cost, traffic, train_ref, weights
from benchlib.core import Outcome, Run

CHECK_STEPS = 3

# Program sizes that must equal the configuration file's.
SIZE_KEYS = {"d_model": "hidden_size", "n_layers": "num_hidden_layers",
             "ssm_state": "state_size", "ssm_head_dim": "head_dim",
             "ssm_expand": "expand", "ssm_chunk": "chunk_size",
             "ssm_groups": "n_groups", "conv_width": "conv_kernel",
             "vocab": "vocab_size"}


def program_args(conf: dict, t: dict) -> list:
    return conf["program_args"] + [
        "--policy", t["policy"], "--container", t["container"],
        "--batch", str(t["batch"]), "--seq", str(t["seq"]),
        "--steps", str(t["optimizer"]["total_steps"]),
        "--lr", str(t["optimizer"]["lr"])]


def check_sizes(cfg, conf: dict) -> None:
    for attr, key in SIZE_KEYS.items():
        if getattr(cfg, attr) != conf[key]:
            raise ValueError(f"program {attr}={getattr(cfg, attr)} but the "
                             f"configuration file says {key}={conf[key]}")


def build(run: Run, fault=None):
    """(step, state, feed, data_key, weight generator, weight key).

    ``fault`` plants a fault in the step for the tests and readings of
    the check (never in the benchmark's runs): ``"unchanged"`` returns the
    state it was given, ``"half_batch"`` trains on the first half of the
    batch only (the mean taken over the rest)."""
    import jax
    import jax.numpy as jnp
    from repro.launch import train as launch
    from repro.optim import adamw
    from repro.train import step as step_mod
    from repro.train.state import TrainState

    conf, t = run.cell.config, run.cell.traffic
    args = launch.build_parser().parse_args(program_args(conf, t))
    cfg, model, tc, batch, seq = launch.build(args)
    check_sizes(cfg, conf)
    k_w, k_d = jax.random.split(weights.seed_key(run.seed))
    gen = weights.generator(model.param_shapes())

    def init(k):
        params = gen(k)
        return TrainState(params=params, opt=adamw.init(params),
                          pstate=model.policy.init_state(model.dims),
                          step=jnp.zeros((), jnp.int32),
                          rng=jax.random.fold_in(k, 999), grad_residual=None)

    state = jax.jit(init)(k_w)
    train_step = step_mod.make_train_step(model, tc)
    if fault == "unchanged":
        step = jax.jit(lambda s, b: (s, train_step(s, b)[1]))
    elif fault == "half_batch":
        step = jax.jit(lambda s, b: train_step(
            s, {k: v[:batch // 2] for k, v in b.items()}),
            donate_argnums=(0,))
    elif fault is None:
        step = jax.jit(train_step, donate_argnums=(0,))
    else:
        raise ValueError(f"unknown fault {fault!r}")
    feed = traffic.train_batch_fn(t, batch, seq, conf["vocab_size"])
    return step, state, feed, k_d, gen, k_w


def run(r: Run, control: bool = False, fault=None) -> Outcome:
    """One run of a training cell. ``control`` runs the reference in
    float8 as well and puts its numbers, against the float32 reference, in
    the program's place in the check (the readings of the check, not the
    benchmark's own runs)."""
    import jax

    conf, t = r.cell.config, r.cell.traffic
    ref = r.cell.reference()
    if t["policy"] not in ref.POLICIES:
        raise ValueError(f"the reference models policies {ref.POLICIES}, "
                         f"not {t['policy']!r}")
    step, state, feed, k_d, gen, k_w = build(r, fault)
    B, S = t["batch"], t["seq"]

    def call(state, i):
        with r.span("batch"):
            b = feed(k_d, i)
        with r.span("train_step"):
            state, m = step(state, b)
        with r.span("loss_read"):
            loss = float(m["xent"])
        return state, loss

    # The check's steps go through the window's own call.
    prog = {"xent": []}
    for i in range(CHECK_STEPS):
        state, loss = call(state, i)
        prog["xent"].append(loss)
        if i == 0:  # the first gradient as the optimizer holds it
            prog["grad"] = (train_ref.leaf_norms(state.opt.m)
                            / (1 - t["optimizer"]["b1"]))
    p0 = gen(k_w)
    prog["delta"] = train_ref.delta_norms(state.params, p0)
    prog["grad"] = prog["grad"].block_until_ready()
    del p0

    steps, bad = 0, 0
    with r.window():
        t0 = time.perf_counter()
        while True:
            state, loss = call(state, CHECK_STEPS + steps)
            steps += 1
            bad += not math.isfinite(loss)
            if time.perf_counter() - t0 >= r.seconds:
                break
    window_s = r.window_s
    tokens = steps * B * S

    del state, step
    gc.collect()
    opt = t["optimizer"]

    def xent_in(prec):
        return lambda p, tok, lab: ref.xent(p, tok, lab, conf, prec=prec)

    batches = [feed(k_d, i) for i in range(CHECK_STEPS)]
    rows = min(t["rows_per_block"], B)
    got = train_ref.run(xent_in("f32"), gen(k_w), batches, opt, rows)
    prog = {k: (v if k == "xent" else jax.device_get(v))
            for k, v in prog.items()}
    gaps = train_ref.gaps(prog, got)
    judged = gaps
    if control:
        low = train_ref.run(xent_in("fp8"), gen(k_w), batches, opt, rows)
        judged = train_ref.gaps(low, got)
    lim = r.cell.limits
    checks = {k: (judged[k], lim[k]) for k in lim}
    facts = {"steps": steps, "tokens_per_step": B * S,
             "flops_per_token": cost.mamba2_train_flops_per_token(conf),
             "numbers": gaps}
    return Outcome(end_to_end={"train_tokens_per_s": tokens / window_s},
                   attempted=steps, failed=bad, checks=checks, facts=facts)
