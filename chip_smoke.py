#!/usr/bin/env python3
"""Smoke test of the serve and train main paths on one TPU chip.

    python chip_smoke.py

One process, no children, through the launchers' own functions:

* serve: ``launch/serve.py`` ``build_model`` + ``run_trace`` on
  mistral-large-123b at every published width, depth cut to 2 layers
  (random weights from a seed), once with an ``sfp8`` and once with an
  ``sfp-m2e4`` paged pool sharing the weights: 8 seeded requests (prompts
  64-512 tokens, 16-64 new tokens), all of which must finish ``ok``. Then
  one paged decode step over the pool the scheduler filled runs twice on
  the same inputs, through the Pallas kernels and through the jnp
  reference (``ops.force_backend("ref")``), and the logits must agree to
  ``LOGIT_TOL``. The pack/unpack kernels must match the reference bit for
  bit.
* train: ``launch/train.py`` ``build`` + ``train/loop.run`` on the whole
  mamba2-370m (48 layers) with the ``qm+qe`` policy and the fused
  quantize+pack ``sfp-m2e4`` stash, batch 8 x 2048 tokens, 5 steps, no
  checkpoints: every loss finite, no restarts.

Each phase compiles its jitted step ahead of time and counts the
``tpu_custom_call`` ops (Pallas kernels) in it. The script exits non-zero
when ``jax.devices()[0]`` is not a TPU, when the kernel backend is not
``pallas``, or when a phase fails; it never falls back to the CPU. Phase
results are printed first; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

SEED = 0
SERVE_ARGV = ["--arch", "mistral-large-123b", "--preset", "full",
              "--layers", "2", "--trace", "--flood", "--requests", "8",
              "--max-slots", "8", "--max-len", "2048",
              "--prompt-len-min", "64", "--prompt-len-max", "512",
              "--max-new-min", "16", "--max-new-max", "64",
              "--burst", "1", "--seed", str(SEED)]
SERVE_CONTAINERS = ("sfp8", "sfp-m2e4")
TRAIN_ARGV = ["--arch", "mamba2-370m", "--preset", "full",
              "--policy", "qm+qe", "--container", "sfp-m2e4",
              "--batch", "8", "--seq", "2048", "--steps", "5",
              "--seed", str(SEED)]
# Pallas vs jnp-reference logits of one decode step, relative to the
# largest reference logit. The decode kernel accumulates attention in f32
# in its own order and rounds its output to bf16, so some activations
# differ by one bf16 ulp (2^-8 relative) and that carries through two
# layers of bf16 matmuls: a few ulps of the logit scale. A wrong bit,
# plane or base exponent in the cache decode changes K/V values by 2^-3
# relative or more, which shows up far above this.
LOGIT_TOL = 2e-2


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class _CompileClock:
    """Sums XLA backend compile seconds (persistent-cache hits add none)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += secs


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _check_bit_exact(container: str, seed: int) -> None:
    """Pallas pack/unpack of one (4096, 1024) bf16 tensor == reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import codecs
    from repro.kernels import ops

    f = codecs.fields_for(container, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(seed), (4096, 1024),
                          jnp.float32).astype(jnp.bfloat16)
    out = {}
    for b in ("pallas", "ref"):
        ops.force_backend(b)
        try:
            p = ops.sfp_compress_nd(x, f)
            out[b] = (np.asarray(p.payload), np.asarray(p.bases),
                      np.asarray(ops.sfp_decompress_nd(p, jnp.bfloat16, f)
                                 ).view(np.uint16))
        finally:
            ops.force_backend(None)
    for name, a, r in zip(("payload", "bases", "unpacked"), out["pallas"],
                          out["ref"]):
        if not np.array_equal(a, r):
            raise AssertionError(f"{container}: Pallas {name} differs from "
                                 f"the reference in {(a != r).sum()} places")


def _compare_decode(sched) -> dict:
    """One paged decode step over the scheduler's live pool, through the
    Pallas kernels and through the jnp reference, on the same inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    eng = sched.engine
    toks = np.zeros(eng.max_slots, np.int32)
    pos = np.zeros(eng.max_slots, np.int32)
    for st in sched.running.values():
        toks[st.slot] = st.last_tok
        pos[st.slot] = st.n_ctx
    slots = sorted(st.slot for st in sched.running.values())
    args = (eng.params, eng.mem, jnp.asarray(toks)[:, None],
            jnp.asarray(pos), jnp.asarray(eng.pool.tables))
    logits = {}
    for b in ("pallas", "ref"):
        ops.force_backend(b)
        try:  # a fresh jit per backend: the backend is read at trace time
            step = jax.jit(lambda *a: eng.model.decode_step_paged(*a)[0])
            # The reference's f32 attention einsums run at full f32 on the
            # TPU only under "highest". The kernels keep their own matmul
            # precision (Mosaic refuses an fp32 contract on bf16 operands).
            with (jax.default_matmul_precision("highest") if b == "ref"
                  else contextlib.nullcontext()):
                logits[b] = np.asarray(step(*args)[slots, -1], np.float32)
        finally:
            ops.force_backend(None)
    diff = float(np.max(np.abs(logits["pallas"] - logits["ref"])))
    scale = float(np.max(np.abs(logits["ref"])))
    agree = float(np.mean(np.argmax(logits["pallas"], -1)
                          == np.argmax(logits["ref"], -1)))
    return {"slots": len(slots), "max_abs_logit_diff": diff,
            "max_abs_logit": scale, "tolerance": LOGIT_TOL * scale,
            "argmax_agree": agree, "finite": bool(
                np.isfinite(logits["pallas"]).all())}


def serve_phase(argv=SERVE_ARGV, containers=SERVE_CONTAINERS) -> None:
    import jax.numpy as jnp
    from repro.launch import serve

    params = None
    for container in containers:
        t0 = time.time()
        args = serve.build_parser().parse_args(
            argv + ["--kv-container", container])
        built = serve.build_model(args, params)
        params = built[2]
        _check_bit_exact(container, SEED)
        checked = {}

        def on_step(i, sched):
            # Once every request is admitted (step 1 of the flood), compare
            # the kernel and reference decode over the filled pool.
            if not checked and len(sched.running) == args.requests:
                checked.update(_compare_decode(sched))

        report, sched = serve.run_trace(args, built, on_step)
        outcomes = {uid: r.status for uid, r in sched.results.items()}
        if (len(outcomes) != args.requests
                or any(o != "ok" for o in outcomes.values())):
            raise AssertionError(f"{container}: outcomes {outcomes}")
        if not checked:
            raise AssertionError(f"{container}: the batch never filled")
        if not (checked["finite"]
                and checked["max_abs_logit_diff"] <= checked["tolerance"]):
            raise AssertionError(f"{container}: kernel vs reference "
                                 f"logits {checked}")
        eng = sched.engine
        B = eng.max_slots
        t1 = time.time()
        compiled = eng._step.lower(
            eng.params, eng.mem, jnp.asarray(eng.pool.tables),
            jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32)
        ).compile()
        _emit("serve", container=container, requests_ok=len(outcomes),
              emitted_tokens=report["emitted_tokens"],
              decode_steps=report["decode_steps"],
              kernel_vs_ref=checked, pack_unpack_bit_exact=True,
              tpu_custom_call=_custom_calls(compiled),
              step_compile_s=round(time.time() - t1, 3),
              phase_s=round(time.time() - t0, 3))
        if _custom_calls(compiled) == 0:
            raise AssertionError("serve step has no Pallas kernel")


def train_phase(argv=TRAIN_ARGV) -> None:
    import jax
    import jax.numpy as jnp
    from repro.data import synthetic
    from repro.launch import train
    from repro.train import loop
    from repro.train import step as step_mod

    t0 = time.time()
    args = train.build_parser().parse_args(argv)
    cfg, model, tc, batch, seq = train.build(args)
    state = step_mod.init_state(model, jax.random.PRNGKey(args.seed), tc)
    dcfg = synthetic.SyntheticConfig(vocab=cfg.vocab, seq_len=seq,
                                     global_batch=batch, seed=args.seed)

    def batches(start):
        return ({k: jnp.asarray(v) for k, v in b.items()}
                for b in synthetic.batches(dcfg, start))

    t1 = time.time()
    step = jax.jit(step_mod.make_train_step(model, tc), donate_argnums=(0,))
    compiled = step.lower(state, next(batches(0))).compile()
    compile_s = time.time() - t1
    if _custom_calls(compiled) == 0:
        raise AssertionError("train step has no Pallas kernel")
    res = loop.run(compiled, state, batches,
                   loop.LoopConfig(total_steps=args.steps, ckpt_dir=None))
    for m in res.history:
        _emit("train_step", step=m["step"], loss=m["loss"],
              qm_act_bits=m.get("qm_act_mean"), qm_w_bits=m.get("qm_w_mean"),
              qe_act_bits=m.get("qe_act_mean"), qe_w_bits=m.get("qe_w_mean"),
              step_s=round(m["step_time_s"], 4))
    losses = [m["loss"] for m in res.history]
    if len(losses) != args.steps or not all(map(math.isfinite, losses)):
        raise AssertionError(f"train losses {losses}")
    if res.restarts:
        raise AssertionError(f"train loop restarted {res.restarts} times")
    _emit("train", arch=cfg.name, layers=cfg.n_layers,
          params_m=round(cfg.param_count() / 1e6, 1), steps=len(losses),
          restarts=res.restarts, tpu_custom_call=_custom_calls(compiled),
          step_compile_s=round(compile_s, 3),
          phase_s=round(time.time() - t0, 3))


def main() -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else under /tmp
    import jax
    from repro.kernels import ops
    from repro.launch.cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0] is "
              f"{dev.platform}); this check runs only on the chip",
              file=sys.stderr)
        return 1
    if ops.backend() != "pallas":
        print(f"chip_smoke: kernel backend is {ops.backend()!r}, not "
              "'pallas'", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    clock = _CompileClock()
    t0 = time.time()
    serve_phase()
    gc.collect()  # drop the serve weights and pools before training
    train_phase()
    _emit("total", compile_s=round(clock.seconds, 3), cache_dir=cache,
          wall_s=round(time.time() - t0, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
