"""granite-4.0-h-small at a tiny size of the same family, on seeded random
weights (the benchmark's own generator), against the plain float32
reference ``bench/configs/granite-4.0-h-small.ref.py``: the full forward,
paged prefill then decode through the pool (SSD state and the sfp8 global
layer), and one train step. The MoE layer alone: ``test_granite_moe.py``."""
import dataclasses
import importlib.util
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import GLOBAL, expert_share, reduced
from repro.models.model import DecoderModel, init_run_state
from repro.optim import adamw
from repro.optim.schedule import Schedule
from repro.serve.engine import PagedEngine
from repro.train import step as step_mod

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
from benchlib import weights  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "granite_ref", ROOT / "bench" / "configs" / "granite-4.0-h-small.ref.py")
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# Every width cut, the family kept: GQA (4 heads of 64 over 2, so the
# KV rows fill the 128 lanes the pool packs), the Mamba-2
# mixer with its conv bias, 16 experts with top 4 and a shared expert,
# every published multiplier, eps and NoPE; 8 shares of 2 experts.
TINY = dataclasses.replace(
    reduced(configs.get("granite-4.0-h-small")), n_kv_heads=2,
    head_dim=64, n_experts=16, top_k=4, d_ff_expert=64, d_ff_shared=96)


def _cfg(shard=0, shards=8, dtype="float32"):
    return expert_share(dataclasses.replace(TINY, dtype=dtype), shard, shards)


def ref_conf(cfg):
    kinds = ["attention" if k == GLOBAL else "mamba" for k in cfg.period]
    return {"hidden_size": cfg.d_model, "mamba_expand": cfg.ssm_expand,
            "mamba_d_head": cfg.ssm_head_dim, "mamba_d_state": cfg.ssm_state,
            "mamba_n_groups": cfg.ssm_groups, "mamba_n_heads": cfg.ssm_heads,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim_,
            "attention_multiplier": cfg.attn_scale,
            "rms_norm_eps": cfg.norm_eps,
            "residual_multiplier": cfg.residual_mult,
            "embedding_multiplier": cfg.emb_mult,
            "logits_scaling": cfg.logits_div, "vocab_size": cfg.vocab,
            "num_experts_per_tok": cfg.top_k,
            "layer_types": kinds * cfg.n_periods,
            "num_hidden_layers": cfg.n_layers,
            "expert_share": {"shard": cfg.expert_shard,
                             "shards": cfg.expert_shards}}


def _params(model, seed=7):
    return weights.generator(model.param_shapes())(weights.seed_key(seed))


def _tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         TINY.vocab), np.int32)


def test_full_forward_matches_reference():
    # the training forward's capacity path, at a capacity that drops
    # nothing (every expert could take all 96 tokens)
    cfg = dataclasses.replace(_cfg(shard=3), capacity_factor=8.0)
    model = DecoderModel(cfg)
    params = _params(model)
    toks = _tokens(96)
    got, metrics = jax.jit(model.forward)(
        params, jnp.asarray(toks)[None],
        init_run_state(cfg, jax.random.PRNGKey(0)))
    assert float(metrics["moe_drop_frac"]) == 0.0
    want = ref.logits_at(params, toks, np.arange(96), ref_conf(cfg))
    # Both in float32: the program's chunked SSD scan and flash attention
    # against the reference's recurrence and plain softmax differ only in
    # summation order (about 1e-6 of logits of about 0.1-1).
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_paged_prefill_then_decode_matches_reference():
    cfg = _cfg(shard=0, dtype="bfloat16")
    model = DecoderModel(cfg, kv_container="sfp8")
    params = _params(model)
    eng = PagedEngine(model, params, max_slots=2, max_len=256)
    n0, n_dec = 40, 20
    toks = _tokens(n0 + n_dec, seed=2)
    slot = 1
    assert eng.pool.alloc_upto(slot, n0 + n_dec)
    eng.prefill_into_slot(slot, toks[:n0])
    rows, tables = [], jnp.asarray(eng.pool.tables)
    step = jax.jit(model.decode_step_paged)
    for t in range(n0, n0 + n_dec):
        tok = np.zeros((2, 1), np.int32)
        pos = np.zeros(2, np.int32)
        tok[slot, 0], pos[slot] = toks[t], t
        logits, eng.mem = step(params, eng.mem, jnp.asarray(tok),
                               jnp.asarray(pos), tables)
        rows.append(np.asarray(logits[slot, 0], np.float32))
    want = np.asarray(ref.logits_at(params, toks, np.arange(n0, n0 + n_dec),
                                    ref_conf(cfg)))
    got = np.stack(rows)
    # bf16 weights and activations and the sfp8 KV container (3 mantissa
    # bits) against float32: the logits (std about 0.1) agree to a few
    # hundredths, and the widest gap stays far below the logits' spread.
    err = np.abs(got - want)
    assert err.max() < 0.05 * np.ptp(want), (err.max(), np.ptp(want))
    assert np.mean(err) < 0.01 * np.ptp(want)


def test_one_train_step_gives_a_finite_loss():
    cfg = _cfg(shard=0, dtype="bfloat16")
    model = DecoderModel(cfg)
    tc = step_mod.TrainConfig(opt=adamw.AdamWConfig(lr=1e-3),
                              schedule=Schedule(total_steps=2,
                                                warmup_steps=1,
                                                base_lr=1e-3))
    step = jax.jit(step_mod.make_train_step(model, tc))
    state = step_mod.init_state(model, jax.random.PRNGKey(0), tc)
    toks = jnp.asarray(_tokens(2 * 33, seed=5).reshape(2, 33))
    _, m = step(state, {"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    assert np.isfinite(float(m["xent"])) and float(m["xent"]) > 0
    # training routes through the capacity path (1.25), which may drop
    assert 0.0 <= float(m["moe_drop_frac"]) < 1.0


def test_moe_counters_ride_the_token_pulls(tmp_path):
    """The engine counts each call's dropless routing from the arrays it
    pulls with the tokens: every routed assignment (rows x top-k x MoE
    layers), those on held experts, the largest load on one; while a
    profiler records, each layer's held count is noted for the
    benchmark's grouped-matmul reader, and nothing is noted otherwise."""
    from repro import obs as obs_mod

    cfg = _cfg(shard=0, dtype="bfloat16")
    model = DecoderModel(cfg, kv_container="sfp8")
    eng = PagedEngine(model, _params(model), max_slots=2, max_len=128)
    fam = eng.obs.registry.counter("serve_moe_assignments_total",
                                   labels=("kind",))
    assert eng.pool.alloc_upto(0, 40)
    before = len(obs_mod.profiled_notes())
    eng.prefill_into_slot(0, _tokens(24))
    assert len(obs_mod.profiled_notes()) == before
    layers = cfg.n_layers  # every layer of the hybrid carries the MoE
    assert fam.total(kind="routed") == 24 * cfg.top_k * layers
    held = fam.total(kind="held")
    load = eng.obs.registry.gauge("serve_moe_max_expert_load").value
    assert 0 < held <= 24 * cfg.experts_held * layers
    assert 0 < load <= 24
    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = time.perf_counter()
        eng.decode(np.zeros(2, np.int32), np.asarray([24, 0], np.int32))
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    # two slots a step, idle ones included; each lands on at most
    # experts_held of this chip's experts in each layer
    assert fam.total(kind="routed") == (24 + 2) * cfg.top_k * layers
    assert held <= fam.total(kind="held") <= held + 2 * cfg.experts_held * layers
    assert eng.obs.registry.gauge("serve_moe_max_expert_load").value >= load
    notes = obs_mod.profiled_notes(t0, t1)
    assert [n for n, _, _ in notes] == ["serve.moe_held"]
    per_layer = notes[0][2]
    assert len(per_layer) == layers
    assert sum(per_layer) == fam.total(kind="held") - held


def test_speculation_rewinds_the_ssd_state():
    """Self-speculation drafts K tokens, then rolls every rejected draft's
    state back; on the hybrid that is each slot's Mamba-2 and conv state
    besides the pool rows. Greedy, so every request's tokens must equal a
    one-token-a-step run's."""
    from repro.kernels import ops
    from repro.serve.scheduler import Request, Scheduler

    cfg = _cfg(shard=0, dtype="bfloat16")
    model = DecoderModel(cfg, kv_container="sfp8")
    params = _params(model)

    def run(**kw):
        reqs = [Request(uid=i, prompt=_tokens(n, seed=10 + i), max_new=m)
                for i, (n, m) in enumerate([(20, 9), (33, 6), (17, 11)])]
        ops.force_backend("ref")
        try:
            eng = PagedEngine(model, params, max_slots=3, max_len=128)
            sched = Scheduler(eng)
            return sched, sched.run(reqs, **kw)
        finally:
            ops.force_backend(None)

    _, one = run(burst=1)
    sched, spec = run(speculate=3)
    assert sched.stats.spec_rounds >= 1 and sched.stats.drafted > 0
    assert set(one) == set(spec)
    for uid in one:
        np.testing.assert_array_equal(one[uid], spec[uid])
