"""Layer-level correctness: chunked attention vs oracle, SSD vs naive
recurrence, RG-LRU scan vs stepwise decode, MoE invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import reduced
from repro.kernels import ref
from repro.models import attention, common, mamba2, moe, rglru


def _cfg(name, **kw):
    cfg = reduced(configs.get(name))
    return dataclasses.replace(cfg, dtype="float32", **kw)


def test_chunked_global_attention_matches_oracle():
    cfg = _cfg("mistral-large-123b")
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = attention.attn_init(p, cfg)
    B, S = 2, 512  # > 2*chunk forces the chunked path with chunk=128
    h = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32) * 0.3
    got = attention.attention_train(params, h, cfg, kind="global",
                                    positions=jnp.arange(S), chunk=128)
    q, k, v = attention._project_qkv(params, h, cfg, jnp.arange(S))
    want = ref.attention(q, k, v, causal=True, softcap=cfg.attn_softcap)
    want = want.reshape(B, S, -1) @ params["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_chunked_local_attention_matches_oracle():
    cfg = dataclasses.replace(_cfg("gemma2-2b"), window=96)
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = attention.attn_init(p, cfg)
    B, S = 1, 512
    h = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model)) * 0.3
    got = attention.attention_train(params, h, cfg, kind="local",
                                    positions=jnp.arange(S), chunk=128)
    q, k, v = attention._project_qkv(params, h, cfg, jnp.arange(S))
    want = ref.attention(q, k, v, causal=True, window=96,
                         softcap=cfg.attn_softcap)
    want = want.reshape(B, S, -1) @ params["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def _naive_ssd(params, h, cfg):
    """Direct per-step recurrence: the ground truth for chunked SSD."""
    x, z, Bp, Cp, dt, A, _ = mamba2._projections(params, h, cfg)
    B, S, H, P = x.shape
    N = cfg.ssm_state
    xf = np.asarray(x, np.float64)
    Bf = np.asarray(Bp, np.float64)
    Cf = np.asarray(Cp, np.float64)
    dtf = np.asarray(dt, np.float64)
    Af = np.asarray(A, np.float64)
    y = np.zeros((B, S, H, P))
    state = np.zeros((B, H, N, P))
    for t in range(S):
        decay = np.exp(dtf[:, t] * Af[None, :])  # (B, H)
        upd = np.einsum("bhn,bhp->bhnp", Bf[:, t],
                        xf[:, t] * dtf[:, t][..., None])
        state = state * decay[:, :, None, None] + upd
        y[:, t] = np.einsum("bhn,bhnp->bhp", Cf[:, t], state)
    y += xf * np.asarray(params["D"], np.float64)[None, None, :, None]
    y = jnp.asarray(y.reshape(B, S, H * P), jnp.float32)
    y = common.rmsnorm(params["norm"],
                       y * jax.nn.silu(z.astype(jnp.float32)))
    return y @ params["w_out"]


def test_ssd_chunked_matches_naive_recurrence():
    cfg = _cfg("mamba2-370m")
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = mamba2.ssd_init(p, cfg)
    B, S = 2, 64  # 4 chunks of 16
    h = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model)) * 0.5
    got = mamba2.ssd_forward(params, h, cfg)
    want = _naive_ssd(params, h, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


def test_ssd_gradient_finite_over_long_chunk():
    """Large dt over a 128-step chunk drives exp(cum_i - cum_j) above the
    diagonal past f32 range; the masked-out entries must not make the
    gradient NaN (full-width mamba2 training hit this on its first step)."""
    cfg = _cfg("mamba2-370m", ssm_chunk=128)
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = mamba2.ssd_init(p, cfg)
    params["dt_bias"] = jnp.full_like(params["dt_bias"], 2.0)  # dt ~ 2
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 128, cfg.d_model))
    g = jax.grad(lambda prm: jnp.sum(mamba2.ssd_forward(prm, h, cfg)))(params)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.slow
def test_ssd_prefill_state_matches_decode_continuation():
    cfg = _cfg("mamba2-370m")
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = mamba2.ssd_init(p, cfg)
    B, S = 1, 32
    h = jax.random.normal(jax.random.PRNGKey(1), (B, S + 1, cfg.d_model)) * 0.5
    # full-sequence output at position S
    full = mamba2.ssd_forward(params, h, cfg)
    # prefill S tokens then decode one
    out, cache = mamba2.ssd_forward(params, h[:, :S], cfg, return_cache=True)
    step, _ = mamba2.ssd_decode(params, h[:, S:S + 1], cache, cfg)
    np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(full[:, S]),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_rglru_scan_matches_stepwise_decode():
    cfg = _cfg("recurrentgemma-9b")
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = rglru.rglru_init(p, cfg)
    B, S = 2, 24
    h = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model)) * 0.5
    full = rglru.rglru_forward(params, h, cfg)
    cache = rglru.lru_cache_init(cfg, B, jnp.float32)
    outs = []
    for t in range(S):
        o, cache = rglru.rglru_decode(params, h[:, t:t + 1], cache, cfg)
        outs.append(o)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               atol=2e-4, rtol=2e-4)


def test_rglru_prefill_cache_continues():
    cfg = _cfg("recurrentgemma-9b")
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = rglru.rglru_init(p, cfg)
    B, S = 1, 16
    h = jax.random.normal(jax.random.PRNGKey(2), (B, S + 1, cfg.d_model)) * 0.5
    full = rglru.rglru_forward(params, h, cfg)
    _, cache = rglru.rglru_forward(params, h[:, :S], cfg, return_cache=True)
    step, _ = rglru.rglru_decode(params, h[:, S:S + 1], cache, cfg)
    np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(full[:, S]),
                               atol=2e-4, rtol=2e-4)


def test_moe_forward_shapes_and_aux():
    cfg = _cfg("olmoe-1b-7b")
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = moe.moe_init(p, cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model)) * 0.5
    out, aux = moe.moe_forward(params, h, cfg)
    assert out.shape == h.shape
    assert float(aux["moe_lb_loss"]) >= 1.0 - 1e-3  # E * sum(me*ce) >= 1
    assert 0.0 <= float(aux["moe_drop_frac"]) < 0.5


def test_moe_capacity_drops_when_unbalanced():
    cfg = dataclasses.replace(_cfg("olmoe-1b-7b"), capacity_factor=0.5)
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = moe.moe_init(p, cfg)
    # bias router hard toward expert 0 -> must overflow capacity
    params["router"] = params["router"].at[:, 0].set(50.0)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    out, aux = moe.moe_forward(params, h, cfg)
    assert float(aux["moe_drop_frac"]) > 0.2


def test_moe_decode_matches_forward_when_no_drops():
    cfg = dataclasses.replace(_cfg("olmoe-1b-7b"), capacity_factor=8.0)
    p = common.ParamFactory("params", jax.random.PRNGKey(0), jnp.float32)
    params = moe.moe_init(p, cfg)
    h = jax.random.normal(jax.random.PRNGKey(2), (4, 1, cfg.d_model)) * 0.5
    # decode routes dropless; a capacity that drops nothing must agree
    dec, _ = moe.moe_dropless(params, h, cfg)
    fwd, aux = moe.moe_forward(params, h, cfg)
    assert float(aux["moe_drop_frac"]) == 0.0
    np.testing.assert_allclose(np.asarray(dec), np.asarray(fwd),
                               atol=1e-4, rtol=1e-4)


def test_ring_pack_kv_layout():
    S, L = 10, 4
    k = jnp.arange(S, dtype=jnp.float32).reshape(1, S, 1, 1)
    kp, _ = attention.ring_pack_kv(k, k, L)
    # slot s holds latest pos p <= 9 with p % 4 == s: [8, 9, 6, 7]
    np.testing.assert_array_equal(np.asarray(kp).reshape(-1), [8, 9, 6, 7])


@pytest.mark.parametrize("arch,full,cut", [
    ("gemma2-2b", 2304 ** 0.5, 128 ** 0.5),   # sqrt(d_model) follows d
    ("granite-4.0-h-small", 12.0, 12.0),       # a fixed multiplier stays
    ("mistral-large-123b", None, None),        # no multiplier
])
def test_reduced_keeps_the_embedding_multiplier_rule(arch, full, cut):
    cfg = configs.get(arch)
    assert cfg.emb_mult == full
    assert reduced(cfg).emb_mult == cut
